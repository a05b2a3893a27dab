"""Metrics, CPU model, baselines, pricing, data pipeline, workload gen, costs."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, cpu_model, metrics, pricing
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.workload.azure import WorkloadConfig, generate_trace
from repro.workload.functions import paper_functions
from repro.workload.trace import concat_traces, pad_trace


class TestMetrics:
    def test_cosine_bounds(self, rng):
        a = jnp.asarray(np.abs(rng.standard_normal(8)), jnp.float32)
        assert float(metrics.cosine_similarity(a, a)) == pytest.approx(1.0, abs=1e-6)
        assert float(metrics.cosine_similarity(a, 3.0 * a)) == pytest.approx(1.0, abs=1e-6)

    def test_individual_difference(self):
        d = metrics.individual_difference(jnp.asarray([11.0]), jnp.asarray([10.0]))
        assert float(d[0]) == pytest.approx(0.1)

    def test_total_power_error(self):
        w = jnp.asarray([100.0, 100.0])
        what = jnp.asarray([90.0, 110.0])
        assert float(metrics.total_power_error(w, what)) == pytest.approx(0.1)

    def test_marginal_energy(self):
        assert metrics.marginal_energy(1000.0, 800.0, 10) == pytest.approx(20.0)


class TestCpuModel:
    def test_ridge_recovery(self, rng):
        n, f = 200, 3
        x = np.abs(rng.standard_normal((n, f)))
        w_true = np.array([5.0, 2.0, 8.0])
        y = x @ w_true + 3.0
        m = cpu_model.fit_ridge(jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32))
        np.testing.assert_allclose(np.asarray(m.weights), w_true, rtol=1e-3)
        assert float(m.bias) == pytest.approx(3.0, rel=1e-2)

    def test_svr_close_to_ridge(self, rng):
        n, f = 300, 3
        x = np.abs(rng.standard_normal((n, f)))
        w_true = np.array([5.0, 2.0, 8.0])
        y = x @ w_true + 3.0 + rng.normal(0, 0.1, n)
        m = cpu_model.fit_linear_svr(
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), epsilon=0.2,
        )
        pred = cpu_model.predict_power(m, jnp.asarray(x, jnp.float32))
        rel = float(jnp.mean(jnp.abs(pred - jnp.asarray(y, jnp.float32)) / jnp.asarray(y, jnp.float32)))
        assert rel < 0.1, rel

    def test_retrain_trigger(self, rng):
        x = jnp.asarray(np.abs(rng.standard_normal((50, 2))), jnp.float32)
        y = x @ jnp.asarray([4.0, 1.0]) + 2.0
        m = cpu_model.fit_ridge(x, y)
        assert not cpu_model.needs_retrain(m, x, y)
        assert cpu_model.needs_retrain(m, x, y * 1.5)

    def test_function_power_sums_to_total(self, rng):
        """Per-function predictions with amortized bias sum ~ interval power."""
        m = cpu_model.LinearPowerModel(jnp.asarray([10.0, 5.0]), jnp.asarray(7.0))
        fn_feats = jnp.asarray([[0.6, 0.2], [0.4, 0.8]], jnp.float32)
        frac = jnp.asarray([0.5, 0.5])
        per_fn = cpu_model.predict_function_power(m, fn_feats, frac)
        total_feats = jnp.asarray([1.0, 1.0], jnp.float32)
        want = float(cpu_model.predict_power(m, total_feats))
        assert float(jnp.sum(per_fn)) == pytest.approx(want, rel=1e-5)


class TestBaselines:
    def test_direct_attribution_splits_evenly(self):
        act = jnp.asarray([[1.0, 1.0]] * 10)      # both always active
        chip = jnp.full((10,), 100.0)
        e = baselines.direct_attribution(act, chip, 0.1, jnp.asarray([1.0, 1.0]), jnp.asarray([1.0, 1.0]))
        np.testing.assert_allclose(np.asarray(e), [50.0, 50.0], rtol=1e-5)

    def test_model_only_ignores_measurement(self):
        c = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
        e = baselines.model_only_attribution(c, 1.0, jnp.asarray(30.0), jnp.asarray([1.0, 2.0]), jnp.asarray([1.0, 1.0]))
        np.testing.assert_allclose(np.asarray(e), [30.0, 60.0])


class TestPricing:
    def test_energy_price(self):
        p = pricing.energy_price_usd(jnp.asarray(3.6e6), 0.12)  # 1 kWh
        assert float(p) == pytest.approx(0.12)

    def test_report_keys(self, rng):
        r = pricing.price_report(
            jnp.ones(3), jnp.ones(3) * 2, jnp.ones(3), jnp.ones(3), jnp.ones(3)
        )
        assert set(r) == {"indiv_usd_per_inv", "total_usd_per_inv", "carbon_g_per_inv", "latency_usd_per_inv"}
        assert np.all(np.asarray(r["total_usd_per_inv"]) >= np.asarray(r["indiv_usd_per_inv"]))


class TestDataPipeline:
    def test_determinism_and_seek(self):
        from repro.configs.registry import get_config
        from repro.configs.shapes import ShapeConfig
        from repro.models import build

        api = build(get_config("internlm2-1.8b", reduced=True))
        shape = ShapeConfig("t", 16, 2, "train")
        b1 = synthetic_batch(api, shape, 5, DataConfig(seed=3))
        b2 = synthetic_batch(api, shape, 5, DataConfig(seed=3))
        b3 = synthetic_batch(api, shape, 6, DataConfig(seed=3))
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert not np.array_equal(b1["tokens"], b3["tokens"])

    def test_labels_are_shifted_tokens(self):
        from repro.configs.registry import get_config
        from repro.configs.shapes import ShapeConfig
        from repro.models import build

        api = build(get_config("internlm2-1.8b", reduced=True))
        b = synthetic_batch(api, ShapeConfig("t", 16, 2, "train"), 0)
        np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
        assert np.all(b["labels"][:, -1] == -1)


class TestWorkload:
    def test_trace_bounds(self, registry):
        t = generate_trace(registry, WorkloadConfig(duration_s=120.0, seed=1))
        valid = t.fn_id >= 0
        assert np.all(t.start[valid] >= 0)
        assert np.all(t.end[valid] <= 120.0 + 1e-3)
        assert np.all(t.end[valid] >= t.start[valid])
        assert t.num_invocations > 10

    def test_load_scales_invocations(self, registry):
        lo = generate_trace(registry, WorkloadConfig(duration_s=300.0, load=0.5, seed=2))
        hi = generate_trace(registry, WorkloadConfig(duration_s=300.0, load=2.0, seed=2))
        assert hi.num_invocations > 1.5 * lo.num_invocations

    def test_closed_loop_no_self_overlap(self, registry):
        t = generate_trace(registry, WorkloadConfig(duration_s=60.0, arrival="closed", seed=3))
        for j in range(t.num_fns):
            mask = t.fn_id == j
            starts, ends = t.start[mask], t.end[mask]
            order = np.argsort(starts)
            assert np.all(starts[order][1:] >= ends[order][:-1] - 1e-4)

    def test_concat_and_pad(self, registry):
        a = generate_trace(registry, WorkloadConfig(duration_s=30.0, seed=4))
        b = generate_trace(registry, WorkloadConfig(duration_s=30.0, seed=5))
        c = concat_traces(a, b, gap=5.0)
        assert c.duration == 65.0
        assert c.num_invocations == a.num_invocations + b.num_invocations
        p = pad_trace(a, 1024)
        assert p.fn_id.shape[0] % 1024 == 0
        assert p.num_invocations == a.num_invocations


class TestCosts:
    def test_dense_forward_close_to_2nd(self):
        """Analytic forward ~ 2*N*D + attention for dense archs."""
        from repro.configs.registry import get_config
        from repro.configs.shapes import TRAIN_4K
        from repro.launch.costs import forward_flops

        cfg = get_config("granite-3-8b")
        fwd = forward_flops(cfg, TRAIN_4K)["total"]
        two_nd = 2.0 * cfg.param_count() * TRAIN_4K.global_batch * TRAIN_4K.seq_len
        assert 0.9 < fwd / two_nd < 1.5, fwd / two_nd

    def test_cost_model_vs_compiled_unrolled(self):
        """Validate against XLA cost_analysis on a tiny LOOP-FREE model."""
        import jax
        import jax.numpy as jnp

        d, f, s, b = 64, 256, 128, 4

        def mlp_fwd(w1, w2, x):
            return jnp.tanh(x @ w1) @ w2

        lo = jax.jit(mlp_fwd).lower(
            jax.ShapeDtypeStruct((d, f), jnp.float32),
            jax.ShapeDtypeStruct((f, d), jnp.float32),
            jax.ShapeDtypeStruct((b, s, d), jnp.float32),
        )
        ca = lo.compile().cost_analysis()
        if isinstance(ca, list):  # older jax returns [dict], newer a dict
            ca = ca[0]
        got = ca["flops"]
        want = 2 * b * s * d * f * 2
        assert 0.9 < got / want < 1.2, (got, want)

    def test_step_cost_decode_memory_dominated(self):
        from repro.configs.registry import get_config
        from repro.configs.shapes import DECODE_32K
        from repro.launch.costs import step_cost

        c = step_cost(get_config("granite-3-8b"), DECODE_32K)
        # decode arithmetic intensity << machine balance: bytes dominate
        intensity = c.flops / c.hbm_bytes
        assert intensity < 240  # v5e balance ~ 197e12/819e9 ~ 240


class TestCompileCache:
    """``repro.compile_cache``: where the entry points keep compiled code."""

    def test_env_dir_receives_the_cache(self, tmp_path):
        import os
        import pathlib
        import subprocess
        import sys

        src = pathlib.Path(__file__).resolve().parents[1] / "src"
        env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
               "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(src)}
        code = (
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.block_until_ready(jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(3)))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=120, check=True,
        )
        assert out.stdout.strip() == str(tmp_path)
        assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())

    def test_default_dir_is_fixed_and_ignored(self, monkeypatch):
        import pathlib

        import jax

        from repro.compile_cache import enable_compile_cache

        root = pathlib.Path(__file__).resolve().parents[1]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        saved = (
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
        )
        try:
            got = enable_compile_cache()
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        finally:
            jax.config.update("jax_compilation_cache_dir", saved[0])
            jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
        assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
