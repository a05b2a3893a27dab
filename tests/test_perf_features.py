"""Beyond-paper perf features: int8 KV cache, chunked CE, ZeRO-3 rules,
cache extension, int8 a2a quantizer — accuracy and invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.configs.shapes import ShapeConfig
from repro.models import build
from repro.models.common import materialize
from repro.models.model_zoo import extend_cache

SMOKE = ShapeConfig("s", 64, 2, "train")


class TestInt8KV:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = dataclasses.replace(
            get_config("granite-3-8b", reduced=True), compute_dtype="float32"
        )
        cfg_q = dataclasses.replace(cfg, kv_cache_dtype="int8")
        api, api_q = build(cfg), build(cfg_q)
        params = materialize(api.params_def, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)
        return cfg, api, api_q, params, toks, rng

    def test_decode_accuracy_vs_bf16_cache(self, setup):
        cfg, api, api_q, params, toks, rng = setup
        _, cache = jax.jit(api.prefill)(params, {"tokens": toks})
        _, cache_q = jax.jit(api_q.prefill)(params, {"tokens": toks})
        cache = extend_cache(api, cache, 4)
        cache_q = extend_cache(api_q, cache_q, 4)
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 1)), jnp.int32)
        d1, _ = jax.jit(api.decode)(params, cache, tok, jnp.asarray(64, jnp.int32))
        d2, _ = jax.jit(api_q.decode)(params, cache_q, tok, jnp.asarray(64, jnp.int32))
        cos = float(jnp.sum(d1 * d2) / (jnp.linalg.norm(d1) * jnp.linalg.norm(d2)))
        assert cos > 0.999, cos
        assert jnp.array_equal(jnp.argmax(d1[:, -1], -1), jnp.argmax(d2[:, -1], -1))

    def test_quantize_kv_roundtrip(self, rng):
        from repro.kernels.ref import quantize_kv

        x = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
        q, s = quantize_kv(x)
        assert q.dtype == jnp.int8
        back = q.astype(jnp.float32) * s.astype(jnp.float32)[..., None]
        # 0.5-LSB quantization error + bf16 rounding of the scale (~0.4 %)
        bound = float(jnp.max(s.astype(jnp.float32))) * 0.51 + 0.01 * float(jnp.max(jnp.abs(x)))
        assert float(jnp.max(jnp.abs(back - x))) <= bound

    def test_cache_spec_matches_prefill_int8(self, setup):
        cfg, api, api_q, params, toks, rng = setup
        _, cache_q = jax.jit(api_q.prefill)(params, {"tokens": toks})
        spec = api_q.cache_spec(SMOKE)
        assert cache_q["k"].dtype == jnp.int8
        assert set(cache_q) == set(spec)
        for name in spec:
            assert tuple(cache_q[name].shape) == tuple(spec[name].shape), name


class TestChunkedCE:
    def test_exact_vs_full(self, rng):
        cfg = get_config("internlm2-1.8b", reduced=True)
        cfg_c = dataclasses.replace(cfg, ce_chunk=16)
        api, api_c = build(cfg), build(cfg_c)
        params = materialize(api.params_def, jax.random.PRNGKey(0))
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)
        labels = jnp.concatenate([toks[:, 1:], jnp.full((2, 1), -1, jnp.int32)], 1)
        batch = {"tokens": toks, "labels": labels}
        l1, _ = jax.jit(api.loss)(params, batch)
        l2, _ = jax.jit(api_c.loss)(params, batch)
        assert abs(float(l1) - float(l2)) < 1e-3

    def test_exact_gradients(self, rng):
        cfg = get_config("internlm2-1.8b", reduced=True)
        cfg_c = dataclasses.replace(cfg, ce_chunk=16)
        api, api_c = build(cfg), build(cfg_c)
        params = materialize(api.params_def, jax.random.PRNGKey(0))
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)
        labels = jnp.concatenate([toks[:, 1:], jnp.full((2, 1), -1, jnp.int32)], 1)
        batch = {"tokens": toks, "labels": labels}
        g1 = jax.grad(lambda p: api.loss(p, batch)[0])(params)
        g2 = jax.grad(lambda p: api_c.loss(p, batch)[0])(params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)

    def test_ragged_tail_padding(self, rng):
        from repro.models.common import chunked_lm_loss, cross_entropy_loss

        h = jnp.asarray(rng.standard_normal((2, 50, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 60, (2, 50)), jnp.int32)
        l1, _ = chunked_lm_loss(h, w, labels, 60, chunk=16)  # 50 % 16 != 0
        logits = jnp.einsum("bsd,dv->bsv", h, w)
        l2, _ = cross_entropy_loss(logits, labels, 60)
        assert abs(float(l1) - float(l2)) < 1e-5


class TestZero3Rules:
    def test_batch_takes_both_axes(self):
        from jax.sharding import AbstractMesh, PartitionSpec as P

        from repro.distributed.sharding import ZERO3_RULES, spec_for

        mesh = AbstractMesh((16, 16), ("data", "model"))
        assert spec_for(("batch", None), (256, 128), mesh, ZERO3_RULES) == P(("data", "model"))
        # TP axes replicate
        assert spec_for(("embed", "qkv"), (4096, 4096), mesh, ZERO3_RULES) == P(("data", "model"))
        # embed table: vocab replicated, embed dim 256-way
        assert spec_for(("vocab", "embed"), (50176, 4096), mesh, ZERO3_RULES) == P(None, ("data", "model"))
        # unembed: lm_head sharded, embed replicated (axes consumed)
        assert spec_for(("embed", "lm_head"), (4096, 50176), mesh, ZERO3_RULES) == P(None, ("data", "model"))

    def test_ep_rules_reserve_model_for_experts(self):
        from jax.sharding import AbstractMesh, PartitionSpec as P

        from repro.distributed.sharding import EP_RULES, spec_for

        mesh = AbstractMesh((16, 16), ("data", "model"))
        assert spec_for(("expert", "embed", "expert_mlp"), (64, 2048, 1408), mesh, EP_RULES) == P("model", "data")
        assert spec_for(("embed", "qkv"), (2048, 2048), mesh, EP_RULES) == P("data")


class TestExtendCache:
    @pytest.mark.parametrize("arch", ["granite-3-8b", "zamba2-7b", "xlstm-350m", "seamless-m4t-large-v2"])
    def test_growable_axes(self, arch, rng):
        cfg = get_config(arch, reduced=True)
        api = build(cfg)
        params = materialize(api.params_def, jax.random.PRNGKey(0))
        batch = {}
        for k, sp in api.prefill_inputs(SMOKE).items():
            if np.issubdtype(np.dtype(sp.dtype), np.integer):
                batch[k] = jnp.asarray(rng.integers(0, cfg.vocab_size, sp.shape), jnp.int32)
            else:
                batch[k] = jnp.asarray(rng.standard_normal(sp.shape) * 0.1, sp.dtype)
        _, cache = jax.jit(api.prefill)(params, batch)
        grown = extend_cache(api, cache, 7)
        from repro.models.model_zoo import _GROWABLE

        for name, axis in _GROWABLE[cfg.family].items():
            if name in cache:
                assert grown[name].shape[axis] == cache[name].shape[axis] + 7


class TestInt8A2AQuantizer:
    def test_row_quantization_error_bound(self, rng):
        from repro.models.moe import _q_a2a  # noqa: F401  (quantize path)
        # direct quantize/dequant property without the collective
        x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        back = q.astype(jnp.float32) * scale
        assert float(jnp.max(jnp.abs(back - x) / jnp.maximum(amax, 1e-8))) <= 0.5 / 127.0 + 1e-6
