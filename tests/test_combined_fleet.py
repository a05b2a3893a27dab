"""Combined mode (§4.3) across all three fleet engines + CPU-model fixes.

The combined model splits a node's power into a chip side — attributed by
the linear counter model (SmartWatts/PowerAPI-style) — and a 'rest' side
disaggregated by the Kalman/Shapley engine over the chip-subtracted target
``max(W_sys - W_chip - rest_idle, 0)``.  This suite pins:

- the per-node ``profile()`` combined oracle == ``fleet_profile_batched``
  == ``StreamingFleetSession`` == the sharded runners (1-, 2-, 8-device
  meshes), dense *and* ragged, with ``sync_max_shift=0`` so the one
  documented streaming difference (init-window skew estimation) is out of
  the picture;
- combined-mode conservation per tick (rest side: attributed +
  unattributed + chip + rest_idle reproduces the measured system power on
  unclamped ticks; chip side: per-function X_CPU + un-attributed bias
  reproduces the model total — including *idle* intervals, the bias
  bugfix);
- the CPU-model correctness fixes: ``fit_ridge`` on badly-scaled float32
  counter features (standardized solve), the idle-interval bias routing,
  and ``_rest_idle``'s consistent slicing (telemetry longer than the
  segment must not change the estimate);
- retrain-signal plumbing on the streaming session and the chip/rest
  split through ``fleet_attribution_totals``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cpu_model as cpumod
from repro.core.batched_engine import (
    EngineConfig,
    combined_rest_target,
    fleet_rest_idle,
    run_fleet,
    synthetic_fleet,
)
from repro.core.profiler import (
    FaasMeterProfiler,
    ProfilerConfig,
    Telemetry,
    fleet_profile_batched,
    prepare_combined_fleet,
)
from repro.distributed.sharding import fleet_attribution_totals, fleet_mesh
from repro.telemetry.counters import function_counters, window_counters

#: Mesh sizes above one device: the ``multidevice`` params (tests/conftest.py).
MULTI = [pytest.param(k, marks=pytest.mark.multidevice) for k in (2, 8)]

#: sync_max_shift=0 pins the skew estimate to 0.0 on every path, so the
#: combined pins are not polluted by the (documented, pure-mode-tested)
#: init-vs-full-segment skew estimation difference of the streaming session.
PCFG = ProfilerConfig(
    init_windows=60, step_windows=30, mode="combined", sync_max_shift=0
)


def _fleet_fixture(b=2, durations=None, platform="desktop", seeds=None):
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    durations = [150.0] * b if durations is None else durations
    seeds = list(range(1, b + 1)) if seeds is None else seeds
    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform=platform))
    profiler = FaasMeterProfiler(PCFG)
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=s))
        for d, s in zip(durations, seeds)
    ]
    sims = sim.simulate_fleet(traces, seeds=[10 + s for s in seeds])
    tels = [s.telemetry for s in sims]
    arrays = [
        (jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
        for t in traces
    ]
    specs = reg.specs
    counters = prepare_combined_fleet(
        profiler.config, arrays, tels,
        num_fns=traces[0].num_fns,
        duration=durations if len(set(durations)) > 1 else durations[0],
        gflops=np.asarray([s.gflops for s in specs]),
        hbm_gb=np.asarray([s.hbm_gb for s in specs]),
        mean_latency=np.asarray([max(s.mean_latency_s, 1e-3) for s in specs]),
    )
    return reg, profiler, traces, tels, arrays, counters


def _solo_reports(profiler, arrays, tels, num_fns, durations, counters):
    fnc, _, models = counters
    return [
        profiler.profile(
            *arrays[i], num_fns=num_fns, duration=durations[i],
            telemetry=tels[i], fn_counters=fnc[i],
            counter_model=cpumod.model_row(models, i),
        )
        for i in range(len(arrays))
    ]


def _run_session(profiler, arrays, tels, counters, *, num_fns, duration, mesh=None):
    fnc, wf, models = counters
    sess = profiler.start_fleet_stream(
        arrays, num_fns=num_fns, duration=duration,
        idle_watts=[t.idle_watts for t in tels],
        has_chip=True, has_cp=tels[0].cp_cpu_frac is not None,
        fn_counters=fnc, counter_model=models, window_features=wf,
        mesh=mesh,
    )
    durs = duration if np.ndim(duration) else [duration] * len(arrays)
    n_max = int(round(max(durs)))

    def col(get, tel, t):
        arr = np.asarray(get(tel))
        return arr[t] if t < arr.shape[0] else 0.0

    for t in range(n_max):
        sess.push_window(
            w_sys=np.asarray([col(lambda x: x.system_power, tel, t) for tel in tels]),
            w_chip=np.asarray([col(lambda x: x.chip_power, tel, t) for tel in tels]),
            cp_frac=np.asarray([col(lambda x: x.cp_cpu_frac, tel, t) for tel in tels]),
            sys_frac=np.asarray([col(lambda x: x.sys_cpu_frac, tel, t) for tel in tels]),
        )
    return sess, sess.finalize()


def _assert_reports_close(got, want, *, atol=1e-4, tag=""):
    np.testing.assert_allclose(
        np.asarray(got.x_power), np.asarray(want.x_power),
        rtol=1e-5, atol=atol, err_msg=f"{tag} x_power",
    )
    assert got.total_error == pytest.approx(want.total_error, abs=1e-4), tag
    np.testing.assert_allclose(
        np.asarray(got.spectrum.j_total), np.asarray(want.spectrum.j_total),
        rtol=1e-4, atol=1e-2, err_msg=f"{tag} j_total",
    )
    assert got.idle_energy == pytest.approx(want.idle_energy), tag
    assert got.skew_windows == want.skew_windows == 0.0, tag


# ---------------------------------------------------------------------------
# CPU-model correctness fixes.
# ---------------------------------------------------------------------------


def test_fit_ridge_survives_badly_scaled_counters():
    """Regression for the float32 normal-equation conditioning bug: the
    counter scales window_counters emits (GFLOP/s up to ~5e4 for the arch
    classes vs duty cycle <= 1) made the raw-space gram ill-conditioned;
    the standardized solve must fit to ~1e-4 relative."""
    rng = np.random.default_rng(0)
    n = 120
    busy = rng.random(n) * 0.9  # one latent activity drives every counter
    gflop = busy * 46800.0 + rng.random(n) * 500.0
    hbm = busy * 160.0 + rng.random(n) * 3.0
    x = np.stack([gflop, hbm, busy], axis=1)
    y = x @ np.array([0.001, 0.2, 55.0]) + 40.0
    m = cpumod.fit_ridge(jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32))
    pred = np.asarray(cpumod.predict_power(m, jnp.asarray(x, jnp.float32)))
    assert float(np.max(np.abs(pred - y) / y)) < 2e-4  # raw-space solve: ~6e-4


def test_fit_ridge_batched_matches_per_node():
    rng = np.random.default_rng(1)
    x = np.abs(rng.standard_normal((3, 50, 3))) * np.array([1e3, 40.0, 0.5])
    w = np.abs(rng.standard_normal((3, 3))) + 0.1
    y = np.einsum("bnf,bf->bn", x, w) + 25.0
    xb = jnp.asarray(x, jnp.float32)
    yb = jnp.asarray(y, jnp.float32)
    mb = cpumod.fit_ridge(xb, yb)
    assert mb.weights.shape == (3, 3) and mb.bias.shape == (3,)
    for i in range(3):
        mi = cpumod.fit_ridge(xb[i], yb[i])
        np.testing.assert_allclose(
            np.asarray(cpumod.model_row(mb, i).weights), np.asarray(mi.weights),
            rtol=1e-4,  # vmapped solve reassociates the gram contraction
        )
    # batched error signal: one scalar per node, traceable flags
    err = cpumod.model_error(mb, xb, yb)
    assert err.shape == (3,) and float(jnp.max(err)) < 0.01
    assert not bool(jnp.any(cpumod.retrain_flags(mb, xb, yb)))
    assert bool(jnp.all(cpumod.retrain_flags(mb, xb, yb * 1.5)))


def test_idle_interval_bias_is_routed_not_dropped():
    """Regression for the silent bias drop: with sum(fn_active_frac) ~ 0
    the static chip power must come back as the residual, and the
    chip-side split must conserve the model total either way."""
    m = cpumod.LinearPowerModel(jnp.asarray([10.0, 5.0]), jnp.asarray(7.0))
    fn_feats = jnp.asarray([[0.6, 0.2], [0.4, 0.8]], jnp.float32)
    # active interval: bias fully amortized, residual zero
    per_fn, resid = cpumod.predict_function_power_split(
        m, fn_feats, jnp.asarray([0.5, 0.5])
    )
    total = float(cpumod.predict_power(m, jnp.sum(fn_feats, axis=0)))
    assert float(resid) == 0.0
    assert float(jnp.sum(per_fn)) == pytest.approx(total, rel=1e-5)
    # idle interval: nothing ran, the bias must not vanish
    per_fn0, resid0 = cpumod.predict_function_power_split(
        m, jnp.zeros_like(fn_feats), jnp.zeros(2)
    )
    assert float(jnp.max(jnp.abs(per_fn0))) == 0.0
    assert float(resid0) == pytest.approx(float(m.bias))
    assert float(jnp.sum(per_fn0) + resid0) == pytest.approx(
        float(cpumod.predict_power(m, jnp.zeros(2)))
    )
    # fleet-batched: one idle node among active ones
    mb = cpumod.stack_models([m, m])
    fb = jnp.stack([fn_feats, jnp.zeros_like(fn_feats)])
    frb = jnp.asarray([[0.5, 0.5], [0.0, 0.0]])
    pf, rs = cpumod.predict_function_power_split(mb, fb, frb)
    np.testing.assert_allclose(np.asarray(rs), [0.0, 7.0], atol=1e-6)
    assert float(jnp.sum(pf[1])) == 0.0


def test_idle_segment_report_conserves_chip_bias():
    """An (almost) idle segment through the combined profiler: the
    un-attributed static chip bias lands in the report's idle energy
    instead of disappearing from the accounting."""
    profiler = FaasMeterProfiler(PCFG)
    n = 120
    rng = np.random.default_rng(3)
    chip = jnp.asarray(30.0 + 0.1 * rng.random(n), jnp.float32)
    tel = Telemetry(
        system_power=jnp.asarray(80.0 + 0.1 * rng.random(n), jnp.float32),
        chip_power=chip,
        idle_watts=78.0,
        cp_cpu_frac=None,
        sys_cpu_frac=None,
    )
    # no invocations at all -> zero counters, zero active fraction
    fn_id = jnp.asarray([-1], jnp.int32)
    start = end = jnp.asarray([0.0], jnp.float32)
    model = cpumod.LinearPowerModel(jnp.asarray([1.0, 1.0, 1.0]), jnp.asarray(12.5))
    report = profiler.profile(
        fn_id, start, end, num_fns=3, duration=float(n), telemetry=tel,
        fn_counters=jnp.zeros((3, 3)), counter_model=model,
    )
    assert float(jnp.max(jnp.abs(report.x_power))) == pytest.approx(0.0, abs=1e-6)
    # idle energy = platform idle + the counter model's un-attributed bias
    assert report.idle_energy == pytest.approx((78.0 + 12.5) * n)


def test_rest_idle_ignores_telemetry_past_the_segment():
    """Regression for the full-array jnp.min: chip telemetry longer than
    the profiled segment (with a lower floor in the tail) must not change
    the combined target or the report."""
    profiler = FaasMeterProfiler(PCFG)
    rng = np.random.default_rng(4)
    n = 100
    base_chip = 40.0 + 5.0 * rng.random(n + 60).astype(np.float32)
    sys_p = 120.0 + 10.0 * rng.random(n + 60).astype(np.float32)
    fn_id = jnp.asarray(np.zeros(40), jnp.int32)
    start = jnp.asarray(np.linspace(1.0, 90.0, 40), jnp.float32)
    end = start + 1.5

    def report_for(chip_tail):
        chip = base_chip.copy()
        chip[n:] = chip_tail  # beyond the segment
        tel = Telemetry(
            system_power=jnp.asarray(sys_p),
            chip_power=jnp.asarray(chip),
            idle_watts=95.0,
            cp_cpu_frac=None,
            sys_cpu_frac=None,
        )
        fnc = jnp.asarray(np.eye(2, 3, dtype=np.float32))
        model = cpumod.LinearPowerModel(jnp.asarray([1.0, 1.0, 1.0]), jnp.asarray(5.0))
        return profiler.profile(
            fn_id, start, end, num_fns=2, duration=float(n), telemetry=tel,
            fn_counters=fnc, counter_model=model,
        )

    r_hi = report_for(chip_tail=60.0)
    r_lo = report_for(chip_tail=1.0)  # pre-fix: drags the chip floor down
    np.testing.assert_array_equal(np.asarray(r_hi.x_power), np.asarray(r_lo.x_power))
    assert r_hi.total_error == r_lo.total_error


def test_rest_idle_is_traceable():
    """No float()/host sync: _rest_idle must stay a traced value so the
    batched/jitted paths never block on it."""
    profiler = FaasMeterProfiler(PCFG)
    tel = Telemetry(
        system_power=jnp.ones(50) * 100.0,
        chip_power=jnp.ones(50) * 30.0,
        idle_watts=80.0,
        cp_cpu_frac=None,
        sys_cpu_frac=None,
    )

    @jax.jit
    def traced(chip):
        t = tel._replace(chip_power=chip)
        return profiler._target_signal(jnp.ones(50) * 100.0, t, 50)

    out = traced(tel.chip_power)  # would raise TracerConversionError pre-fix
    np.testing.assert_allclose(np.asarray(out), 100.0 - 30.0 - 50.0, atol=1e-6)
    assert isinstance(profiler._rest_idle(tel, 50), jax.Array)


# ---------------------------------------------------------------------------
# Fleet-shaped counters.
# ---------------------------------------------------------------------------


def test_counters_fleet_shape_matches_per_node_and_masks_junk():
    rng = np.random.default_rng(5)
    b, n, m = 4, 30, 5
    c = rng.random((b, n, m))
    gf = np.abs(rng.standard_normal(m)) + 0.5
    hb = np.abs(rng.standard_normal(m)) * 0.2
    lat = np.abs(rng.standard_normal(m)) + 0.1
    wf = window_counters(c, gf, hb, lat, 1.0)
    fc = function_counters(c, gf, hb, lat)
    assert wf.shape == (b, n, 3) and fc.shape == (b, m, 3)
    for i in range(b):
        np.testing.assert_allclose(
            np.asarray(wf[i]), np.asarray(window_counters(c[i], gf, hb, lat, 1.0)),
            rtol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(fc[i]), np.asarray(function_counters(c[i], gf, hb, lat)),
            rtol=1e-6,
        )
        # per-node normalization: each node's totals sum to one
        np.testing.assert_allclose(np.asarray(fc[i].sum(axis=0)), 1.0, rtol=1e-5)
    # ragged: junk past a node's real windows must be erased exactly
    lengths = [n, 12, 20, 7]
    junk = c.copy()
    mask = np.zeros((b, n), np.float32)
    for i, li in enumerate(lengths):
        junk[i, li:] = 777.0
        mask[i, :li] = 1.0
    wf_m = window_counters(junk, gf, hb, lat, 1.0, mask=mask)
    fc_m = function_counters(junk, gf, hb, lat, mask=mask)
    for i, li in enumerate(lengths):
        if li < n:
            assert float(jnp.max(jnp.abs(wf_m[i, li:]))) == 0.0
        np.testing.assert_allclose(
            np.asarray(fc_m[i]),
            np.asarray(function_counters(c[i, :li], gf, hb, lat)),
            rtol=1e-6,
        )


# ---------------------------------------------------------------------------
# Engine-level combined conservation.
# ---------------------------------------------------------------------------


def test_combined_target_conserves_per_tick():
    """Rest-side conservation through the engine: attributed + unattributed
    == the combined target on every tick, and target + chip + rest_idle
    reconstructs the measured system power wherever the relu clamp is
    inactive.  Padded (masked) ticks contribute exactly zero."""
    b, s, n_w, m = 3, 3, 10, 6
    inputs = synthetic_fleet(b, s, n_w, m, seed=7, density=0.3)
    rng = np.random.default_rng(8)
    chip = jnp.asarray(
        35.0 + 5.0 * rng.random((b, s * n_w)), jnp.float32
    )
    idle = jnp.asarray([90.0, 85.0, 95.0])
    rest_idle = fleet_rest_idle(chip[:, :20], idle)
    assert rest_idle.shape == (b,)
    np.testing.assert_allclose(
        np.asarray(rest_idle),
        np.maximum(np.asarray(idle) - np.asarray(chip[:, :20]).min(-1), 0.0),
    )
    # measured system = rest + chip + rest_idle by construction: the relu
    # clamp is inactive everywhere and the window identity is exact.
    w_sys = inputs.w.reshape(b, -1) + chip + rest_idle[:, None]
    target = combined_rest_target(w_sys, chip, rest_idle[:, None])
    np.testing.assert_allclose(
        np.asarray(target) + np.asarray(chip) + np.asarray(rest_idle)[:, None],
        np.asarray(w_sys),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(target), np.asarray(inputs.w).reshape(b, -1), atol=1e-4
    )
    out = run_fleet(
        inputs._replace(w=target.reshape(b, s, n_w)), EngineConfig()
    )
    recon = np.asarray(out.tick_power).sum(-1) + np.asarray(out.unattributed)
    np.testing.assert_allclose(recon, np.asarray(target), atol=1e-3)


def test_combined_report_conserves_energy_per_window():
    """Profiler-level conservation: the combined reconstruction offset is
    the measured chip series + rest idle, so W_hat = C X_rest + chip +
    rest_idle tracks the synchronized system signal (total_error is the
    normalized residual and must stay small on a clean platform)."""
    _, profiler, traces, tels, arrays, counters = _fleet_fixture(b=1)
    solo = _solo_reports(
        profiler, arrays, tels, traces[0].num_fns, [150.0], counters
    )[0]
    assert solo.total_error < 0.3


# ---------------------------------------------------------------------------
# The acceptance pins: oracle == batched == streaming == sharded.
# ---------------------------------------------------------------------------


def test_combined_batched_matches_per_node_oracle():
    _, profiler, traces, tels, arrays, counters = _fleet_fixture(b=3)
    num_fns = traces[0].num_fns
    fnc, _, models = counters
    solo = _solo_reports(profiler, arrays, tels, num_fns, [150.0] * 3, counters)
    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=150.0,
        fn_counters=fnc, counter_model=models,
    )
    for i, (rb, rs) in enumerate(zip(batched, solo)):
        _assert_reports_close(rb, rs, tag=f"node {i} batched-vs-oracle")


def test_combined_streaming_matches_batched_bitwise_class():
    """The streaming session sees identical targets (skew pinned to 0,
    rest idle from the same init block), so it pins to the batched path
    at engine tolerance and to the per-node oracle at 1e-5 class."""
    _, profiler, traces, tels, arrays, counters = _fleet_fixture(b=2)
    num_fns = traces[0].num_fns
    fnc, _, models = counters
    solo = _solo_reports(profiler, arrays, tels, num_fns, [150.0] * 2, counters)
    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=150.0,
        fn_counters=fnc, counter_model=models,
    )
    _, streamed = _run_session(
        profiler, arrays, tels, counters, num_fns=num_fns, duration=150.0
    )
    for i in range(2):
        np.testing.assert_allclose(
            np.asarray(streamed[i].x_power), np.asarray(batched[i].x_power),
            rtol=1e-5, atol=1e-5, err_msg=f"node {i} stream-vs-batched",
        )
        assert streamed[i].total_error == pytest.approx(
            batched[i].total_error, abs=1e-5
        )
        _assert_reports_close(streamed[i], solo[i], tag=f"node {i} stream-vs-oracle")


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
@pytest.mark.parametrize("k", [1, *MULTI])
def test_combined_sharded_matches_oracle(k, ragged):
    """fleet_profile_batched + the streaming session under a 1-, 2-, or
    8-device FleetMesh reproduce the per-node combined oracle — on dense
    and on ragged (per-node duration) fleets alike."""
    if k > len(jax.devices()):
        pytest.skip(f"needs {k} devices")
    b = max(k, 2)
    durs = (
        [(150.0, 100.0, 125.0, 65.0)[i % 4] for i in range(b)]
        if ragged
        else [150.0] * b
    )
    _, profiler, traces, tels, arrays, counters = _fleet_fixture(
        b=b, durations=durs
    )
    num_fns = traces[0].num_fns
    fnc, _, models = counters
    fm = fleet_mesh(devices=jax.devices()[:k])
    duration = durs if ragged else durs[0]
    solo = _solo_reports(profiler, arrays, tels, num_fns, durs, counters)
    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=duration,
        fn_counters=fnc, counter_model=models, mesh=fm,
    )
    _, streamed = _run_session(
        profiler, arrays, tels, counters, num_fns=num_fns, duration=duration,
        mesh=fm,
    )
    for i in range(b):
        _assert_reports_close(batched[i], solo[i], tag=f"node {i} sharded-batched")
        _assert_reports_close(streamed[i], solo[i], tag=f"node {i} sharded-stream")


def test_combined_ragged_fleet_matches_per_node():
    """Ragged fleet in combined mode: per-node durations, every node still
    reproducing its solo combined report — including the zero-post-init
    node whose trajectory is just X_0."""
    durs = [150.0, 100.0, 65.0]
    _, profiler, traces, tels, arrays, counters = _fleet_fixture(
        b=3, durations=durs
    )
    num_fns = traces[0].num_fns
    fnc, _, models = counters
    solo = _solo_reports(profiler, arrays, tels, num_fns, durs, counters)
    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=durs,
        fn_counters=fnc, counter_model=models,
    )
    _, streamed = _run_session(
        profiler, arrays, tels, counters, num_fns=num_fns, duration=durs
    )
    assert solo[2].x_trajectory.shape[0] == 1  # 65 s: init-only node
    for i in range(3):
        _assert_reports_close(batched[i], solo[i], tag=f"ragged node {i} batched")
        _assert_reports_close(streamed[i], solo[i], tag=f"ragged node {i} stream")
        assert batched[i].x_trajectory.shape == solo[i].x_trajectory.shape


# ---------------------------------------------------------------------------
# Streaming retrain plumbing.
# ---------------------------------------------------------------------------


def test_streaming_retrain_signal_plumbing():
    """The session scores every node's counter model at each Kalman-step
    boundary: a healthy model stays un-flagged under a loose threshold, a
    corrupted model must flag every node, and the error history covers
    every completed step."""
    _, profiler, traces, tels, arrays, counters = _fleet_fixture(b=2)
    num_fns = traces[0].num_fns
    fnc, wf, models = counters

    def run(model, threshold):
        sess = profiler.start_fleet_stream(
            arrays, num_fns=num_fns, duration=150.0,
            idle_watts=[t.idle_watts for t in tels],
            has_chip=True, has_cp=True,
            fn_counters=fnc, counter_model=model, window_features=wf,
            retrain_config=cpumod.CpuModelConfig(retrain_threshold=threshold),
        )
        for t in range(150):
            sess.push_window(
                w_sys=np.asarray([np.asarray(tel.system_power)[t] for tel in tels]),
                w_chip=np.asarray([np.asarray(tel.chip_power)[t] for tel in tels]),
                cp_frac=np.asarray([np.asarray(tel.cp_cpu_frac)[t] for tel in tels]),
                sys_frac=np.asarray([np.asarray(tel.sys_cpu_frac)[t] for tel in tels]),
            )
        sess.finalize()
        return sess

    healthy = run(models, threshold=0.25)
    assert len(healthy.model_errors) == 3  # (150 - 60) / 30 completed steps
    assert not healthy.retrain_needed.any()
    assert float(np.stack(healthy.model_errors).max()) < 0.25

    # drift: a model whose bias is way off must trip the 5 % default
    broken = cpumod.LinearPowerModel(
        weights=models.weights, bias=models.bias + 50.0
    )
    drifted = run(broken, threshold=0.05)
    assert drifted.retrain_needed.all()
    # the errors the flags were derived from are exposed per step
    assert all(e.shape == (2,) for e in drifted.model_errors)


def test_session_rejects_missing_combined_inputs():
    _, profiler, traces, tels, arrays, counters = _fleet_fixture(b=2)
    num_fns = traces[0].num_fns
    with pytest.raises(ValueError, match="fn_counters"):
        profiler.start_fleet_stream(
            arrays, num_fns=num_fns, duration=150.0,
            idle_watts=[t.idle_watts for t in tels],
            has_chip=True, has_cp=True,
        )
    with pytest.raises(ValueError, match="chip"):
        profiler.start_fleet_stream(
            arrays, num_fns=num_fns, duration=150.0,
            idle_watts=[t.idle_watts for t in tels],
            has_chip=False, has_cp=True,
            fn_counters=counters[0], counter_model=counters[2],
        )


# ---------------------------------------------------------------------------
# Fleet totals: the chip/rest split through the psum path.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, *MULTI])
def test_fleet_totals_chip_split(k):
    if k > len(jax.devices()):
        pytest.skip(f"needs {k} devices")
    fm = fleet_mesh(devices=jax.devices()[:k])
    inputs = synthetic_fleet(8, 2, 10, 7, seed=k)
    res = run_fleet(inputs, EngineConfig(), mesh=fm)
    x_cpu = jnp.asarray(
        np.abs(np.random.default_rng(k).standard_normal((8, 7))), jnp.float32
    )
    ref = fleet_attribution_totals(
        np.asarray(res.tick_power), np.asarray(res.unattributed),
        chip_power=np.asarray(x_cpu),
    )
    np.testing.assert_allclose(
        np.asarray(ref.chip_per_fn), np.asarray(x_cpu).sum(0), rtol=1e-6
    )
    assert float(ref.chip_total) == pytest.approx(float(x_cpu.sum()), rel=1e-6)
    tot = fleet_attribution_totals(
        res.tick_power, res.unattributed, chip_power=x_cpu, mesh=fm
    )
    np.testing.assert_allclose(
        np.asarray(tot.chip_per_fn), np.asarray(ref.chip_per_fn), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(tot.per_fn), np.asarray(ref.per_fn), rtol=1e-5
    )
    # without a chip split the fields are zeros, not absent
    plain = fleet_attribution_totals(res.tick_power, res.unattributed, mesh=fm)
    assert float(plain.chip_total) == 0.0
    assert plain.chip_per_fn.shape == (7,)


# ---------------------------------------------------------------------------
# Control plane end-to-end.
# ---------------------------------------------------------------------------


def _control_plane(platform="desktop"):
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.telemetry.simulator import SimulatorConfig
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    cfg = dataclasses.replace(PCFG, mode="pure")  # combined via mode= override
    return reg, EnergyFirstControlPlane(
        reg, SimulatorConfig(platform=platform, seed=0), cfg
    )


def test_control_plane_combined_end_to_end_matches_oracle():
    """profile_fleet(mode='combined', mesh='auto'): live streaming session,
    counter models fit by the control plane, reports matching the per-node
    profile() combined oracle built from the same inputs."""
    from repro.workload.azure import WorkloadConfig, generate_trace

    reg, cp = _control_plane()
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=150.0, load=1.0, seed=s))
        for s in (3, 4)
    ]
    ticks_seen = []
    out = cp.profile_fleet(
        traces, seeds=[21, 22], mode="combined",
        on_tick=lambda tk, trs: ticks_seen.append(tk.t),
    )
    assert len(out) == 2 and ticks_seen == list(range(60, 150))
    # oracle: same sims, same counter inputs, per-node combined profile()
    prof_c = FaasMeterProfiler(PCFG)
    sims = cp.simulator.simulate_fleet(traces, seeds=[21, 22])
    tels = [s.telemetry for s in sims]
    arrays = [
        (jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
        for t in traces
    ]
    fnc, _, models = cp.combined_counter_inputs(
        prof_c, arrays, tels, num_fns=traces[0].num_fns, duration=150.0
    )
    for i, prof in enumerate(out):
        solo = prof_c.profile(
            *arrays[i], num_fns=traces[0].num_fns, duration=150.0,
            telemetry=tels[i], fn_counters=fnc[i],
            counter_model=cpumod.model_row(models, i),
        )
        _assert_reports_close(prof.report, solo, tag=f"node {i} control-plane")
        # the live tracker metered the full spectrum (chip + rest): its
        # cumulative energy is within a few percent of the report's j_indiv
        tr = prof.footprint_stream
        assert tr is not None and tr.ticks_seen == 90
        j_report = float(np.asarray(solo.spectrum.j_indiv).sum())
        assert np.abs(tr.j_indiv.sum() - j_report) / j_report < 0.25
        assert prof.prices


def test_control_plane_combined_rejects_chipless_platform():
    from repro.workload.azure import WorkloadConfig, generate_trace

    reg, cp = _control_plane(platform="edge")
    traces = [generate_trace(reg, WorkloadConfig(duration_s=150.0, load=1.0, seed=1))]
    with pytest.raises(ValueError, match="chip"):
        cp.profile_fleet(traces, seeds=[5], mode="combined")


def test_control_plane_pure_mode_unchanged_by_default():
    """mode= defaults to the profiler config: the pure path keeps its
    exact behavior (idle-offset targets, no counter fitting)."""
    from repro.workload.azure import WorkloadConfig, generate_trace

    reg, cp = _control_plane()
    traces = [generate_trace(reg, WorkloadConfig(duration_s=150.0, load=1.0, seed=9))]
    default = cp.profile_fleet(traces, seeds=[7])
    explicit = cp.profile_fleet(traces, seeds=[7], mode="pure")
    np.testing.assert_array_equal(
        np.asarray(default[0].report.x_power), np.asarray(explicit[0].report.x_power)
    )
