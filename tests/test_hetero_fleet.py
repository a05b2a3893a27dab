"""Heterogeneous mixed-platform fleets: platform mix as data.

One fleet batch with per-node power-model parameters stacked as (B,)
arrays must reproduce the per-platform batches it replaces — across the
sequential per-node oracle, the batched segment engine, and the
streaming session (1-, 2-, and 8-device meshes), dense and ragged, in
combined mode with a chipless edge node riding the same batch.  Plus the
fn-axis validity mask (ragged ``num_fns`` per node), the fleet-batched
linear-SVR trainer, the vectorized truth model, and the
``sys_cpu_fraction`` front-end regressions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cpu_model as cpumod
from repro.core.batched_engine import (
    EngineConfig,
    pack_fleet_inputs,
    run_fleet,
    run_fleet_gram,
    run_fleet_sequential,
    run_fleet_stream,
)
from repro.core.profiler import (
    FaasMeterProfiler,
    ProfilerConfig,
    fleet_profile_batched,
)
from repro.distributed.sharding import fleet_mesh
from repro.telemetry.power_model import FleetPowerModel, NodePowerModel, PowerModelConfig
from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
from repro.workload.azure import WorkloadConfig, generate_trace
from repro.workload.functions import paper_functions

#: sync_max_shift=0 keeps the streaming session's init-window skew
#: estimate out of the cross-engine pins (same convention as
#: tests/test_combined_fleet.py).
PCFG = ProfilerConfig(
    init_windows=60, step_windows=30, mode="combined", sync_max_shift=0
)

PLATFORMS = ["server", "desktop", "edge"]
#: Mesh sizes above one device: the ``multidevice`` params (tests/conftest.py).
MULTI = [pytest.param(k, marks=pytest.mark.multidevice) for k in (2, 8)]


def _mixed_fixture(durations=None, platforms=PLATFORMS):
    b = len(platforms)
    durations = [150.0] * b if durations is None else durations
    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform="desktop"))
    profiler = FaasMeterProfiler(PCFG)
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=1 + i))
        for i, d in enumerate(durations)
    ]
    seeds = [10 + i for i in range(b)]
    sims = sim.simulate_fleet(traces, seeds=seeds, platforms=list(platforms))
    tels = [s.telemetry for s in sims]
    arrays = [
        (jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
        for t in traces
    ]
    return reg, profiler, sim, traces, seeds, tels, arrays, durations


def _counters(reg, profiler, arrays, tels, num_fns, duration):
    from repro.core.profiler import prepare_combined_fleet

    specs = reg.specs
    return prepare_combined_fleet(
        profiler.config, arrays, tels, num_fns=num_fns, duration=duration,
        gflops=np.asarray([s.gflops for s in specs]),
        hbm_gb=np.asarray([s.hbm_gb for s in specs]),
        mean_latency=np.asarray([max(s.mean_latency_s, 1e-3) for s in specs]),
    )


def _session_reports(profiler, arrays, tels, counters, *, num_fns, duration, mesh=None):
    fnc, wf, models = counters
    sess = profiler.start_fleet_stream(
        arrays, num_fns=num_fns, duration=duration,
        idle_watts=[t.idle_watts for t in tels],
        has_chip=[t.chip_power is not None for t in tels],
        has_cp=tels[0].cp_cpu_frac is not None,
        fn_counters=fnc, counter_model=models, window_features=wf,
        mesh=mesh,
    )
    durs = duration if np.ndim(duration) else [duration] * len(arrays)
    n_max = int(round(max(durs)))

    def col(get, tel, t):
        v = get(tel)
        if v is None:
            return 0.0
        arr = np.asarray(v)
        return arr[t] if t < arr.shape[0] else 0.0

    for t in range(n_max):
        sess.push_window(
            w_sys=np.asarray([col(lambda x: x.system_power, tel, t) for tel in tels]),
            w_chip=np.asarray([col(lambda x: x.chip_power, tel, t) for tel in tels]),
            cp_frac=np.asarray([col(lambda x: x.cp_cpu_frac, tel, t) for tel in tels]),
            sys_frac=np.asarray([col(lambda x: x.sys_cpu_frac, tel, t) for tel in tels]),
        )
    return sess.finalize()


def _assert_reports_close(got, want, tag=""):
    np.testing.assert_allclose(
        np.asarray(got.x_power), np.asarray(want.x_power),
        rtol=1e-5, atol=1e-4, err_msg=f"{tag} x_power",
    )
    assert got.total_error == pytest.approx(want.total_error, abs=1e-4), tag
    np.testing.assert_allclose(
        np.asarray(got.spectrum.j_total), np.asarray(want.spectrum.j_total),
        rtol=1e-4, atol=1e-2, err_msg=f"{tag} j_total",
    )
    assert got.idle_energy == pytest.approx(want.idle_energy, rel=1e-5), tag


# ---------------------------------------------------------------------------
# The tentpole pin: one mixed batch == per-platform batches, three engines.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
def test_mixed_fleet_matches_per_platform_batches(ragged):
    """A mixed server/desktop/edge batch must reproduce each node's
    single-platform result at 1e-5 on the oracle, the batched segment
    engine, and the streaming session — the chipless edge node falls back
    to pure mode *inside* the combined batch, as data."""
    durations = [180.0, 150.0, 150.0] if ragged else None
    reg, profiler, sim, traces, seeds, tels, arrays, durations = _mixed_fixture(
        durations=durations
    )
    num_fns = traces[0].num_fns
    duration = durations if len(set(durations)) > 1 else durations[0]
    counters = _counters(reg, profiler, arrays, tels, num_fns, duration)
    fnc, _, models = counters

    # Per-platform references: each node simulated + profiled alone on its
    # own platform (B=1 batches of the pre-existing per-platform path).
    # A chipless platform cannot run combined at all on its own — its
    # reference is the pure path, which is exactly what the mixed batch's
    # chipless rows must degenerate to.
    import dataclasses

    pure = FaasMeterProfiler(dataclasses.replace(PCFG, mode="pure"))
    refs = []
    for i, plat in enumerate(PLATFORMS):
        ref_sim = NodeSimulator(reg, SimulatorConfig(platform=plat))
        (tel_i,) = [
            s.telemetry
            for s in ref_sim.simulate_fleet([traces[i]], seeds=[seeds[i]])
        ]
        np.testing.assert_array_equal(
            np.asarray(tel_i.system_power), np.asarray(tels[i].system_power),
            err_msg=f"mixed-batch sensing diverged from per-platform ({plat})",
        )
        if tel_i.chip_power is None:
            refs.extend(
                fleet_profile_batched(
                    pure, [arrays[i]], [tel_i],
                    num_fns=num_fns, duration=durations[i],
                )
            )
            continue
        ctr_i = _counters(reg, profiler, [arrays[i]], [tel_i], num_fns, durations[i])
        refs.extend(
            fleet_profile_batched(
                profiler, [arrays[i]], [tel_i],
                num_fns=num_fns, duration=durations[i],
                fn_counters=ctr_i[0], counter_model=ctr_i[2],
            )
        )

    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=duration,
        fn_counters=fnc, counter_model=models,
    )
    oracle = [
        profiler.profile(
            *arrays[i], num_fns=num_fns, duration=durations[i],
            telemetry=tels[i], fn_counters=fnc[i],
            counter_model=cpumod.model_row(models, i),
        )
        for i in range(len(arrays))
    ]
    streamed = _session_reports(
        profiler, arrays, tels, counters, num_fns=num_fns, duration=duration
    )
    for i, plat in enumerate(PLATFORMS):
        _assert_reports_close(batched[i], refs[i], tag=f"batched:{plat}")
        _assert_reports_close(oracle[i], refs[i], tag=f"oracle:{plat}")
        _assert_reports_close(streamed[i], refs[i], tag=f"stream:{plat}")


def test_mixed_fleet_rerun_retraces_nothing():
    """A mixed server/desktop/edge fleet profiled a second time through
    ``profile_fleet`` adds no ``fleet_step`` trace: the platform mix is
    data, not shapes."""
    from repro.core.engine import fleet_step
    from repro.serving.control_plane import EnergyFirstControlPlane

    reg = paper_functions()
    cp = EnergyFirstControlPlane(reg, SimulatorConfig(platform="server"), PCFG)
    plats = [PLATFORMS[i % 3] for i in range(6)]
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=120.0, load=1.0, seed=20 + i))
        for i in range(6)
    ]
    seeds = [50 + i for i in range(6)]
    cp.profile_fleet(traces, seeds=seeds, platforms=plats, mode="combined")
    before = fleet_step._cache_size()
    cp.profile_fleet(traces, seeds=seeds, platforms=plats, mode="combined")
    assert fleet_step._cache_size() == before


@pytest.mark.parametrize("ndev", [1, *MULTI])
def test_mixed_fleet_sharded_session(ndev):
    """The mixed-platform streaming session under a 1-/2-/8-device node
    mesh: stacked per-node parameters and the chip mask shard with the
    node axis; results pin against the unsharded session."""
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} devices")
    platforms = [PLATFORMS[i % 3] for i in range(8)]
    reg, profiler, sim, traces, seeds, tels, arrays, durations = _mixed_fixture(
        platforms=platforms
    )
    num_fns = traces[0].num_fns
    counters = _counters(reg, profiler, arrays, tels, num_fns, durations[0])
    base = _session_reports(
        profiler, arrays, tels, counters, num_fns=num_fns, duration=durations[0]
    )
    mesh = fleet_mesh(len(traces), devices=jax.devices()[:ndev])
    assert mesh.num_devices == ndev
    sharded = _session_reports(
        profiler, arrays, tels, counters,
        num_fns=num_fns, duration=durations[0], mesh=mesh,
    )
    for i, (got, want) in enumerate(zip(sharded, base)):
        _assert_reports_close(got, want, tag=f"mesh{ndev}:node{i}")


# ---------------------------------------------------------------------------
# fn-axis raggedness: per-node num_fns as a validity mask.
# ---------------------------------------------------------------------------

ENGINES = [
    ("run_fleet", run_fleet),
    ("run_fleet_gram", run_fleet_gram),
    ("run_fleet_sequential", run_fleet_sequential),
    ("run_fleet_stream", run_fleet_stream),
]


def _fn_ragged_inputs(b=2, n=120, m=8, m0=5, nw=30, seed=0):
    rng = np.random.default_rng(seed)
    c = np.abs(rng.standard_normal((b, n, m))).astype(np.float32)
    a = rng.integers(0, 3, (b, n, m)).astype(np.float32)
    ls, lq = a * 0.3, a * 0.12
    for x in (c, a, ls, lq):
        x[1, :, m0:] = 0.0
    w = (c.sum(-1) * 5.0 + 1.0).astype(np.float32)
    args = [jnp.asarray(x) for x in (c, w, a, ls, lq)]
    return args, m0, nw


@pytest.mark.parametrize("name,engine", ENGINES)
def test_fn_mask_attribution_exactly_zero(name, engine):
    """Functions masked off a node's fn axis get exactly-0 attribution in
    every output (x_final, trajectory, x0, tick_power) — not epsilon."""
    args, m0, nw = _fn_ragged_inputs()
    m = args[0].shape[-1]
    inp = pack_fleet_inputs(*args, step_windows=nw, fn_lengths=[m, m0])
    assert inp.fn_mask is not None
    res = engine(inp, EngineConfig())
    assert np.all(np.asarray(res.x_final[1, m0:]) == 0.0), name
    assert np.all(np.asarray(res.x_trajectory[1, :, m0:]) == 0.0), name
    assert np.all(np.asarray(res.x0[1, m0:]) == 0.0), name
    if res.tick_power is not None:
        assert np.all(np.asarray(res.tick_power[1, :, m0:]) == 0.0), name


@pytest.mark.parametrize("name,engine", ENGINES)
def test_fn_mask_matches_trimmed_solve(name, engine):
    """The masked node's real functions must match a fleet packed at its
    own (smaller) M — padding the fn axis is free of numerical leakage."""
    args, m0, nw = _fn_ragged_inputs()
    m = args[0].shape[-1]
    inp = pack_fleet_inputs(*args, step_windows=nw, fn_lengths=[m, m0])
    trim = pack_fleet_inputs(
        *[x[1:, :, :m0] if x.ndim == 3 else x[1:] for x in args],
        step_windows=nw,
    )
    res, ref = engine(inp, EngineConfig()), engine(trim, EngineConfig())
    np.testing.assert_allclose(
        np.asarray(res.x_final[1, :m0]), np.asarray(ref.x_final[0]),
        rtol=1e-5, atol=1e-5, err_msg=name,
    )
    np.testing.assert_allclose(
        np.asarray(res.x_trajectory[1, :, :m0]), np.asarray(ref.x_trajectory[0]),
        rtol=1e-5, atol=1e-5, err_msg=name,
    )


def test_fn_mask_all_ones_is_dense_bitwise():
    """fn_lengths at the full M must pack with fn_mask=None — the dense
    path's exact inputs, no mask fold at all."""
    args, _, nw = _fn_ragged_inputs()
    m = args[0].shape[-1]
    inp = pack_fleet_inputs(*args, step_windows=nw, fn_lengths=[m, m])
    dense = pack_fleet_inputs(*args, step_windows=nw)
    assert inp.fn_mask is None
    r, rd = run_fleet(inp, EngineConfig()), run_fleet(dense, EngineConfig())
    np.testing.assert_array_equal(np.asarray(r.x_final), np.asarray(rd.x_final))


# ---------------------------------------------------------------------------
# Fleet-batched SVR trainer.
# ---------------------------------------------------------------------------


def test_batched_svr_matches_sequential():
    """The vmapped subgradient loop must reproduce the per-node
    ``fit_linear_svr`` exactly (same iterate path, batched as data)."""
    rng = np.random.default_rng(2)
    b, n, f = 3, 80, 3
    x = np.abs(rng.standard_normal((b, n, f))).astype(np.float32)
    w = np.abs(rng.standard_normal((b, f))).astype(np.float32) + 0.1
    y = np.einsum("bnf,bf->bn", x, w) + 30.0 + 0.1 * rng.standard_normal((b, n))
    xb, yb = jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)
    mb = cpumod.fit_linear_svr(xb, yb)
    assert mb.weights.shape == (b, f) and mb.bias.shape == (b,)
    for i in range(b):
        mi = cpumod.fit_linear_svr(xb[i], yb[i])
        np.testing.assert_allclose(
            np.asarray(cpumod.model_row(mb, i).weights), np.asarray(mi.weights),
            rtol=1e-5, atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(cpumod.model_row(mb, i).bias), np.asarray(mi.bias),
            rtol=1e-5, atol=1e-6,
        )


# ---------------------------------------------------------------------------
# Front-end regressions: stacked truth model + sys_cpu_fraction.
# ---------------------------------------------------------------------------


def test_fleet_power_model_rows_match_node_models():
    """Each FleetPowerModel row is bitwise the scalar NodePowerModel —
    including the linear edge row (sublinearity >= 1 passes through) and
    per-node control-plane event scatter."""
    cfgs = [
        PowerModelConfig(),
        PowerModelConfig(idle_w=15.0, chip_idle_w=6.0, sublinearity=0.95),
        PowerModelConfig(idle_w=8.0, chip_idle_w=3.0, sublinearity=1.0, cp_base_w=1.0),
    ]
    rng = np.random.default_rng(3)
    m, t, dt = 4, 50, 0.25
    dyn = np.abs(rng.standard_normal(m)) * 20.0
    frac = rng.random(m)
    fleet = FleetPowerModel(cfgs, dyn, frac)
    act = np.abs(rng.standard_normal((3, t, m)))
    starts = [np.sort(rng.random(5) * t * dt), np.zeros(0), np.sort(rng.random(3) * t * dt)]
    grid = np.arange(t) * dt
    cp = fleet.control_plane_power(starts, t, dt)
    p_dyn = np.einsum("btm,m->bt", act, dyn)
    p_cpu = np.einsum("btm,m->bt", act, dyn * frac)
    sysp = fleet.system_power(p_dyn, cp)
    chip = fleet.chip_power(p_cpu, cp)
    sysf = fleet.sys_cpu_fraction(p_cpu, cp, np.full(3, t))
    for i, cfg in enumerate(cfgs):
        node = NodePowerModel(cfg, dyn, frac)
        cp_i = node.control_plane_power(starts[i], grid, dt)
        np.testing.assert_array_equal(cp[i], cp_i)
        np.testing.assert_array_equal(sysp[i], node.system_power(act[i], cp_i))
        np.testing.assert_array_equal(chip[i], node.chip_power(act[i], cp_i))
        np.testing.assert_array_equal(sysf[i], node.sys_cpu_fraction(act[i], cp_i))


def test_sys_cpu_fraction_empty_activity_regression():
    """Regression: ``np.max`` on a zero-length busy series crashed, and the
    ``cap ... or 1.0`` guard was dead (``+`` binds before ``or``).  Empty
    input must yield an empty series; a non-positive capacity must fall
    back to 1 W instead of dividing by <= 0."""
    cfg = PowerModelConfig()
    node = NodePowerModel(cfg, np.asarray([10.0]), np.asarray([0.5]))
    out = node.sys_cpu_fraction(np.zeros((0, 1)), np.zeros(0))
    assert out.shape == (0,)
    # Degenerate capacity: cp capacity 0 and an all-zero busy series.
    node0 = NodePowerModel(
        PowerModelConfig(cp_cpu_capacity_w=0.0), np.asarray([10.0]), np.asarray([0.5])
    )
    frac = node0.sys_cpu_fraction(np.zeros((4, 1)), np.zeros(4))
    assert np.all(np.isfinite(frac)) and frac.shape == (4,)
    np.testing.assert_allclose(frac, 1e-3)  # clipped 0/1.0, not 0/0
    # Fleet twin honors the same guards per row.
    fleet = FleetPowerModel(
        [PowerModelConfig(cp_cpu_capacity_w=0.0), cfg],
        np.asarray([10.0]), np.asarray([0.5]),
    )
    f2 = fleet.sys_cpu_fraction(np.zeros((2, 4)), np.zeros((2, 4)), np.asarray([0, 4]))
    assert np.all(np.isfinite(f2))
