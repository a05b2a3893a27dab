"""Mesh-sharded fleet controller vs the unsharded engines.

The ``FleetMesh`` path (``distributed.sharding``) shards the B-node axis of
``run_fleet`` / ``run_fleet_gram`` / ``run_fleet_stream`` / ``fleet_step``
over a 1-D device mesh via ``shard_map``.  Per-node math is node-local, so
the sharded engines must reproduce the unsharded ones at 1e-5 on 1-, 2-,
and 8-device meshes; fleet-level reductions go through a single ``psum``
(``fleet_attribution_totals``) and must equal the plain ``jnp.sum`` path.
Also pinned: one jit trace for a whole sharded stream (the retrace guard),
sharded state placement/donation, and the control plane's auto-mesh.

Multi-device cases carry the ``multidevice`` marker; on a one-device host
they run in a child process that sees eight host devices
(tests/conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched_engine import (
    EngineConfig,
    fleet_initial_estimate,
    fleet_step,
    fleet_stream_init,
    fleet_ticks,
    run_fleet,
    run_fleet_gram,
    run_fleet_stream,
    synthetic_fleet,
)
from repro.distributed.sharding import (
    FleetMesh,
    fleet_attribution_totals,
    fleet_mesh,
    fleet_mesh_auto,
)

ENGINES = [run_fleet, run_fleet_gram, run_fleet_stream]
CFG = EngineConfig()
#: Mesh sizes above one device: the ``multidevice`` params (tests/conftest.py).
MULTI = [pytest.param(k, marks=pytest.mark.multidevice) for k in (2, 8)]


def _mesh(k: int) -> FleetMesh:
    return fleet_mesh(devices=jax.devices()[:k])


def _assert_result_close(out, ref, *, tol=1e-5):
    for name in ("x_final", "x_trajectory", "x0", "tick_power", "unattributed"):
        np.testing.assert_allclose(
            np.asarray(getattr(out, name)), np.asarray(getattr(ref, name)),
            rtol=tol, atol=tol, err_msg=name,
        )


# ---------------------------------------------------------------------------
# Mesh construction / validation (device-count independent).
# ---------------------------------------------------------------------------


def test_fleet_mesh_fits_largest_divisor():
    """fleet_mesh(num_nodes) never builds a mesh the fleet can't tile."""
    for b in (1, 2, 3, 5, 6, 7, 8, 12):
        fm = fleet_mesh(b)
        assert b % fm.num_devices == 0
        assert fm.num_devices <= len(jax.devices())
    # and with no node count it uses every device
    assert fleet_mesh().num_devices == len(jax.devices())


def test_one_device_mesh_is_identity_sharding():
    """The 1-device mesh runs every mesh= code path on any machine."""
    fm = _mesh(1)
    inputs = synthetic_fleet(3, 2, 8, 5, seed=0)
    for fn in ENGINES:
        _assert_result_close(fn(inputs, CFG, mesh=fm), fn(inputs, CFG))


def test_mesh_put_places_scalars_replicated():
    fm = _mesh(1)
    x0 = fleet_initial_estimate(*synthetic_fleet(2, 2, 6, 4, seed=1)[:2], CFG)
    state = fleet_stream_init(x0, 6, CFG, mesh=fm)
    assert state.tick_in_step.sharding.spec == jax.sharding.PartitionSpec()
    assert state.c_buf.sharding.spec == jax.sharding.PartitionSpec(fm.axis)


@pytest.mark.multidevice
def test_validate_rejects_ragged_fleet():
    fm = _mesh(2)
    with pytest.raises(ValueError, match="not divisible"):
        fm.validate(3)
    with pytest.raises(ValueError, match="not divisible"):
        run_fleet(synthetic_fleet(3, 2, 6, 4, seed=0), CFG, mesh=fm)


# ---------------------------------------------------------------------------
# Equivalence: sharded == unsharded at 1e-5 on 2- and 8-device meshes.
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("fn", ENGINES, ids=lambda f: f.__name__)
def test_sharded_engine_matches_unsharded(fn, k):
    if k > len(jax.devices()):
        pytest.skip(f"needs {k} devices")
    fm = _mesh(k)
    inputs = synthetic_fleet(8, 3, 12, 10, seed=k)
    out = fn(inputs, CFG, mesh=fm)
    _assert_result_close(out, fn(inputs, CFG))
    # outputs really live sharded over the node axis
    assert out.x_final.sharding.spec == jax.sharding.PartitionSpec(fm.axis)


@pytest.mark.multidevice
def test_sharded_respects_dedicated_init_block():
    """The profiler-style init_c/init_w path shards too."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    fm = _mesh(2)
    inputs = synthetic_fleet(4, 3, 10, 6, seed=9)
    init = synthetic_fleet(4, 1, 25, 6, seed=10)
    kw = dict(init_c=init.c.reshape(4, 25, 6), init_w=init.w.reshape(4, 25))
    _assert_result_close(
        run_fleet(inputs, CFG, mesh=fm, **kw), run_fleet(inputs, CFG, **kw)
    )


# ---------------------------------------------------------------------------
# Sharded streaming: tick-at-a-time dispatch, one trace, donated state.
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_sharded_stream_matches_and_retraces_once():
    """Driving the jitted sharded step tick-by-tick equals the (sharded)
    scan at 1e-5 with exactly ONE jit trace for the whole stream."""
    b, s, n_w, m = 8, 3, 8, 6
    fm = fleet_mesh(b)
    assert fm.num_devices > 1
    inputs = synthetic_fleet(b, s, n_w, m, seed=3)
    ref = run_fleet_stream(inputs, CFG)

    x0 = fleet_initial_estimate(inputs.c, inputs.w, CFG)
    state = fleet_stream_init(x0, n_w, CFG, mesh=fm)
    ticks = fleet_ticks(inputs)
    before = fleet_step._cache_size()
    boundary_xs = []
    for t in range(s * n_w):
        tick = jax.tree.map(lambda l: l[t], ticks)
        state, att = fleet_step(state, tick, config=CFG, mesh=fm)
        if bool(att.step_completed):
            boundary_xs.append(np.asarray(att.x))
    assert fleet_step._cache_size() - before == 1
    np.testing.assert_allclose(
        np.asarray(state.kalman.x), np.asarray(ref.x_final), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.stack(boundary_xs, axis=1), np.asarray(ref.x_trajectory),
        rtol=1e-5, atol=1e-5,
    )
    # the carried state stayed sharded across the whole stream
    assert state.kalman.x.sharding.spec == jax.sharding.PartitionSpec(fm.axis)
    assert int(state.step_idx) == s


@pytest.mark.multidevice
def test_sharded_stream_conserves_per_tick():
    """The per-tick efficiency property survives sharding: attributed +
    unattributed == measured on every tick, on every node shard."""
    b, s, n_w, m = 4, 2, 6, 5
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    fm = _mesh(2)
    inputs = synthetic_fleet(b, s, n_w, m, seed=11, density=0.3)
    x0 = fleet_initial_estimate(inputs.c, inputs.w, CFG)
    state = fleet_stream_init(x0, n_w, CFG, mesh=fm)
    ticks = fleet_ticks(inputs)
    for t in range(s * n_w):
        tick = jax.tree.map(lambda l: l[t], ticks)
        state, att = fleet_step(state, tick, config=CFG, mesh=fm)
        recon = np.asarray(att.tick_power).sum(-1) + np.asarray(att.unattributed)
        np.testing.assert_allclose(recon, np.asarray(tick.w), atol=1e-3)


# ---------------------------------------------------------------------------
# Fleet-level reductions: psum along the node axis == plain sums.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, *MULTI])
def test_fleet_attribution_totals_psum_matches_sum(k):
    if k > len(jax.devices()):
        pytest.skip(f"needs {k} devices")
    fm = _mesh(k)
    inputs = synthetic_fleet(8, 2, 10, 7, seed=k)
    res = run_fleet(inputs, CFG, mesh=fm)
    ref = fleet_attribution_totals(
        np.asarray(res.tick_power), np.asarray(res.unattributed),
        np.asarray(res.x_final[:, -1]),
    )
    tot = fleet_attribution_totals(
        res.tick_power, res.unattributed, res.x_final[:, -1], mesh=fm
    )
    np.testing.assert_allclose(np.asarray(tot.per_fn), np.asarray(ref.per_fn), rtol=1e-5)
    np.testing.assert_allclose(float(tot.attributed), float(ref.attributed), rtol=1e-5)
    np.testing.assert_allclose(float(tot.unattributed), float(ref.unattributed), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(tot.cp_total), float(ref.cp_total), rtol=1e-5)
    # conservation: per-function totals sum to the attributed total
    np.testing.assert_allclose(
        float(jnp.sum(tot.per_fn)), float(tot.attributed), rtol=1e-5
    )


# ---------------------------------------------------------------------------
# Profiler + control-plane surface.
# ---------------------------------------------------------------------------


def _fleet_fixture(b=2, duration=150.0):
    from repro.core.profiler import FaasMeterProfiler, ProfilerConfig
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform="edge"))
    profiler = FaasMeterProfiler(ProfilerConfig(init_windows=60, step_windows=30))
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=duration, load=1.0, seed=3 + i))
        for i in range(b)
    ]
    sims = sim.simulate_fleet(traces, seeds=list(range(b)))
    arrays = [
        (jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
        for t in traces
    ]
    return profiler, traces, [s.telemetry for s in sims], arrays


@pytest.mark.multidevice
def test_fleet_profile_batched_sharded_matches():
    from repro.core.profiler import fleet_profile_batched

    profiler, traces, tels, arrays = _fleet_fixture(b=2)
    kw = dict(num_fns=traces[0].num_fns, duration=traces[0].duration)
    ref = fleet_profile_batched(profiler, arrays, tels, **kw)
    out = fleet_profile_batched(profiler, arrays, tels, mesh=_mesh(2), **kw)
    for r, o in zip(ref, out):
        np.testing.assert_allclose(
            np.asarray(o.x_power), np.asarray(r.x_power), rtol=1e-5, atol=1e-5
        )
        assert abs(o.total_error - r.total_error) < 1e-5


@pytest.mark.multidevice
def test_control_plane_auto_mesh_matches_unsharded():
    """profile_fleet(mesh='auto') shards the live streaming session and
    still reproduces the single-device result (reports and live-fed
    trackers alike)."""
    from repro.core.profiler import ProfilerConfig
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.telemetry.simulator import SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    assert fleet_mesh_auto(2) is not None  # >1 device in this process
    reg = paper_functions()
    cp = EnergyFirstControlPlane(
        reg, SimulatorConfig(platform="edge", seed=0),
        ProfilerConfig(init_windows=60, step_windows=30),
    )
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=150.0, load=1.0, seed=s))
        for s in range(2)
    ]
    auto = cp.profile_fleet(traces, seeds=[0, 1])
    plain = cp.profile_fleet(traces, seeds=[0, 1], mesh=None)
    for a, b in zip(auto, plain):
        np.testing.assert_allclose(
            np.asarray(a.report.x_power), np.asarray(b.report.x_power),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            a.footprint_stream.j_indiv, b.footprint_stream.j_indiv,
            rtol=1e-4, atol=1e-4,
        )
        assert a.footprint_stream.ticks_seen == b.footprint_stream.ticks_seen


def test_fleet_mesh_auto_single_device_is_none():
    if len(jax.devices()) > 1:
        pytest.skip("single-device semantics")
    assert fleet_mesh_auto(4) is None
