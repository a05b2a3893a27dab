"""Ragged fleets end-to-end: masked padding through all three engines.

FaasMeter's claim is accurate footprints under *diverse, dynamic* fleets —
nodes join late, die early, and sample at drifting rates, so per-node
window counts differ.  ``pack_fleet_inputs`` pads such a fleet to the
longest node and carries a ``(B, S, n_w)`` validity mask; this suite pins
the masked contract everywhere it matters:

- every engine (batched / gram / streaming / sharded on 1-, 2-, and
  8-device meshes) reproduces the **per-node sequential oracle** — each
  node profiled alone, unpadded — at 1e-5, including a node with zero
  post-init windows;
- mask invariants: padded ticks attribute exactly 0 J even when the
  padded region holds junk, energy conservation holds per real tick, and
  padding a uniform fleet with dead steps is **bit-identical** to not
  padding (the Kalman state freezes bitwise on masked steps);
- the mask is *data*, not a static shape: differing rag patterns share
  one jit trace (segment scan and streaming step alike);
- the streaming step handles a node's stream ending *mid-step* (partial
  ring-buffer step, warm handoff across the death) and the profiler /
  simulator / control-plane stack handles per-node durations.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched_engine import (
    EngineConfig,
    FleetInputs,
    _scan_stream,
    fleet_initial_estimate,
    fleet_step,
    fleet_stream_init,
    fleet_ticks,
    pack_fleet_inputs,
    run_fleet,
    run_fleet_gram,
    run_fleet_sequential,
    run_fleet_stream,
    synthetic_fleet,
    synthetic_ragged_windows,
)
from repro.core.kalman import run_kalman_fleet
from repro.distributed.sharding import (
    fleet_attribution_totals,
    fleet_mesh,
)

CFG = EngineConfig()
ENGINES = [run_fleet, run_fleet_gram, run_fleet_stream]
#: Mesh sizes above one device: the ``multidevice`` params (tests/conftest.py).
MULTI = [pytest.param(k, marks=pytest.mark.multidevice) for k in (2, 8)]

# Per-node window counts drawn from {T/2 .. T} (T = 5 steps of 8 ticks),
# plus a node with zero full steps and one with a sub-step tail.
N_W = 8
N = 5 * N_W
LENGTHS = [N, 3 * N_W + 3, N_W, 5, N // 2, N - 1, 2 * N_W, N]


def _ragged(b=4, lengths=None, seed=0):
    lengths = LENGTHS[:b] if lengths is None else lengths
    wins = synthetic_ragged_windows(b, N, 6, lengths=lengths, seed=seed)
    return wins, pack_fleet_inputs(*wins, step_windows=N_W, lengths=lengths), lengths


# ---------------------------------------------------------------------------
# Equivalence: ragged batched == gram == streaming == per-node oracle.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fn", ENGINES, ids=lambda f: f.__name__)
def test_ragged_engines_match_per_node_oracle(fn):
    """Each node of a heterogeneous fleet gets the result it would get
    profiled alone (unpadded, sequential seed semantics), at 1e-5."""
    wins, inputs, lengths = _ragged()
    assert inputs.mask is not None
    out = fn(inputs, CFG)
    for i, li in enumerate(lengths):
        s_i = li // N_W
        if s_i == 0:
            # No full step: the node is fully masked — X stays at X_0 and
            # nothing is ever attributed to it.
            np.testing.assert_array_equal(
                np.asarray(out.x_final[i]), np.asarray(out.x0[i])
            )
            assert float(jnp.max(jnp.abs(out.tick_power[i]))) == 0.0
            continue
        sub = pack_fleet_inputs(
            *[w[i : i + 1, :li] for w in wins], step_windows=N_W
        )
        assert sub.mask is None  # single unpadded node: the dense path
        ref = run_fleet_sequential(sub, CFG)
        np.testing.assert_allclose(
            np.asarray(out.x0[i]), np.asarray(ref.x0[0]), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(out.x_final[i]), np.asarray(ref.x_final[0]),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(out.x_trajectory[i, :s_i]), np.asarray(ref.x_trajectory[0]),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(out.tick_power[i, : s_i * N_W]),
            np.asarray(ref.tick_power[0]),
            rtol=1e-4, atol=1e-4,
        )


@pytest.mark.parametrize("k", [1, *MULTI])
@pytest.mark.parametrize("fn", ENGINES, ids=lambda f: f.__name__)
def test_ragged_sharded_matches_unsharded(fn, k):
    """The masked engines shard like the dense ones: the mask splits with
    the node axis and the 1e-5 pin holds on 1-, 2-, and 8-device meshes."""
    if k > len(jax.devices()):
        pytest.skip(f"needs {k} devices")
    fm = fleet_mesh(devices=jax.devices()[:k])
    _, inputs, _ = _ragged(b=8, seed=3)
    ref = fn(inputs, CFG)
    out = fn(inputs, CFG, mesh=fm)
    for name in ("x_final", "x_trajectory", "x0", "tick_power", "unattributed"):
        np.testing.assert_allclose(
            np.asarray(getattr(out, name)), np.asarray(getattr(ref, name)),
            rtol=1e-5, atol=1e-5, err_msg=name,
        )


# ---------------------------------------------------------------------------
# Mask invariants.
# ---------------------------------------------------------------------------


def test_padded_ticks_attribute_exactly_zero_despite_junk():
    """synthetic_ragged_windows deliberately fills the padded region with
    junk; masking must erase it EXACTLY (not approximately) from every
    engine's attribution."""
    _, inputs, _ = _ragged(b=6, seed=1)
    dead = 1.0 - np.asarray(inputs.mask).reshape(6, -1)
    assert dead.sum() > 0
    for fn in ENGINES:
        out = fn(inputs, CFG)
        assert float(np.max(np.abs(np.asarray(out.tick_power) * dead[..., None]))) == 0.0
        assert float(np.max(np.abs(np.asarray(out.unattributed) * dead))) == 0.0


def test_conservation_holds_per_real_tick():
    """tick_power.sum(-1) + unattributed == (masked) measured power on
    every tick — the per-tick efficiency property, ragged or not."""
    _, inputs, _ = _ragged(b=6, seed=2)
    masked_w = np.asarray(inputs.w * inputs.mask).reshape(6, -1)
    for fn in ENGINES:
        out = fn(inputs, CFG)
        recon = np.asarray(out.tick_power).sum(-1) + np.asarray(out.unattributed)
        np.testing.assert_allclose(recon, masked_w, atol=1e-3)


def _pad_with_junk_steps(u: FleetInputs, k: int) -> FleetInputs:
    """Append k fully-masked steps of junk to a dense fleet batch."""
    b, s, n_w, m = u.c.shape
    junk = lambda shape, v: jnp.full(shape, v, jnp.float32)
    cat = lambda a, p: jnp.concatenate([a, p], axis=1)
    return FleetInputs(
        c=cat(u.c, junk((b, k, n_w, m), 7.0)),
        w=cat(u.w, junk((b, k, n_w), 55.0)),
        a=cat(u.a, junk((b, k, m), 2.0)),
        lat_sum=cat(u.lat_sum, junk((b, k, m), 1.0)),
        lat_sumsq=cat(u.lat_sumsq, junk((b, k, m), 1.0)),
        mask=cat(jnp.ones((b, s, n_w)), jnp.zeros((b, k, n_w))),
    )


@pytest.mark.parametrize("fn", ENGINES, ids=lambda f: f.__name__)
def test_padding_uniform_fleet_is_bit_identical(fn):
    """Padding a uniform fleet with k dead (junk-filled, masked) steps is
    BIT-identical to not padding: a float zero added to a gram is exact,
    and a step with zero invocations freezes the whole Kalman state."""
    b, s, n_w, m = 3, 4, 8, 6
    u = synthetic_fleet(b, s, n_w, m, seed=2)
    padded = _pad_with_junk_steps(u, k=2)
    ru, rp = fn(u, CFG), fn(padded, CFG)
    np.testing.assert_array_equal(np.asarray(rp.x0), np.asarray(ru.x0))
    np.testing.assert_array_equal(np.asarray(rp.x_final), np.asarray(ru.x_final))
    np.testing.assert_array_equal(
        np.asarray(rp.x_trajectory[:, :s]), np.asarray(ru.x_trajectory)
    )
    np.testing.assert_array_equal(
        np.asarray(rp.tick_power[:, : s * n_w]), np.asarray(ru.tick_power)
    )
    # the dead tail: trajectory frozen, zero energy
    np.testing.assert_array_equal(
        np.asarray(rp.x_trajectory[:, s:]),
        np.broadcast_to(np.asarray(ru.x_final)[:, None], (b, 2, m)),
    )
    assert float(jnp.max(jnp.abs(rp.tick_power[:, s * n_w :]))) == 0.0
    # the FULL Kalman state froze bitwise — not just the estimate
    for leaf in ("x", "p", "seen", "lat_mean", "lat_m2", "lat_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rp.state, leaf)), np.asarray(getattr(ru.state, leaf)),
            err_msg=leaf,
        )


def test_one_trace_across_differing_rag_patterns():
    """The mask is data: fleets with different rag patterns (same padded
    shape) must NOT retrace the scan or the streaming step."""
    b = 4
    _, in_a, _ = _ragged(b=b, lengths=[N, 3 * N_W, 2 * N_W, N_W], seed=5)
    _, in_b, _ = _ragged(b=b, lengths=[N, N_W, 4 * N_W, 3 * N_W + 5], seed=6)
    assert in_a.c.shape == in_b.c.shape

    run_fleet(in_a, CFG)
    scan_before = _scan_stream._cache_size()
    kal_before = run_kalman_fleet._cache_size()
    run_fleet_stream(in_a, CFG)
    scan_mid = _scan_stream._cache_size()
    # different rag pattern, same shapes: zero new traces anywhere
    run_fleet(in_b, CFG)
    run_fleet_stream(in_b, CFG)
    assert _scan_stream._cache_size() == scan_mid
    assert run_kalman_fleet._cache_size() == kal_before

    x0 = fleet_initial_estimate(in_a.c, in_a.w, CFG)
    state = fleet_stream_init(x0, N_W, CFG)
    ticks_a, ticks_b = fleet_ticks(in_a), fleet_ticks(in_b)
    before = fleet_step._cache_size()
    for t in range(in_a.c.shape[1] * N_W):
        ticks = ticks_a if t % 2 == 0 else ticks_b  # interleave rag patterns
        state, _ = fleet_step(state, jax.tree.map(lambda l: l[t], ticks), config=CFG)
    assert fleet_step._cache_size() - before == 1


# ---------------------------------------------------------------------------
# Streaming: mid-step stream death + warm handoff across it.
# ---------------------------------------------------------------------------


def test_stream_node_dies_mid_step_matches_masked_segment():
    """A node's stream ending mid-step leaves a *partial* ring-buffer step;
    the boundary update must reduce it over exactly the valid ticks — the
    same answer as the segment engine given the same tick-granular mask."""
    b, s, n_w, m = 3, 4, 8, 6
    u = synthetic_fleet(b, s, n_w, m, seed=9)
    death = 2 * n_w + 3  # node 1 dies 3 ticks into step 2
    tick_alive = np.ones((b, s * n_w), np.float32)
    tick_alive[1, death:] = 0.0
    inputs = u._replace(mask=jnp.asarray(tick_alive.reshape(b, s, n_w)))

    ref = run_fleet(inputs, CFG)
    seq = run_fleet_sequential(inputs, CFG)
    np.testing.assert_allclose(
        np.asarray(ref.x_final), np.asarray(seq.x_final), rtol=1e-5, atol=1e-5
    )

    # Seed from the masked init estimate (run_fleet's own X_0): ticks the
    # node never produced must not leak into the bootstrap either.
    state = fleet_stream_init(ref.x0, n_w, CFG)
    ticks = fleet_ticks(inputs)
    half = death + 2  # hand off mid-step, after the death
    for t in range(half):
        state, _ = fleet_step(state, jax.tree.map(lambda l: l[t], ticks), config=CFG)
    resumed = state  # warm handoff of the carried state (ragged partial step)
    for t in range(half, s * n_w):
        resumed, att = fleet_step(
            resumed, jax.tree.map(lambda l: l[t], ticks), config=CFG
        )
    np.testing.assert_allclose(
        np.asarray(resumed.kalman.x), np.asarray(ref.x_final), rtol=1e-5, atol=1e-5
    )
    # the dead node still froze at its last full-information estimate
    scan = run_fleet_stream(inputs, CFG)
    np.testing.assert_array_equal(
        np.asarray(resumed.kalman.x), np.asarray(scan.x_final)
    )


# ---------------------------------------------------------------------------
# Fleet totals: the psum path honors the mask.
# ---------------------------------------------------------------------------


def test_fleet_attribution_totals_masked():
    """Totals over masked partials: junk on padded ticks of an *external*
    per-tick source is excluded, and the engine's own (already-zero)
    output is unchanged by passing the mask explicitly."""
    _, inputs, _ = _ragged(b=4, seed=7)
    out = run_fleet(inputs, CFG)
    tmask = inputs.mask.reshape(4, -1)
    ref = fleet_attribution_totals(out.tick_power, out.unattributed)
    tot = fleet_attribution_totals(out.tick_power, out.unattributed, mask=tmask)
    np.testing.assert_allclose(np.asarray(tot.per_fn), np.asarray(ref.per_fn))
    # external source with junk on dead ticks: the mask must excise it
    junk_tp = out.tick_power + 13.0 * (1.0 - tmask)[..., None]
    junk_ua = out.unattributed + 13.0 * (1.0 - tmask)
    tot2 = fleet_attribution_totals(junk_tp, junk_ua, mask=tmask)
    np.testing.assert_allclose(
        float(tot2.attributed), float(ref.attributed), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(tot2.unattributed), float(ref.unattributed), rtol=1e-6, atol=1e-6
    )


@pytest.mark.multidevice
def test_fleet_attribution_totals_masked_psum():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    fm = fleet_mesh(devices=jax.devices()[:2])
    _, inputs, _ = _ragged(b=4, seed=8)
    out = run_fleet(inputs, CFG)
    tmask = inputs.mask.reshape(4, -1)
    junk_tp = out.tick_power + 5.0 * (1.0 - tmask)[..., None]
    ref = fleet_attribution_totals(junk_tp, out.unattributed, mask=tmask)
    tot = fleet_attribution_totals(junk_tp, out.unattributed, mask=tmask, mesh=fm)
    np.testing.assert_allclose(
        np.asarray(tot.per_fn), np.asarray(ref.per_fn), rtol=1e-5
    )
    np.testing.assert_allclose(float(tot.attributed), float(ref.attributed), rtol=1e-5)


# ---------------------------------------------------------------------------
# Profiler / simulator / control plane over a ragged node set.
# ---------------------------------------------------------------------------

DUR_RAGGED = [120.0, 100.0, 40.0, 95.0]  # full / short / init-only / sub-step tail


def _ragged_fixture():
    from repro.core.profiler import FaasMeterProfiler, ProfilerConfig
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform="edge"))
    profiler = FaasMeterProfiler(ProfilerConfig(init_windows=40, step_windows=20))
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=i))
        for i, d in enumerate(DUR_RAGGED)
    ]
    sims = sim.simulate_fleet(traces, seeds=[11, 12, 13, 14])
    arrays = [
        (jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
        for t in traces
    ]
    return reg, profiler, traces, sims, arrays


def test_simulate_fleet_ragged_matches_per_node():
    """Ragged fleet simulation == per-node simulation, per node (same
    seeds, same truth chain, each node's own window count)."""
    _, _, traces, sims, _ = _ragged_fixture()
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.functions import paper_functions

    sim = NodeSimulator(paper_functions(), SimulatorConfig(platform="edge"))
    for trace, fleet_r, seed in zip(traces, sims, [11, 12, 13, 14]):
        solo = sim.simulate(trace, seed=seed)
        assert fleet_r.num_windows == int(round(trace.duration))
        np.testing.assert_allclose(
            np.asarray(fleet_r.telemetry.system_power),
            np.asarray(solo.telemetry.system_power),
            rtol=1e-6,
        )
        assert fleet_r.measured_energy_j == pytest.approx(solo.measured_energy_j)


def test_stream_fleet_ragged_valid_flags():
    """Live ragged telemetry: every window up to the longest node arrives
    in order, ended nodes are flagged invalid and never stall the fleet."""
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig())  # server: laggy IPMI sensing
    durs = [60.0, 35.0]
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=s))
        for s, d in enumerate(durs)
    ]
    ticks = list(sim.stream_fleet(traces, seeds=[5, 6]))
    assert [tk.t for tk in ticks] == list(range(60))
    for tk in ticks:
        want = np.asarray([tk.t < 60, tk.t < 35])
        np.testing.assert_array_equal(np.asarray(tk.valid), want)
        assert np.all(tk.w_sys[want] > 0)
        assert np.all(tk.w_sys[~want] == 0.0)


def test_ragged_profiler_batched_and_streaming_match_per_node():
    """The acceptance pin at the profiler level: batched and streaming
    fleet profiling over per-node durations reproduce each node's solo
    report — including the node with zero post-init windows."""
    from repro.core.profiler import fleet_profile_batched

    _, profiler, traces, sims, arrays = _ragged_fixture()
    tels = [s.telemetry for s in sims]
    num_fns = traces[0].num_fns

    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=DUR_RAGGED
    )

    sess = profiler.start_fleet_stream(
        arrays, num_fns=num_fns, duration=DUR_RAGGED,
        idle_watts=[t.idle_watts for t in tels],
        has_chip=False, has_cp=tels[0].cp_cpu_frac is not None,
    )
    n_max = int(max(DUR_RAGGED))

    def col(get, tel, t):
        arr = np.asarray(get(tel))
        return arr[t] if t < arr.shape[0] else 0.0

    for t in range(n_max):
        sess.push_window(
            w_sys=np.asarray([col(lambda x: x.system_power, tel, t) for tel in tels]),
            cp_frac=np.asarray([col(lambda x: x.cp_cpu_frac, tel, t) for tel in tels]),
            sys_frac=np.asarray([col(lambda x: x.sys_cpu_frac, tel, t) for tel in tels]),
        )
    streamed = sess.finalize()

    for i, d in enumerate(DUR_RAGGED):
        solo = profiler.profile(
            *arrays[i], num_fns=num_fns, duration=d, telemetry=tels[i]
        )
        for rep, path in ((batched[i], "batched"), (streamed[i], "streamed")):
            np.testing.assert_allclose(
                np.asarray(rep.x_power), np.asarray(solo.x_power),
                atol=1e-3, err_msg=f"node {i} via {path}",
            )
            assert rep.x_trajectory.shape == solo.x_trajectory.shape
            assert rep.total_error == pytest.approx(solo.total_error, abs=1e-4)
            assert rep.idle_energy == solo.idle_energy
        # streaming pins to batched at engine tolerance (edge: no sync skew)
        np.testing.assert_allclose(
            np.asarray(streamed[i].x_power), np.asarray(batched[i].x_power),
            rtol=1e-5, atol=1e-5,
        )


def test_ragged_session_with_sync_clamps_at_each_nodes_tail():
    """With a chip reference and positive sensor skew, a short node's
    tail reads must zero-order-hold at ITS OWN last real window (the
    batch path's per-node clamp) — never interpolate into the zero
    padding after its stream ended.  Session vs batched stays within the
    uniform-fleet sync tolerance (skew estimated on init vs full segment)
    for every node of a ragged server-platform fleet."""
    from repro.core.profiler import (
        FaasMeterProfiler,
        ProfilerConfig,
        fleet_profile_batched,
    )
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform="server"))  # laggy IPMI
    # Same segment geometry as the uniform-fleet sync test
    # (test_streaming_session_with_sync_close_to_batched), whose 2 W
    # tolerance absorbs the documented init-vs-full-segment skew estimate
    # difference; the pre-fix clamp bug put the short node tens of watts off.
    profiler = FaasMeterProfiler(ProfilerConfig(init_windows=60, step_windows=30))
    durs = [180.0, 120.0]
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=i))
        for i, d in enumerate(durs)
    ]
    sims = sim.simulate_fleet(traces, seeds=[31, 32])
    tels = [s.telemetry for s in sims]
    arrays = [
        (jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
        for t in traces
    ]
    num_fns = traces[0].num_fns
    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=durs
    )
    sess = profiler.start_fleet_stream(
        arrays, num_fns=num_fns, duration=durs,
        idle_watts=[t.idle_watts for t in tels],
        has_chip=True, has_cp=tels[0].cp_cpu_frac is not None,
    )

    def col(get, tel, t):
        arr = np.asarray(get(tel))
        return arr[t] if t < arr.shape[0] else 0.0

    for t in range(int(max(durs))):
        sess.push_window(
            w_sys=np.asarray([col(lambda x: x.system_power, tel, t) for tel in tels]),
            w_chip=np.asarray([col(lambda x: x.chip_power, tel, t) for tel in tels]),
            cp_frac=np.asarray([col(lambda x: x.cp_cpu_frac, tel, t) for tel in tels]),
            sys_frac=np.asarray([col(lambda x: x.sys_cpu_frac, tel, t) for tel in tels]),
        )
    streamed = sess.finalize()
    assert float(np.max(sess.skews)) > 0.0  # the clamp is actually exercised
    for rb, rs in zip(batched, streamed):
        assert abs(rs.skew_windows - rb.skew_windows) < 1.0
        assert float(jnp.max(jnp.abs(rs.x_power - rb.x_power))) < 2.0


def test_control_plane_profile_fleet_ragged_trackers():
    """profile_fleet over a ragged node set: live trackers stop the moment
    their node's stream ends; every node still gets a report + prices."""
    from repro.core.profiler import ProfilerConfig
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.telemetry.simulator import SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    cp = EnergyFirstControlPlane(
        reg, SimulatorConfig(platform="edge"),
        ProfilerConfig(init_windows=40, step_windows=20),
    )
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=i))
        for i, d in enumerate(DUR_RAGGED)
    ]
    valid_seen = []
    out = cp.profile_fleet(
        traces, seeds=[21, 22, 23, 24],
        on_tick=lambda tk, trs: valid_seen.append(np.asarray(tk.valid)),
    )
    assert len(out) == 4
    # engine ticks span the longest node; per-node tick counts follow S_i
    expect_ticks = [int((d - 40) // 20) * 20 for d in DUR_RAGGED]
    for prof, want in zip(out, expect_ticks):
        tr = prof.footprint_stream
        assert tr is not None
        assert tr.ticks_seen == want
        assert tr.steps_seen == want + 1  # + the init-segment seed
        assert prof.prices
    # validity really went ragged over the run
    stacked = np.stack(valid_seen)
    assert stacked[:, 0].all() and not stacked[:, 2].any()
    assert stacked[0].tolist() == [True, True, False, True]
    assert stacked[-1].tolist() == [True, False, False, False]
