"""Streaming incremental fleet engine vs the segment engines and the oracle.

The streaming step API (``core.batched_engine.fleet_step``) must reproduce
the segment engines exactly up to float reassociation: a ``lax.scan`` over
the step function is the segment path (``run_fleet_stream``), and driving
the jitted step one dispatch at a time must equal the scan bitwise.  Also
covered here: the retracing guard (one trace for the whole stream), the
shared ``_finalize_report`` across all three profiling paths, the streaming
telemetry front-ends pinned against their batch twins, and the control
plane's live per-tick tracker feed.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched_engine import (
    EngineConfig,
    FleetStep,
    fleet_step,
    fleet_stream_init,
    fleet_ticks,
    fleet_initial_estimate,
    pack_fleet_inputs,
    run_fleet,
    run_fleet_sequential,
    run_fleet_stream,
    synthetic_fleet,
)

FLEET_SHAPES = [(2, 8, 32, 64, 0), (3, 5, 20, 10, 1), (1, 4, 16, 8, 2)]


@pytest.mark.parametrize("b,s,n_w,m,seed", FLEET_SHAPES)
def test_stream_matches_segment_and_oracle(b, s, n_w, m, seed):
    """scan-over-step == run_fleet == sequential oracle to 1e-5."""
    inputs = synthetic_fleet(b, s, n_w, m, seed=seed)
    cfg = EngineConfig()
    seq = run_fleet_sequential(inputs, cfg)
    bat = run_fleet(inputs, cfg)
    stream = run_fleet_stream(inputs, cfg)
    for ref in (seq, bat):
        np.testing.assert_allclose(
            np.asarray(stream.x0), np.asarray(ref.x0), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(stream.x_final), np.asarray(ref.x_final), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(stream.x_trajectory), np.asarray(ref.x_trajectory),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(stream.tick_power), np.asarray(ref.tick_power),
            rtol=1e-4, atol=1e-4,
        )


def test_fleet_step_matches_scan_and_retraces_once():
    """Tick-at-a-time jitted dispatch == the scanned stream, bitwise, with
    exactly ONE trace of the step function across all ticks."""
    b, s, n_w, m = 2, 4, 8, 6
    inputs = synthetic_fleet(b, s, n_w, m, seed=3)
    cfg = EngineConfig()
    ref = run_fleet_stream(inputs, cfg)

    x0 = fleet_initial_estimate(inputs.c, inputs.w, cfg)
    state = fleet_stream_init(x0, n_w, cfg)
    ticks = fleet_ticks(inputs)
    traces_before = fleet_step._cache_size()
    boundary_xs = []
    for t in range(s * n_w):
        tick = jax.tree.map(lambda l: l[t], ticks)
        state, att = fleet_step(state, tick, config=cfg)
        if bool(att.step_completed):
            boundary_xs.append(np.asarray(att.x))
    # no per-tick retracing: the whole stream compiled exactly once
    assert fleet_step._cache_size() - traces_before == 1
    np.testing.assert_array_equal(
        np.asarray(state.kalman.x), np.asarray(ref.x_final)
    )
    np.testing.assert_array_equal(
        np.stack(boundary_xs, axis=1), np.asarray(ref.x_trajectory)
    )
    # state-carry contract: partial step empty again at a step boundary
    assert int(state.tick_in_step) == 0
    assert int(state.step_idx) == s
    assert float(jnp.max(jnp.abs(state.a))) == 0.0


def test_live_attribution_conserved_per_tick():
    """The causal streaming attribution keeps the efficiency property on
    every single tick: attributed power + unattributed == measured."""
    b, s, n_w, m = 3, 3, 10, 8
    inputs = synthetic_fleet(b, s, n_w, m, seed=5, density=0.3)
    cfg = EngineConfig()
    state = fleet_stream_init(fleet_initial_estimate(inputs.c, inputs.w, cfg), n_w, cfg)
    ticks = fleet_ticks(inputs)
    for t in range(s * n_w):
        tick = jax.tree.map(lambda l: l[t], ticks)
        state, att = fleet_step(state, tick, config=cfg)
        recon = np.asarray(att.tick_power).sum(-1) + np.asarray(att.unattributed)
        np.testing.assert_allclose(recon, np.asarray(tick.w), atol=1e-3)
        # unattributed only where nothing ran
        busy = np.asarray(tick.c).sum(-1) > 0
        assert float(np.max(np.abs(np.asarray(att.unattributed)[busy]))) == 0.0


def test_stream_state_warm_handoff():
    """A session can resume from another's final state: splitting one
    segment into two back-to-back streams equals the unsplit stream."""
    b, s, n_w, m = 2, 6, 8, 5
    inputs = synthetic_fleet(b, s, n_w, m, seed=7)
    cfg = EngineConfig()
    ref = run_fleet_stream(inputs, cfg)

    x0 = fleet_initial_estimate(inputs.c, inputs.w, cfg)
    state = fleet_stream_init(x0, n_w, cfg)
    ticks = fleet_ticks(inputs)
    half = (s // 2) * n_w
    for t in range(half):
        state, _ = fleet_step(state, jax.tree.map(lambda l: l[t], ticks), config=cfg)
    # hand the carried state off (e.g. across a controller restart)
    resumed = state
    for t in range(half, s * n_w):
        resumed, att = fleet_step(resumed, jax.tree.map(lambda l: l[t], ticks), config=cfg)
    np.testing.assert_array_equal(np.asarray(resumed.kalman.x), np.asarray(ref.x_final))


# ---------------------------------------------------------------------------
# Shared report finalization across the three profiling paths.
# ---------------------------------------------------------------------------


def _fleet_fixture(platform, duration=180.0, seeds=(1, 2), sim_seeds=(11, 12)):
    from repro.core.profiler import FaasMeterProfiler, ProfilerConfig
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform=platform))
    profiler = FaasMeterProfiler(ProfilerConfig(init_windows=60, step_windows=30))
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=duration, load=1.0, seed=s))
        for s in seeds
    ]
    sims = sim.simulate_fleet(traces, seeds=list(sim_seeds))
    arrays = [
        (jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
        for t in traces
    ]
    return profiler, traces, sims, arrays


def _run_session(profiler, arrays, tels, *, num_fns, duration, on_tick=None):
    sess = profiler.start_fleet_stream(
        arrays, num_fns=num_fns, duration=duration,
        idle_watts=[t.idle_watts for t in tels],
        has_chip=tels[0].chip_power is not None,
        has_cp=tels[0].cp_cpu_frac is not None,
        on_tick=on_tick,
    )
    n = int(round(duration))
    for t in range(n):
        sess.push_window(
            w_sys=np.asarray([np.asarray(tel.system_power)[t] for tel in tels]),
            w_chip=(
                np.asarray([np.asarray(tel.chip_power)[t] for tel in tels])
                if tels[0].chip_power is not None else None
            ),
            cp_frac=(
                np.asarray([np.asarray(tel.cp_cpu_frac)[t] for tel in tels])
                if tels[0].cp_cpu_frac is not None else None
            ),
            sys_frac=(
                np.asarray([np.asarray(tel.sys_cpu_frac)[t] for tel in tels])
                if tels[0].sys_cpu_frac is not None else None
            ),
        )
    return sess.finalize()


def test_finalize_report_equivalent_across_three_paths():
    """Per-node, batched-segment, and streaming profiling all flow through
    the shared ``_finalize_report``; on a no-sync platform (edge: no chip
    reference, so the streaming session sees bit-identical inputs) the
    streaming reports pin to the batched ones, and both stay within the
    established tolerance of the per-node reference."""
    from repro.core.profiler import fleet_profile_batched

    profiler, traces, sims, arrays = _fleet_fixture("edge")
    tels = [s.telemetry for s in sims]
    num_fns, duration = traces[0].num_fns, traces[0].duration

    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=duration
    )
    streamed = _run_session(
        profiler, arrays, tels, num_fns=num_fns, duration=duration
    )
    for (f, st, en), tel, rb, rs in zip(arrays, tels, batched, streamed):
        single = profiler.profile(
            f, st, en, num_fns=num_fns, duration=duration, telemetry=tel
        )
        # streaming == batched (same engine family, 1e-5-class float noise)
        np.testing.assert_allclose(
            np.asarray(rs.x_power), np.asarray(rb.x_power), rtol=1e-5, atol=1e-5
        )
        assert rs.total_error == pytest.approx(rb.total_error, abs=1e-4)
        assert rs.skew_windows == rb.skew_windows == 0.0
        np.testing.assert_allclose(
            np.asarray(rs.spectrum.j_total), np.asarray(rb.spectrum.j_total),
            rtol=1e-4, atol=1e-3,
        )
        # both == the per-node reference path (batched-engine tolerance)
        np.testing.assert_allclose(
            np.asarray(rs.x_power), np.asarray(single.x_power), atol=1e-3
        )
        assert rs.total_error == pytest.approx(single.total_error, abs=1e-4)
        assert rs.cp_energy == pytest.approx(single.cp_energy, rel=1e-3, abs=1e-6)
        assert rs.idle_energy == pytest.approx(single.idle_energy)


def test_streaming_session_with_sync_close_to_batched():
    """With a chip reference the session estimates skew on the init window
    only (the batch path sees the full segment), so reports agree loosely —
    same skew to within a window, footprints within a watt."""
    from repro.core.profiler import fleet_profile_batched

    profiler, traces, sims, arrays = _fleet_fixture("server")
    tels = [s.telemetry for s in sims]
    num_fns, duration = traces[0].num_fns, traces[0].duration
    batched = fleet_profile_batched(
        profiler, arrays, tels, num_fns=num_fns, duration=duration
    )
    streamed = _run_session(profiler, arrays, tels, num_fns=num_fns, duration=duration)
    for rb, rs in zip(batched, streamed):
        assert abs(rs.skew_windows - rb.skew_windows) < 1.0
        assert float(jnp.max(jnp.abs(rs.x_power - rb.x_power))) < 2.0
        assert rs.total_error < rb.total_error + 0.05


# ---------------------------------------------------------------------------
# Streaming telemetry front-ends pinned against the batch implementations.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preset", ["ipmi", "plug", "rapl", "battery"])
def test_streaming_sensor_matches_batch(preset):
    from repro.telemetry import sources as src

    cfg = src.PRESETS[preset]
    dt = 0.02
    rng = np.random.default_rng(0)
    true = np.abs(np.cumsum(rng.standard_normal(7000))) + 50.0
    batch = src.sense(true, dt, cfg, np.random.default_rng(3))

    sensor = src.StreamingSensor(cfg, dt, np.random.default_rng(3))
    chunks = np.random.default_rng(11)
    watts, times, i = [], [], 0
    while i < len(true):
        k = int(chunks.integers(1, 137))
        sig = sensor.push(true[i : i + k])
        watts.append(sig.watts)
        times.append(sig.times)
        i += k
    got_w = np.concatenate(watts)
    got_t = np.concatenate(times)
    np.testing.assert_array_equal(got_w, batch.watts)
    np.testing.assert_array_equal(got_t, batch.times)


@pytest.mark.parametrize("preset", ["ipmi", "plug", "rapl", "battery"])
def test_streaming_resampler_matches_batch(preset):
    from repro.telemetry import sources as src

    cfg = src.PRESETS[preset]
    dt = 0.02
    true = np.abs(np.cumsum(np.random.default_rng(1).standard_normal(7000))) + 50.0
    sig = src.sense(true, dt, cfg, np.random.default_rng(5))
    n_win = 140
    want = src.resample_to_windows(sig, n_win, 1.0)

    rs = src.StreamingWindowResampler(1.0)
    chunks = np.random.default_rng(13)
    got, i = [], 0
    while i < len(sig.watts):
        k = int(chunks.integers(1, 9))
        got.append(rs.push(sig.times[i : i + k], sig.watts[i : i + k]))
        i += k
    got.append(rs.flush(n_win))
    got = np.concatenate(got)[:n_win]
    np.testing.assert_allclose(got, want, atol=1e-9)


def test_stream_fleet_yields_ordered_windows():
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig())
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=60.0, load=1.0, seed=s))
        for s in (1, 2)
    ]
    ticks = list(sim.stream_fleet(traces, seeds=[5, 6]))
    assert [tk.t for tk in ticks] == list(range(60))
    for tk in ticks:
        assert tk.w_sys.shape == (2,) and np.all(tk.w_sys > 0)
        assert tk.w_chip is not None and tk.w_chip.shape == (2,)
        assert tk.cp_frac.shape == (2,) and tk.sys_frac.shape == (2,)
    # The streaming measurement path is bitwise the batch path: both spawn
    # the same per-sensor child RNGs and the fleet resampler reproduces the
    # batch cumulative-sum float for float, so the tick stream must equal
    # simulate_fleet's telemetry EXACTLY, noise included.
    sims = sim.simulate_fleet(traces, seeds=[5, 6])
    w_sys = np.stack([np.asarray(tk.w_sys) for tk in ticks], axis=1)
    w_chip = np.stack([np.asarray(tk.w_chip) for tk in ticks], axis=1)
    for i, s in enumerate(sims):
        np.testing.assert_array_equal(
            w_sys[i].astype(np.float32), np.asarray(s.telemetry.system_power)
        )
        np.testing.assert_array_equal(
            w_chip[i].astype(np.float32), np.asarray(s.telemetry.chip_power)
        )


# ---------------------------------------------------------------------------
# Control plane: live per-tick feed + hooks.
# ---------------------------------------------------------------------------


def test_profile_fleet_feeds_trackers_per_tick():
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    cp = EnergyFirstControlPlane(reg)
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=180.0, load=1.0, seed=s))
        for s in (3, 4)
    ]
    hook_ticks = []

    def on_tick(tick, trackers):
        hook_ticks.append(tick.t)
        # the online hook sees conserved attribution every tick
        recon = tick.tick_power.sum(-1) + tick.unattributed
        np.testing.assert_allclose(recon, tick.target, atol=1e-3)

    out = cp.profile_fleet(traces, seeds=[21, 22], on_tick=on_tick)
    cfg = cp.profiler.config
    n_engine_ticks = ((180 - cfg.init_windows) // cfg.step_windows) * cfg.step_windows
    assert hook_ticks == list(range(cfg.init_windows, cfg.init_windows + n_engine_ticks))
    for prof in out:
        tr = prof.footprint_stream
        assert tr is not None
        assert tr.ticks_seen == n_engine_ticks
        # init seed + one observation per tick
        assert tr.steps_seen == n_engine_ticks + 1
        assert tr.elapsed_s == pytest.approx(180.0 - (180 - cfg.init_windows) % cfg.step_windows)


def test_profile_fleet_short_segment_has_no_tracker():
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    cp = EnergyFirstControlPlane(reg)
    traces = [generate_trace(reg, WorkloadConfig(duration_s=90.0, load=1.0, seed=7))]
    out = cp.profile_fleet(traces, seeds=[31])
    assert len(out) == 1 and out[0].footprint_stream is None


def test_profile_fleet_compiles_bootstrap_once_per_fleet():
    """Nodes whose traces differ in length share one compile of each
    per-node bootstrap program: the traces are padded to one length."""
    from repro.core.contribution import contribution_matrix
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    cp = EnergyFirstControlPlane(reg)
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=150.0, load=load, seed=s))
        for s, load in ((41, 0.7), (42, 1.0), (43, 1.3))
    ]
    assert len({t.fn_id.shape[0] for t in traces}) == 3
    before = contribution_matrix._cache_size()
    cp.profile_fleet(traces, seeds=[1, 2, 3], mesh=None)
    assert contribution_matrix._cache_size() <= before + 1


def test_pack_fleet_inputs_pads_and_masks_without_warning():
    """The old ragged-tail UserWarning + truncation is gone: packing is
    pad-and-mask by default (warning-free), with ``lengths`` driving the
    per-node validity mask and ``strict=True`` restoring the equal-length
    contract as a hard error."""
    rng = np.random.default_rng(7)
    b, n, m, step = 2, 37, 4, 10
    c = jnp.asarray(rng.random((b, n, m)), jnp.float32)
    w = jnp.asarray(rng.random((b, n)), jnp.float32)
    a = jnp.asarray(rng.integers(0, 3, (b, n, m)), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        dense = pack_fleet_inputs(c, w, a, a * 0.5, a * 0.25, step_windows=step)
        ragged = pack_fleet_inputs(
            c, w, a, a * 0.5, a * 0.25, step_windows=step, lengths=[37, 13]
        )
    assert dense.mask is None  # uniform fleet: sub-step tail, no padding
    assert ragged.mask is not None and ragged.mask.shape == (b, 3, step)
    # node 1 has one full step; its other ticks are masked and zeroed
    np.testing.assert_array_equal(np.asarray(ragged.mask[1, 0]), 1.0)
    np.testing.assert_array_equal(np.asarray(ragged.mask[1, 1:]), 0.0)
    assert float(jnp.max(jnp.abs(ragged.c[1, 1:]))) == 0.0
    with pytest.raises(ValueError, match="strict"):
        pack_fleet_inputs(
            c, w, a, a * 0.5, a * 0.25, step_windows=step,
            lengths=[37, 13], strict=True,
        )
