"""Drained ingest: the background emit stage of ``StreamingFleetSession``.

``ingest(drain=True)`` moves tick emission (device→numpy materialization,
retrain checks, ``on_tick`` hooks) onto a background drain thread while the
dispatching thread keeps feeding the jitted engine.  Contracts pinned here:

- numerics are *bitwise* identical to the inline path (dispatch order is
  unchanged; only where the host-side materialization runs moves);
- ticks emit in dispatch order, every tick exactly once;
- a drained session abandoned mid-stream (source iterator dies) joins BOTH
  background threads — the drain worker and the prefetch producer — before
  the error reaches the caller (no leaked threads, no deadlock);
- an exception inside an ``on_tick`` hook on the drain thread re-raises at
  the ingesting caller, again with both threads joined.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.profiler import FaasMeterProfiler, ProfilerConfig
from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
from repro.workload.azure import WorkloadConfig, generate_trace
from repro.workload.functions import paper_functions

DURATION = 150.0  # 60 init + 3 Kalman steps of 30


def _live_threads(name):
    return [
        t for t in threading.enumerate() if t.name == name and t.is_alive()
    ]


def _assert_no_leak(name, before, wait=False):
    if wait:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if len(_live_threads(name)) <= before:
                break
            time.sleep(0.02)
    assert len(_live_threads(name)) <= before, f"{name} thread leaked"


def _fixture(platform="edge", seeds=(1, 2), sim_seeds=(11, 12)):
    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform=platform))
    profiler = FaasMeterProfiler(ProfilerConfig(init_windows=60, step_windows=30))
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=DURATION, load=1.0, seed=s))
        for s in seeds
    ]
    tels = [s.telemetry for s in sim.simulate_fleet(traces, seeds=list(sim_seeds))]
    arrays = [
        (jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
        for t in traces
    ]
    return profiler, sim, traces, tels, arrays


def _open_session(profiler, arrays, tels, num_fns, on_tick):
    return profiler.start_fleet_stream(
        arrays, num_fns=num_fns, duration=DURATION,
        idle_watts=[t.idle_watts for t in tels],
        has_chip=tels[0].chip_power is not None,
        has_cp=tels[0].cp_cpu_frac is not None,
        on_tick=on_tick,
    )


@pytest.mark.parametrize("platform", ["edge", "server"])
def test_drained_ingest_bitwise_equals_inline(platform):
    """drain=True changes WHERE emission runs, never WHAT is computed: the
    tick stream and the finalized reports must equal the inline path
    bitwise (assert_array_equal, not allclose)."""
    profiler, sim, traces, tels, arrays = _fixture(platform)
    num_fns = traces[0].num_fns

    def run(drain):
        emitted = []
        sess = _open_session(profiler, arrays, tels, num_fns, emitted.append)
        sess.ingest(
            sim.stream_fleet(traces, seeds=[11, 12]), prefetch=2, drain=drain
        )
        return emitted, sess.finalize()

    inline_ticks, inline_reports = run(drain=False)
    drained_ticks, drained_reports = run(drain=True)

    assert [tk.t for tk in drained_ticks] == [tk.t for tk in inline_ticks]
    for a, b in zip(inline_ticks, drained_ticks):
        assert a.step_completed == b.step_completed
        np.testing.assert_array_equal(a.x, b.x)
        np.testing.assert_array_equal(a.tick_power, b.tick_power)
        np.testing.assert_array_equal(a.unattributed, b.unattributed)
        np.testing.assert_array_equal(a.target, b.target)
        np.testing.assert_array_equal(a.w_sys, b.w_sys)
    for ra, rb in zip(inline_reports, drained_reports):
        np.testing.assert_array_equal(np.asarray(ra.x_power), np.asarray(rb.x_power))
        np.testing.assert_array_equal(
            np.asarray(ra.x_trajectory), np.asarray(rb.x_trajectory)
        )
        assert ra.total_error == rb.total_error
        np.testing.assert_array_equal(
            np.asarray(ra.spectrum.j_total), np.asarray(rb.spectrum.j_total)
        )


def test_drained_step_boundaries_follow_plan():
    """The drain path computes ``step_completed`` host-side from the tick
    index; the emitted boundaries must land exactly every step_windows
    ticks, matching the engine's own counter."""
    profiler, sim, traces, tels, arrays = _fixture()
    emitted = []
    sess = _open_session(profiler, arrays, tels, traces[0].num_fns, emitted.append)
    sess.ingest(sim.stream_fleet(traces, seeds=[11, 12]), prefetch=2, drain=True)
    n_w = profiler.config.step_windows
    assert len(emitted) == sess.s * n_w
    for k, tk in enumerate(emitted):
        assert tk.step_completed == ((k + 1) % n_w == 0)
    assert sum(tk.step_completed for tk in emitted) == sess.s


def test_drain_abandoned_midstream_joins_both_threads():
    """A source iterator dying mid-stream must propagate its error AND
    leave neither the drain worker nor the prefetch producer behind —
    the no-deadlock shutdown contract."""
    profiler, sim, traces, tels, arrays = _fixture()
    before_drain = len(_live_threads("session-drain"))
    before_prod = len(_live_threads("prefetch-producer"))
    sess = _open_session(profiler, arrays, tels, traces[0].num_fns, lambda tk: None)

    def dying(ticks, fail_at=100):
        for tk in ticks:
            if tk.t >= fail_at:  # well past bootstrap: engine is ticking
                raise RuntimeError("sensor fabric went away")
            yield tk

    with pytest.raises(RuntimeError, match="sensor fabric went away"):
        sess.ingest(
            dying(sim.stream_fleet(traces, seeds=[11, 12])), prefetch=2, drain=True
        )
    # Both stages joined before ingest returned: no wait loop for the drain
    # worker (close() joins it); the producer gets the generator-close path.
    _assert_no_leak("session-drain", before_drain)
    _assert_no_leak("prefetch-producer", before_prod, wait=True)
    # the session is reusable for a fresh drained ingest after the abort
    assert sess._drain is None


def test_drain_hook_exception_reraises_at_caller():
    """An ``on_tick`` hook blowing up ON THE DRAIN THREAD must surface at
    the ingesting caller (not vanish into the worker) with both background
    threads joined."""
    profiler, sim, traces, tels, arrays = _fixture()
    before_drain = len(_live_threads("session-drain"))
    before_prod = len(_live_threads("prefetch-producer"))

    def bad_hook(tick):
        if tick.t >= 100:
            raise ValueError("tracker rejected tick")

    sess = _open_session(profiler, arrays, tels, traces[0].num_fns, bad_hook)
    with pytest.raises(ValueError, match="tracker rejected tick"):
        sess.ingest(
            sim.stream_fleet(traces, seeds=[11, 12]), prefetch=2, drain=True
        )
    _assert_no_leak("session-drain", before_drain)
    _assert_no_leak("prefetch-producer", before_prod, wait=True)


def test_drain_rejects_reentrant_ingest():
    """A second drained ingest while one is running on the same session is
    a caller bug and must be refused loudly."""
    profiler, sim, traces, tels, arrays = _fixture()
    sess = _open_session(profiler, arrays, tels, traces[0].num_fns, None)

    def reenter(ticks):
        it = iter(ticks)
        yield next(it)
        with pytest.raises(ValueError, match="already running"):
            sess.ingest(iter([]), drain=True)
        yield from it

    sess.ingest(
        reenter(sim.stream_fleet(traces, seeds=[11, 12])), prefetch=2, drain=True
    )
    reports = sess.finalize()
    assert len(reports) == len(arrays)


def test_ingest_retraces_nothing_after_warmup():
    """After one warm-up ingest, fresh sessions of the same fleet add no
    ``fleet_step`` trace, whether they ingest inline, prefetched or
    drained: the stream compiles once per fleet shape."""
    from repro.core.engine import fleet_step

    profiler, sim, traces, tels, arrays = _fixture()

    def run(prefetch, drain):
        sess = _open_session(profiler, arrays, tels, traces[0].num_fns, None)
        sess.ingest(
            sim.stream_fleet(traces, seeds=[11, 12]), prefetch=prefetch, drain=drain
        )
        sess.finalize()

    run(0, False)
    before = fleet_step._cache_size()
    for prefetch, drain in ((0, False), (4, False), (4, True)):
        run(prefetch, drain)
    assert fleet_step._cache_size() == before
