"""Host spans on the streaming tick path (``repro.tracing``).

A combined-mode ``profile_fleet`` stream runs under ``jax.profiler.trace``
and the recorded ``.xplane.pb`` is read back with ``ProfileData``.  Pinned:

- every span of the contract is recorded;
- ``faasmeter.session.dispatch``, ``faasmeter.session.emit`` and
  ``faasmeter.control.trackers`` occur once per emitted tick, with the
  ``tick`` values the ``on_tick`` hook saw;
- ``faasmeter.control.trackers`` is the fleet tracker's one update: it
  carries the rows it folded (``nodes``) and holds no span per node;
- ``faasmeter.engine.fleet_step`` lies inside ``faasmeter.session.dispatch``;
- one ``faasmeter.pull`` per device->host transfer the code path makes;
- one ``faasmeter.put`` per host->device transfer, each on one device;
- each dispatch launches two device programs, the combined target and the
  engine step (JAX's ``PjitFunction(...)`` host events);
- the ticks are bitwise the same with and without a trace;
- under ``ingest(drain=True)`` the emit spans sit on the drain thread.
"""

from __future__ import annotations

import collections
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.profiler import ProfilerConfig
from repro.serving.control_plane import EnergyFirstControlPlane
from repro.telemetry.simulator import SimulatorConfig
from repro.workload.azure import WorkloadConfig, generate_trace
from repro.workload.functions import paper_functions

DURATION = 150.0  # 60 init windows + 3 Kalman steps of 30
INIT, STEP = 60, 30
TICKS = list(range(INIT, int(DURATION)))
FIELDS = ("x", "tick_power", "unattributed", "busy_seconds", "a", "target", "w_sys")


def _run(trace_dir=None, drain=False, hook=None, slots=None):
    """One combined-mode stream of two server nodes; returns its ticks."""
    reg = paper_functions()
    cp = EnergyFirstControlPlane(
        reg, SimulatorConfig(platform="server", seed=0),
        ProfilerConfig(init_windows=INIT, step_windows=STEP, mode="combined"),
    )
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=DURATION, load=1.0, seed=s))
        for s in (3, 4)
    ]
    ticks = []

    def on_tick(tk, trackers):
        ticks.append(tk)
        if hook is not None:
            hook(tk)

    def go():
        cp.profile_fleet(traces, seeds=[21, 22], mode="combined", mesh=None,
                         on_tick=on_tick, drain=drain, slots=slots)

    if trace_dir is None:
        go()
        return ticks, None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        go()
    return ticks, _spans(trace_dir)


def _spans(trace_dir):
    """Per host thread line: [(name, start_ns, end_ns, stats)] of the
    ``faasmeter.*`` and ``test.*`` spans and JAX's ``PjitFunction(...)``
    launches."""
    from jax.profiler import ProfileData

    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                  for e in line.events if e.name.startswith(("faasmeter.", "test.", "PjitFunction("))]
            if ev:
                lines.append(ev)
    return lines


@pytest.fixture(scope="module")
def untraced():
    return _run()[0]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("trace"))


def _named(lines, name):
    return [ev for line in lines for ev in line if ev[0] == name]


def test_every_span_of_the_contract_is_recorded(traced):
    _, lines = traced
    names = {ev[0] for line in lines for ev in line if ev[0].startswith("faasmeter.")}
    assert names == {
        "faasmeter.ingest.wait", "faasmeter.ingest.push", "faasmeter.session.dispatch",
        "faasmeter.engine.fleet_step", "faasmeter.session.emit", "faasmeter.pull",
        "faasmeter.put", "faasmeter.control.trackers",
    }
    waits = _named(lines, "faasmeter.ingest.wait")
    assert all(0 <= ev[3]["depth"] <= 2 for ev in waits)
    pushes = _named(lines, "faasmeter.ingest.push")
    assert sorted(ev[3]["window"] for ev in pushes) == list(range(int(DURATION)))


@pytest.mark.parametrize("name", ["faasmeter.session.dispatch", "faasmeter.session.emit",
                                  "faasmeter.control.trackers"])
def test_one_span_per_emitted_tick(traced, name):
    ticks, lines = traced
    assert [tk.t for tk in ticks] == TICKS
    assert sorted(ev[3]["tick"] for ev in _named(lines, name)) == TICKS


def test_tracker_span_is_one_fleet_update_per_tick(traced):
    """Each emitted tick folds into the fleet tracker in one update: one
    ``faasmeter.control.trackers`` span with ``tick`` and ``nodes`` (the
    rows folded, both nodes here), and no span opened inside it per node."""
    _, lines = traced
    spans = _named(lines, "faasmeter.control.trackers")
    assert sorted((ev[3]["tick"], ev[3]["nodes"]) for ev in spans) == [(t, 2) for t in TICKS]
    for line in lines:
        for _, s, e, _ in (ev for ev in line if ev[0] == "faasmeter.control.trackers"):
            inside = [ev[0] for ev in line if s <= ev[1] and ev[2] <= e]
            assert inside == ["faasmeter.control.trackers"], inside


def test_fleet_step_lies_inside_dispatch(traced):
    _, lines = traced
    for line in lines:
        dispatch = [ev for ev in line if ev[0] == "faasmeter.session.dispatch"]
        steps = [ev for ev in line if ev[0] == "faasmeter.engine.fleet_step"]
        for _, s, e, _ in steps:
            assert sum(ds <= s and e <= de for _, ds, de, _ in dispatch) == 1
    assert len(_named(lines, "faasmeter.engine.fleet_step")) == len(TICKS)


def test_dispatch_launches_two_device_programs(traced):
    """Each tick's dispatch launches the combined rest target and the engine
    step, and nothing else: the contribution row is host data, so no eager
    slice, squeeze, broadcast or concatenate of it on the device.  JAX
    records a launch as nested ``PjitFunction(...)`` events (and traces the
    program's own calls under the first), so the outermost ones count."""
    _, lines = traced
    per_tick = {}
    for line in lines:
        launches = [ev for ev in line if ev[0].startswith("PjitFunction(")]
        for _, s, e, meta in (ev for ev in line if ev[0] == "faasmeter.session.dispatch"):
            inside = sorted((ev for ev in launches if s <= ev[1] and ev[2] <= e),
                            key=lambda ev: (ev[1], -ev[2]))
            outermost, end = [], -1
            for name, ls, le, _ in inside:
                if ls >= end:
                    outermost.append(name)
                    end = le
            per_tick[meta["tick"]] = sorted(outermost)
    assert sorted(per_tick) == TICKS
    want = ["PjitFunction(_fleet_step_impl)", "PjitFunction(combined_rest_target)"]
    assert all(names == want for names in per_tick.values()), per_tick[TICKS[0]]


def test_one_pull_per_device_transfer(traced):
    """Each pushed window pulls its principal column; each emitted tick
    pulls four arrays (``a`` and ``busy_seconds`` are host data already);
    each completed Kalman step pulls the retrain check's error and flags;
    the trackers pull X_CPU once, at bootstrap."""
    _, lines = traced
    pulls = _named(lines, "faasmeter.pull")
    by_site = collections.Counter(ev[3]["site"] for ev in pulls)
    steps = len(TICKS) // STEP
    assert by_site == {
        "push.principal": int(DURATION),
        "emit.x": len(TICKS), "emit.tick_power": len(TICKS),
        "emit.unattributed": len(TICKS), "emit.target": len(TICKS),
        "retrain.error": steps, "retrain.flags": steps,
        "control.x_cpu": 1,
    }
    emitted = {ev[3]["tick"] for ev in pulls if ev[3]["site"].startswith("emit.")}
    assert emitted == set(TICKS)
    # Each pull sits inside the span that made it, on the same thread.
    for line in lines:
        emits = [ev for ev in line if ev[0] == "faasmeter.session.emit"]
        for _, s, e, meta in line:
            if meta.get("site", "").startswith("emit."):
                assert any(es <= s and e <= ee and em["tick"] == meta["tick"]
                           for _, es, ee, em in emits)


def test_one_put_per_host_transfer(traced):
    """Each pushed window puts the principal's two CPU fractions; each
    dispatched tick puts the rest target's three inputs and the step's four
    rows; each completed Kalman step puts the retrain check's inputs.
    Without a mesh every put lands on one device, and a tick's puts lie
    inside its dispatch span."""
    _, lines = traced
    puts = _named(lines, "faasmeter.put")
    assert all(ev[3]["shards"] == 1 for ev in puts)
    by_site = collections.Counter(ev[3]["site"] for ev in puts)
    steps = len(TICKS) // STEP
    dispatch = ("w_sync", "chip", "rest_idle", "c", "a", "lat_sum", "lat_sumsq")
    assert by_site == {
        "push.cp_frac": int(DURATION), "push.sys_frac": int(DURATION),
        **{f"dispatch.{leaf}": len(TICKS) for leaf in dispatch},
        "retrain.features": steps, "retrain.chip": steps, "retrain.live": steps,
    }
    for line in lines:
        ticks = {em["tick"]: (ds, de) for name, ds, de, em in line
                 if name == "faasmeter.session.dispatch"}
        for name, s, e, meta in line:
            if name == "faasmeter.put" and meta["site"].startswith("dispatch."):
                ds, de = ticks[meta["tick"]]
                assert ds <= s and e <= de


def test_slot_mode_pulls_the_target_alone(tmp_path):
    """Through the slot pool each tick pulls its target to fill the pool's
    feeds; the contribution row is host data there too, so no
    ``pool.busy_seconds`` pull."""
    ticks, lines = _run(tmp_path, slots=3)
    assert [tk.t for tk in ticks] == TICKS
    by_site = collections.Counter(ev[3]["site"] for ev in _named(lines, "faasmeter.pull"))
    steps = len(TICKS) // STEP
    assert by_site == {
        "push.principal": int(DURATION), "pool.target": len(TICKS),
        "emit.x": len(TICKS), "emit.tick_power": len(TICKS),
        "emit.unattributed": len(TICKS), "emit.target": len(TICKS),
        "retrain.error": steps, "retrain.flags": steps,
        "control.x_cpu": 1,
    }


def test_traced_ticks_equal_untraced_bitwise(traced, untraced):
    ticks, _ = traced
    assert [tk.t for tk in ticks] == [tk.t for tk in untraced]
    for a, b in zip(ticks, untraced):
        assert a.step_completed == b.step_completed
        for f in FIELDS:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)


def test_drained_emit_spans_sit_on_the_drain_thread(tmp_path):
    threads = set()

    def hook(tk):
        threads.add(threading.current_thread().name)
        with jax.profiler.TraceAnnotation("test.hook", tick=tk.t):
            pass

    ticks, lines = _run(tmp_path, drain=True, hook=hook)
    assert [tk.t for tk in ticks] == TICKS and threads == {"session-drain"}
    (drain,) = [line for line in lines if any(ev[0] == "test.hook" for ev in line)]
    assert sorted(ev[3]["tick"] for ev in drain if ev[0] == "faasmeter.session.emit") == TICKS
    assert not any(ev[0] == "faasmeter.session.dispatch" for ev in drain)
    emits = [ev for ev in drain if ev[0] == "faasmeter.session.emit"]
    for _, s, e, meta in (ev for ev in drain if ev[0] == "test.hook"):
        assert any(es <= s and e <= ee and em["tick"] == meta["tick"]
                   for _, es, ee, em in emits)
