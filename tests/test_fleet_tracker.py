"""The fleet footprint tracker against a plain per-node fold.

``profile_fleet`` folds each engine tick into one ``FleetFootprintTracker``
with a masked, vectorized update.  Here the same stream is folded again node
by node in plain numpy — the seed's per-node ``observe_step`` /
``observe_tick`` arithmetic, X_CPU added per node in combined mode — and every
node's row view must equal that fold bitwise: the same float64 adds in the
same order, dead ragged rows untouched.  Cases cross pure/combined mode with
uniform/ragged fleets, plus the slot pool and a live counter-model refit
(the X_CPU snapshot changes mid-stream).
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.profiler import FaasMeterProfiler, ProfilerConfig
from repro.serving.control_plane import EnergyFirstControlPlane, FleetFootprintTracker
from repro.telemetry.simulator import SimulatorConfig
from repro.workload.azure import WorkloadConfig, generate_trace
from repro.workload.functions import paper_functions

INIT, STEP = 60, 30
UNIFORM = (150.0, 150.0, 150.0)
RAGGED = (150.0, 120.0, 150.0)


class _NodeFold:
    """One node's footprints, folded the seed's way: (M,) rows, Python
    floats and counters."""

    def __init__(self, num_fns, idle_watts):
        self.m = num_fns
        self.idle_watts = idle_watts
        self.j_indiv = np.zeros(num_fns)
        self.invocations = np.zeros(num_fns)
        self.elapsed_s = 0.0
        self.steps_seen = 0
        self.ticks_seen = 0

    def step(self, x, busy, a, seconds):
        m = self.m
        self.j_indiv += np.asarray(busy[:m], float) * np.asarray(x[:m], float)
        self.invocations += np.asarray(a[:m], float)
        self.elapsed_s += seconds
        self.steps_seen += 1

    def tick(self, x, busy, a, seconds):
        self.step(x, busy, a, seconds)
        self.ticks_seen += 1

    def per_invocation_indiv(self):
        inv = self.invocations
        return np.where(inv > 0, self.j_indiv / np.maximum(inv, 1.0), 0.0)

    def per_invocation_total(self):
        active = self.invocations > 0
        n_active = max(int(active.sum()), 1)
        idle_j = self.idle_watts * self.elapsed_s / n_active
        total = self.j_indiv + np.where(active, idle_j, 0.0)
        return np.where(active, total / np.maximum(self.invocations, 1.0), 0.0)


def _stream(monkeypatch, mode, durations, slots=None, refit=False):
    """Run ``profile_fleet`` and record what its tracker was fed: the
    bootstrap seed, every tick, and the X_CPU snapshot each fold used."""
    reg = paper_functions()
    cp = EnergyFirstControlPlane(
        reg, SimulatorConfig(platform="server", seed=0),
        ProfilerConfig(init_windows=INIT, step_windows=STEP, mode=mode),
    )
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=s))
        for s, d in enumerate(durations)
    ]
    rec = {"ticks": [], "x_cpu": [], "fleet": []}
    start = FaasMeterProfiler.start_fleet_stream

    def spy(self, *args, on_bootstrap, **kw):
        def boot(sess):
            rec["boot"] = (
                np.array(sess.x0), np.array(sess.init_busy_seconds),
                np.array(sess.init_invocations), sess.init_seconds,
            )
            if sess.x_cpu is not None:
                rec["x_cpu"].append(np.array(sess.x_cpu))
            on_bootstrap(sess)

        rec["session"] = start(self, *args, on_bootstrap=boot, **kw)
        return rec["session"]

    monkeypatch.setattr(FaasMeterProfiler, "start_fleet_stream", spy)

    def on_tick(tk, trackers):
        rec["fleet"].append(trackers)
        rec["ticks"].append((
            np.array(tk.x), np.array(tk.busy_seconds), np.array(tk.a),
            None if tk.valid is None else np.array(tk.valid), len(rec["x_cpu"]) - 1,
        ))
        if refit and tk.step_completed:
            sess = rec["session"]
            flags = sess.refit_counter_models(np.ones(sess.b, bool), window_steps=1)
            assert flags.any()
            rec["x_cpu"].append(np.array(sess.x_cpu))

    out = cp.profile_fleet(
        traces, seeds=[21, 22, 23], mode=mode, mesh=None, slots=slots,
        on_tick=on_tick,
    )
    return out, rec, traces[0].num_fns, [p.sim.telemetry.idle_watts for p in out]


def _fold(rec, num_fns, idle, combined, delta):
    """The plain per-node fold of everything the tracker was fed."""
    x0, busy0, a0, secs0 = rec["boot"]
    folds = [_NodeFold(num_fns, w) for w in idle]

    def full_x(x, i, snap):
        return np.asarray(x[:num_fns]) + rec["x_cpu"][snap][i] if combined else x

    for i, f in enumerate(folds):
        f.step(full_x(x0[i], i, 0), busy0[i], a0[i], secs0)
    for x, busy, a, valid, snap in rec["ticks"]:
        for i, f in enumerate(folds):
            if valid is None or valid[i]:
                f.tick(full_x(x[i], i, snap), busy[i], a[i], delta)
    return folds


@pytest.mark.parametrize(
    "mode,durations,slots,refit",
    [
        ("pure", UNIFORM, None, False),
        ("pure", RAGGED, None, False),
        ("combined", UNIFORM, None, False),
        ("combined", RAGGED, None, False),
        ("combined", RAGGED, 4, False),
        ("combined", UNIFORM, None, True),
    ],
    ids=["pure-uniform", "pure-ragged", "combined-uniform", "combined-ragged",
         "combined-ragged-slots", "combined-refit"],
)
def test_fleet_tracker_equals_per_node_fold_bitwise(monkeypatch, mode, durations, slots, refit):
    out, rec, num_fns, idle = _stream(monkeypatch, mode, durations, slots, refit)
    combined = mode == "combined"
    folds = _fold(rec, num_fns, idle, combined, delta=1.0)

    fleet = rec["fleet"][0]
    assert isinstance(fleet, FleetFootprintTracker)
    assert all(f is fleet for f in rec["fleet"]) and len(fleet) == len(durations)
    if durations == RAGGED:
        assert any(v is not None and not v.all() for _, _, _, v, _ in rec["ticks"])
    if refit:
        assert len(rec["x_cpu"]) > 1
        assert not np.array_equal(rec["x_cpu"][0], rec["x_cpu"][-1])
    for i, (prof, f) in enumerate(zip(out, folds)):
        tr = prof.footprint_stream
        assert tr is fleet[i]
        assert np.array_equal(tr.j_indiv, f.j_indiv)
        assert np.array_equal(tr.invocations, f.invocations)
        assert tr.elapsed_s == f.elapsed_s
        assert tr.ticks_seen == f.ticks_seen and tr.steps_seen == f.steps_seen
        assert tr.idle_watts == f.idle_watts
        assert np.array_equal(tr.per_invocation_indiv, f.per_invocation_indiv())
        assert np.array_equal(tr.per_invocation_total, f.per_invocation_total())
        assert np.array_equal(fleet.per_invocation_indiv[i], f.per_invocation_indiv())
        assert np.array_equal(fleet.per_invocation_total[i], f.per_invocation_total())
        with pytest.raises(ValueError):
            tr.j_indiv[0] = 1.0  # a view reads the fleet's state, never writes it
    want_ticks = [int((d - INIT) // STEP) * STEP for d in durations]
    assert [tr.ticks_seen for tr in fleet] == want_ticks
