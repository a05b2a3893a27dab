"""Closed-loop energy control (ControlLoop): capping invariants, work
conservation, Azure-trace-scale overshoot reduction, retrain-on-stream
recovery, placement semantics, determinism.

The loop runs one causal control round against the live streaming replay
(observed power = the uncontrolled baseline's telemetry), then the reshaped
``controlled_traces()`` are re-simulated to measure what the control
actually did — every comparison here runs on that second pass.
"""

import numpy as np
import pytest

from repro.core.batched_engine import (
    EngineConfig,
    pack_fleet_inputs,
    run_fleet,
    run_fleet_gram,
    run_fleet_stream,
)
from repro.core.capping import CappingConfig, FleetPowerCapController
from repro.core.contribution import contribution_matrix, invocation_counts
from repro.core.profiler import ProfilerConfig
from repro.serving.control_plane import (
    ControlConfig,
    ControlLoop,
    EnergyFirstControlPlane,
)
from repro.serving.scheduler import (
    EnergyAwareScheduler,
    Invocation,
    SchedulerConfig,
    energy_aware_placement,
)
from repro.telemetry.simulator import SimulatorConfig, chip_drift_transform
from repro.workload.azure import WorkloadConfig, fleet_traces
from repro.workload.functions import paper_functions

import jax.numpy as jnp

PCFG = ProfilerConfig(init_windows=60, step_windows=30)


def _controlled_run(
    *,
    duration=240.0,
    load=6.0,
    nodes=3,
    seed=3,
    quantile=0.85,
    tick_transform=None,
    **ctl_kw,
):
    """One full closed-loop replay: returns (registry, control plane,
    original traces, uncontrolled (B, N) power, cap, finished loop)."""
    reg = paper_functions()
    traces = fleet_traces(
        reg, WorkloadConfig(duration_s=duration, load=load, seed=seed), nodes
    )
    cp = EnergyFirstControlPlane(
        reg, SimulatorConfig(platform="server", seed=0), PCFG
    )
    sims = cp.simulator.simulate_fleet(traces, None)
    w = np.stack([np.asarray(s.telemetry.system_power) for s in sims])
    cap = float(np.quantile(w, quantile))
    loop = ControlLoop(ControlConfig(cap_watts=cap, **ctl_kw))
    cp.profile_fleet(
        traces, mode="combined", mesh=None, control=loop,
        tick_transform=tick_transform,
    )
    return reg, cp, traces, w, cap, loop


def _resimulate(cp, loop):
    ct = loop.controlled_traces()
    sims = cp.simulator.simulate_fleet(ct, None)
    return ct, np.stack([np.asarray(s.telemetry.system_power) for s in sims])


def _counts_per_fn(traces, num_fns):
    """(B, M) invocation counts per node."""
    out = np.zeros((len(traces), num_fns))
    for i, t in enumerate(traces):
        valid = t.fn_id >= 0
        np.add.at(out[i], t.fn_id[valid], 1.0)
    return out


def _busy_per_fn(traces, num_fns):
    """(B, M) total busy seconds per node."""
    out = np.zeros((len(traces), num_fns))
    for i, t in enumerate(traces):
        valid = t.fn_id >= 0
        np.add.at(
            out[i], t.fn_id[valid], (t.end - t.start)[valid].astype(np.float64)
        )
    return out


class TestControlLoopSmall:
    """Moderate-load replay: invariants that must hold on any controlled run."""

    @pytest.fixture(scope="class")
    def run(self):
        reg, cp, traces, w, cap, loop = _controlled_run()
        ct, wc = _resimulate(cp, loop)
        return reg, cp, traces, w, cap, loop, ct, wc

    def test_overshoot_fraction_bounds(self, run):
        _, _, _, w, cap, loop, _, wc = run
        s = loop.fleet.stats
        assert 0.0 <= s.overshoot_fraction <= 1.0
        summ = loop.summary()
        assert 0.0 <= summ["observed_overshoot_fraction"] <= 1.0
        assert summ["deferred_by_cap"] >= 0
        assert summ["mean_queue_wait_s"] >= 0.0
        assert summ["max_queue_wait_s"] >= summ["mean_queue_wait_s"]
        assert np.isfinite(summ["billed_joules"]) and summ["billed_joules"] > 0

    def test_controlled_overshoot_below_uncontrolled(self, run):
        _, _, _, w, cap, loop, _, wc = run
        assert float(np.mean(wc > cap)) < float(np.mean(w > cap))

    def test_admission_conserves_work(self, run):
        """Deferral moves starts, never drops or duplicates work: fleet-wide
        per-function invocation counts and total busy seconds are identical
        (placement may migrate an invocation across nodes)."""
        reg, _, traces, _, _, _, ct, _ = run
        m = len(reg)
        np.testing.assert_array_equal(
            _counts_per_fn(traces, m).sum(0), _counts_per_fn(ct, m).sum(0)
        )
        np.testing.assert_allclose(
            _busy_per_fn(traces, m).sum(0), _busy_per_fn(ct, m).sum(0),
            rtol=1e-5, atol=1e-2,
        )

    def test_starts_only_move_forward(self, run):
        """The multiset of (fn, duration) pairs is preserved and the total
        start-time shift is non-negative: capping defers, never hoists."""
        reg, _, traces, _, _, _, ct, _ = run
        orig = np.sort(
            np.concatenate([(t.end - t.start)[t.fn_id >= 0] for t in traces])
        )
        ctrl = np.sort(
            np.concatenate([(t.end - t.start)[t.fn_id >= 0] for t in ct])
        )
        # Traces store float32 start/end; a deferred start at a larger
        # magnitude re-quantizes end - start, so durations match to float32
        # absolute precision at the shifted offset, not exactly.
        np.testing.assert_allclose(orig, ctrl, rtol=1e-5, atol=2e-3)
        t_orig = np.concatenate([t.start[t.fn_id >= 0] for t in traces])
        t_ctrl = np.concatenate([t.start[t.fn_id >= 0] for t in ct])
        assert t_ctrl.sum() >= t_orig.sum() - 1e-3

    def test_live_price_meter_bills_during_segment(self, run):
        reg, _, _, _, _, loop, _, _ = run
        assert loop.meter.ticks_seen > 0
        assert float(np.sum(loop.meter.j_total)) > 0.0
        # Conservation of the live bill: total == attributed + idle accrual.
        np.testing.assert_allclose(
            float(np.sum(loop.meter.j_total)),
            float(np.sum(loop.meter.j_indiv)) + loop.meter.idle_joules,
            rtol=1e-9,
        )


    def test_footprint_of_is_the_mean_over_node_views(self, run):
        """The scheduler's J_lambda is the fleet mean of the live per-node
        footprints, over the nodes that metered the function."""
        reg, _, _, _, _, loop, _, _ = run
        views = list(loop.trackers)
        assert len(views) == loop.b
        for j, name in enumerate(reg.names):
            vals = [tr.per_invocation_indiv[j] for tr in views if tr.invocations[j] > 0]
            want = float(np.mean(vals)) if vals else None
            assert loop._footprint_of(name) == want


class TestNoMigration:
    def test_per_node_counts_preserved_without_placement(self):
        reg, cp, traces, _, _, loop = _controlled_run(
            duration=150.0, load=4.0, nodes=2, seed=5, placement=False
        )
        ct, _ = _resimulate(cp, loop)
        m = len(reg)
        np.testing.assert_array_equal(
            _counts_per_fn(traces, m), _counts_per_fn(ct, m)
        )
        np.testing.assert_allclose(
            _busy_per_fn(traces, m), _busy_per_fn(ct, m), rtol=1e-5, atol=1e-2
        )


class TestDeterminism:
    def test_bitwise_deterministic_replay(self):
        outs = []
        for _ in range(2):
            _, cp, _, _, _, loop = _controlled_run(
                duration=150.0, load=4.0, nodes=2, seed=5
            )
            ct, wc = _resimulate(cp, loop)
            outs.append((ct, wc, loop.summary()))
        (ct0, wc0, s0), (ct1, wc1, s1) = outs
        for a, b in zip(ct0, ct1):
            np.testing.assert_array_equal(a.fn_id, b.fn_id)
            np.testing.assert_array_equal(a.start, b.start)
            np.testing.assert_array_equal(a.end, b.end)
        np.testing.assert_array_equal(wc0, wc1)
        assert s0 == s1


class TestPlacement:
    """Scheduler/placement semantics driven directly (no replay)."""

    def _cfg(self, cap=200.0):
        return CappingConfig(power_cap_watts=cap, control_interval_s=1.0)

    def test_placement_prefers_headroom(self):
        fleet = FleetPowerCapController(self._cfg(), 3)
        fleet.observe_power(np.asarray([150.0, 50.0, 100.0]))
        assert energy_aware_placement(fleet, 10.0, 1.0) == 1

    def test_placement_respects_live_mask(self):
        fleet = FleetPowerCapController(self._cfg(), 3)
        fleet.observe_power(np.asarray([150.0, 50.0, 100.0]))
        live = np.asarray([True, False, True])
        assert energy_aware_placement(fleet, 10.0, 1.0, live=live) == 2

    def test_placement_none_when_no_headroom(self):
        fleet = FleetPowerCapController(self._cfg(), 2)
        fleet.observe_power(np.asarray([199.0, 199.0]))
        assert energy_aware_placement(fleet, 50.0, 1.0) is None

    def test_would_admit_probe_is_pure(self):
        fleet = FleetPowerCapController(self._cfg(), 2)
        fleet.observe_power(np.asarray([50.0, 50.0]))
        before = fleet.stats.decisions
        assert fleet.would_admit(0, 10.0, 1.0)
        assert fleet.stats.decisions == before  # probe left no trace
        assert fleet.nodes[0]._current_power == 50.0

    def _sched(self):
        return EnergyAwareScheduler(
            SchedulerConfig(capping=self._cfg()),
            executor=lambda inv: inv.payload["dur"],
            footprint_of=lambda fn: 5.0,
            mean_latency_of=lambda fn: 1.0,
        )

    def test_drain_fleet_no_migration_uses_origin_node(self):
        s = self._sched()
        fleet = FleetPowerCapController(self._cfg(), 2)
        fleet.observe_power(np.asarray([0.0, 0.0]))
        s.submit(Invocation("f", arrival=0.0, payload={"node": 1, "dur": 1.0}))
        placed = s.drain_fleet(2.0, fleet=fleet, placement=False)
        assert [n for _, n in placed] == [1]

    def test_deferred_invocation_restarts_at_admitting_window(self):
        s = self._sched()
        fleet = FleetPowerCapController(self._cfg(), 1)
        fleet.observe_power(np.asarray([0.0]))
        s.submit(Invocation("f", arrival=0.5, payload={"node": 0, "dur": 1.0}))
        (inv, _), = s.drain_fleet(3.0, fleet=fleet)
        assert inv.started_at == 3.0 and inv.queue_wait == pytest.approx(2.5)

    def test_same_window_admission_keeps_arrival(self):
        s = self._sched()
        fleet = FleetPowerCapController(self._cfg(), 1)
        fleet.observe_power(np.asarray([0.0]))
        s.submit(Invocation("f", arrival=4.5, payload={"node": 0, "dur": 1.0}))
        (inv, _), = s.drain_fleet(4.0, fleet=fleet)
        assert inv.started_at == 4.5 and inv.queue_wait == 0.0

    def test_head_of_line_blocking(self):
        s = self._sched()
        fleet = FleetPowerCapController(
            CappingConfig(power_cap_watts=100.0, control_interval_s=1.0), 1
        )
        fleet.observe_power(np.asarray([97.0]))  # head's 5 J / 1 s won't fit
        s.submit(Invocation("big", arrival=0.0, payload={"node": 0, "dur": 1.0}))
        s.submit(Invocation("small", arrival=0.0, payload={"node": 0, "dur": 1.0}))
        assert s.drain_fleet(1.0, fleet=fleet) == []
        assert len(s.queue) == 2 and s.stats.deferred_by_cap == 1


class TestRetrainOnStream:
    def test_drift_triggers_retrain_and_recovers(self):
        """Mid-stream chip drift -> retrain_needed fires -> the fleet-batched
        sliding-window refit swaps models in and model_errors recover below
        the pre-drift threshold (ISSUE acceptance pin)."""
        _, cp, _, _, _, loop = _controlled_run(
            duration=300.0, load=4.0, nodes=2, seed=11,
            tick_transform=chip_drift_transform(1.4, 120.0),
        )
        errs = np.stack(loop.session.model_errors)  # (steps, B)
        thr = loop.session._retrain_cfg.retrain_threshold
        assert errs[0].max() < thr                  # clean before the drift
        assert errs.max() > thr                     # the drift was visible
        assert loop.retrain_events                  # and acted upon
        assert len(loop.session.refits) >= 1
        assert errs[-1].max() < thr                 # recovered after refit
        assert errs[-1].max() < errs.max() / 3      # and by a wide margin

    def test_retrain_disabled_leaves_errors_high(self):
        _, cp, _, _, _, loop = _controlled_run(
            duration=300.0, load=4.0, nodes=2, seed=11, retrain=False,
            tick_transform=chip_drift_transform(1.4, 120.0),
        )
        errs = np.stack(loop.session.model_errors)
        thr = loop.session._retrain_cfg.retrain_threshold
        assert not loop.retrain_events and not loop.session.refits
        assert errs[-1].max() > thr  # stale models never recover

    def test_resync_events_recorded(self):
        _, cp, _, _, _, loop = _controlled_run(
            duration=240.0, load=4.0, nodes=2, seed=5, resync_every_steps=2
        )
        assert loop.resync_events
        assert loop.session.skew_history
        # Causality clamp: re-estimated skews never exceed the bootstrap
        # lookahead the engine committed to.
        for _, skews in loop.session.skew_history:
            assert np.all(skews <= loop.session._lookahead + 1e-9)


@pytest.mark.slow
class TestAzureScale:
    """The ISSUE acceptance run: >= 1e5 invocations, strict overshoot
    reduction, per-tick conservation across all three fleet engines."""

    @pytest.fixture(scope="class")
    def scale(self):
        reg, cp, traces, w, cap, loop = _controlled_run(
            duration=420.0, load=45.0, nodes=4, seed=7, quantile=0.90
        )
        ct, wc = _resimulate(cp, loop)
        return reg, cp, traces, w, cap, loop, ct, wc

    def test_trace_scale(self, scale):
        _, _, traces, _, _, _, _, _ = scale
        assert sum(int((t.fn_id >= 0).sum()) for t in traces) >= 100_000

    def test_overshoot_strictly_below_uncontrolled(self, scale):
        _, _, _, w, cap, _, _, wc = scale
        controlled = float(np.mean(wc > cap))
        uncontrolled = float(np.mean(w > cap))
        assert controlled < uncontrolled, (controlled, uncontrolled)

    def test_work_conserved_at_scale(self, scale):
        reg, _, traces, _, _, _, ct, _ = scale
        m = len(reg)
        np.testing.assert_array_equal(
            _counts_per_fn(traces, m).sum(0), _counts_per_fn(ct, m).sum(0)
        )
        np.testing.assert_allclose(
            _busy_per_fn(traces, m).sum(0), _busy_per_fn(ct, m).sum(0),
            rtol=1e-5, atol=1e-2,
        )

    def test_per_tick_conservation_all_engines(self, scale):
        """Feed the controlled replay through run_fleet, run_fleet_gram and
        run_fleet_stream: per-tick attributed + unattributed reconstructs
        the measured power at 1e-5 relative on every engine."""
        reg, cp, _, _, _, _, ct, wc = scale
        m = len(reg)
        step = PCFG.step_windows
        n = (min(int(t.duration) for t in ct) // step) * step
        idle = cp.simulator.power_cfg.idle_w
        c = jnp.stack([
            contribution_matrix(
                jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end),
                num_fns=m, num_windows=n,
            )
            for t in ct
        ])
        a = jnp.stack([
            invocation_counts(
                jnp.asarray(t.fn_id), jnp.asarray(t.start),
                num_fns=m, num_windows=n,
            )
            for t in ct
        ])
        w = jnp.asarray(np.maximum(wc[:, :n] - idle, 0.0), jnp.float32)
        inputs = pack_fleet_inputs(
            c, w, a, a * 0.0, a * 0.0, step_windows=step
        )
        cfg = EngineConfig()
        scale_w = float(np.abs(wc[:, :n] - idle).max())
        for engine in (run_fleet, run_fleet_gram, run_fleet_stream):
            res = engine(inputs, cfg, with_ticks=True)
            recon = np.asarray(res.tick_power).sum(-1) + np.asarray(
                res.unattributed
            )
            err = np.abs(recon - np.asarray(inputs.w).reshape(recon.shape))
            assert err.max() / scale_w <= 1e-5, (engine.__name__, err.max())
