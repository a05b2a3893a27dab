"""API-surface pins: the packages' exported names are a frozen contract.

``repro.core``, ``repro.serving``, and ``repro.telemetry`` are the three
import surfaces external callers (benchmarks, notebooks, downstream code)
build on.  These tests snapshot each package's ``__all__`` exactly: a
refactor that drops or renames an export fails here — by design — and an
intentional API change must update the snapshot in the same commit.
"""

import importlib

import pytest

CORE_EXPORTS = [
    "activity_series",
    "contribution_matrix",
    "invocation_counts",
    "shared_principal_contribution",
    "DisaggregationConfig",
    "solve_nnls",
    "solve_ridge",
    "disaggregate",
    "per_invocation_energy",
    "KalmanConfig",
    "KalmanState",
    "kalman_init",
    "kalman_step",
    "run_kalman",
    "shapley_control_plane_share",
    "shapley_idle_share",
    "total_footprint",
    "cosine_similarity",
    "individual_difference",
    "total_power_error",
    "latency_normalized_variance",
    "coefficient_of_variation",
    "marginal_energy",
    "estimate_skew",
    "apply_shift",
    "synchronize",
    "CappingConfig",
    "PowerCapController",
    "FaasMeterProfiler",
    "ProfilerConfig",
    "FootprintReport",
]

SERVING_EXPORTS = [
    "CapRunResult",
    "ControlConfig",
    "ControlLoop",
    "EnergyAwareScheduler",
    "EnergyFirstControlPlane",
    "FleetFootprintTracker",
    "Invocation",
    "KeepAliveCache",
    "MeteredServer",
    "ProfiledWorkload",
    "SchedulerConfig",
    "SchedulerStats",
    "SlotAdmissionQueue",
    "SlotRequest",
    "StreamingFootprintTracker",
    "energy_aware_placement",
]

TELEMETRY_EXPORTS = [
    "PowerModelConfig",
    "NodePowerModel",
    "SensorConfig",
    "PowerSignal",
    "FleetPowerSignal",
    "FleetStreamingSensor",
    "FleetWindowResampler",
    "sense",
    "sense_fleet",
    "resample_to_windows",
    "resample_fleet",
    "window_counters",
    "function_counters",
    "NodeSimulator",
    "SimResult",
    "SimulatorConfig",
]

SNAPSHOTS = {
    "repro.core": CORE_EXPORTS,
    "repro.serving": SERVING_EXPORTS,
    "repro.telemetry": TELEMETRY_EXPORTS,
}


@pytest.mark.parametrize("pkg", sorted(SNAPSHOTS))
def test_package_all_matches_snapshot(pkg):
    mod = importlib.import_module(pkg)
    got, want = sorted(mod.__all__), sorted(SNAPSHOTS[pkg])
    missing = sorted(set(want) - set(got))
    extra = sorted(set(got) - set(want))
    assert got == want, (
        f"{pkg}.__all__ drifted from the pinned surface "
        f"(missing={missing}, unpinned-new={extra}); if intentional, "
        "update tests/test_api_surface.py in the same commit"
    )


@pytest.mark.parametrize("pkg", sorted(SNAPSHOTS))
def test_every_export_resolves(pkg):
    mod = importlib.import_module(pkg)
    unresolved = [n for n in mod.__all__ if not hasattr(mod, n)]
    assert not unresolved, f"{pkg}.__all__ names that don't resolve: {unresolved}"
