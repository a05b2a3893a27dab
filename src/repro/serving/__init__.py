"""Serving runtime: engines, scheduler, energy-first control plane.

Top of the layer stack (see ``scripts/check_layering.py``): these modules
may import anything below — the profiler orchestration, the session layer,
the jitted engine stages — but nothing below may import them back.

``ServeEngine`` (model-zoo continuous batching) is intentionally not
re-exported here: it drags the full model zoo in at import time, while the
energy-first control plane is what this package exists for.
"""

from repro.serving.control_plane import (
    CapRunResult,
    ControlConfig,
    ControlLoop,
    EnergyFirstControlPlane,
    FleetFootprintTracker,
    MeteredServer,
    ProfiledWorkload,
    StreamingFootprintTracker,
)
from repro.serving.scheduler import (
    EnergyAwareScheduler,
    Invocation,
    KeepAliveCache,
    SchedulerConfig,
    SchedulerStats,
    SlotAdmissionQueue,
    SlotRequest,
    energy_aware_placement,
)

__all__ = [
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
