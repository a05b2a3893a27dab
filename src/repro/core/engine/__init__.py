"""Layered fleet engine: the paper's pipeline as composable stages.

The old ``core.batched_engine`` monolith is now a package of stages with
one declarative composition point, the ``FleetPlan`` (``engine.plan``):
mask folding, init-block defaults, the gram backend, mesh dispatch, and
the conserved-attribution/fn-fold exits are each written exactly once and
shared by all four engine paths (sequential oracle, batched segment,
gram-hoisted, streaming step).  Module DAG, imports only downward:

    types        dataclasses/NamedTuples shared by every stage
    masking      the single definition of ragged-fleet semantics
    targets      combined-mode (§4.3) target construction
    estimate     whole-trace X_0 solves (§4.2) + gram backends
    attribution  conserved per-tick splits + §4.4 spectra
    plan         FleetPlan: resolve_plan / finish_result / segment_plan
    sharding     shard_map dispatch of any stage over a FleetMesh
    segment      run_fleet / run_fleet_gram / run_fleet_sequential
    streaming    fleet_step / pack_tick_feed / tick_feed /
                 fleet_stream_reset_slots / run_fleet_stream
    packing      per-window host arrays → (B, S, n_w, ...) batches
    buckets      AOT-warmable compile shapes for serving

``repro.core.batched_engine`` remains as a deprecation shim re-exporting
this package's names (the *same* function objects, so jit caches and
``lru_cache`` keys are shared).
"""

from repro.core.engine.attribution import (
    _conserved_split,
    fleet_spectrum,
    tick_attribution,
)
from repro.core.engine.buckets import (
    DEFAULT_BUCKETS,
    FleetBucket,
    _bucket_init_solve,
    _pad_steps,
    bucket_for,
    bucketed_initial_estimate,
    bucketed_pad_waste,
    pack_fleet_buckets,
    pad_waste_frac,
    run_fleet_bucketed,
    warm_bucket_solvers,
)
from repro.core.engine.estimate import (
    _gram_fn,
    _init_states,
    _node_init_gram,
    fleet_initial_estimate,
)
from repro.core.engine.masking import _apply_mask, _mask_fn_axis, fold_step_valid
from repro.core.engine.packing import (
    pack_fleet_inputs,
    synthetic_fleet,
    synthetic_ragged_windows,
)
from repro.core.engine.plan import (
    FleetPlan,
    finish_result,
    resolve_plan,
    segment_plan,
)
from repro.core.engine.segment import (
    run_fleet,
    run_fleet_gram,
    run_fleet_sequential,
)
from repro.core.engine.sharding import (
    _run_sharded,
    _sharded_reset_runner,
    _sharded_segment_runner,
    _sharded_step_runner,
)
from repro.core.engine.streaming import (
    _fleet_step_impl,
    _fleet_ticks_masked,
    _reset_slots_impl,
    _reset_slots_local,
    _scan_stream,
    fleet_step,
    fleet_stream_init,
    fleet_stream_reset_slots,
    fleet_ticks,
    pack_tick_feed,
    run_fleet_stream,
    tick_feed,
)
from repro.core.engine.targets import combined_rest_target, fleet_rest_idle
from repro.core.engine.types import (
    Array,
    EngineConfig,
    FleetInputs,
    FleetResult,
    FleetStep,
    FleetStreamState,
    TickAttribution,
)

__all__ = [
    "Array",
    "DEFAULT_BUCKETS",
    "EngineConfig",
    "FleetBucket",
    "FleetInputs",
    "FleetPlan",
    "FleetResult",
    "FleetStep",
    "FleetStreamState",
    "TickAttribution",
    "bucket_for",
    "bucketed_initial_estimate",
    "bucketed_pad_waste",
    "combined_rest_target",
    "finish_result",
    "fleet_initial_estimate",
    "fleet_rest_idle",
    "fleet_spectrum",
    "fleet_step",
    "fleet_stream_init",
    "fleet_stream_reset_slots",
    "fleet_ticks",
    "fold_step_valid",
    "pack_fleet_buckets",
    "pack_fleet_inputs",
    "pack_tick_feed",
    "pad_waste_frac",
    "resolve_plan",
    "run_fleet",
    "run_fleet_bucketed",
    "run_fleet_gram",
    "run_fleet_sequential",
    "run_fleet_stream",
    "segment_plan",
    "synthetic_fleet",
    "synthetic_ragged_windows",
    "tick_attribution",
    "tick_feed",
    "warm_bucket_solvers",
]
