"""Mesh-dispatch stage: the B-node axis over a FleetMesh via shard_map.

Every shard_map wrapper the engine package owns lives here — the segment
engines' runner, the streaming step's, and the slot reset's — so mesh
dispatch is written in exactly one stage.  Per-node Kalman/disaggregation
math is node-independent, so every sharded program is collective-free;
fleet-level reductions live in ``distributed.sharding``.

The wrappers are parameterized by the *local* function they shard (the
engine entry point or step/reset body) and cached on it together with the
static configuration, so repeated calls — benchmarks, the control plane's
per-segment loop, a live stream's every tick — reuse one executable.
"""

from __future__ import annotations

import functools

import jax

from repro.core.engine.masking import _apply_mask
from repro.core.engine.types import (
    EngineConfig,
    FleetResult,
    FleetStep,
    FleetStreamState,
    TickAttribution,
)


@functools.lru_cache(maxsize=None)
def _sharded_segment_runner(fn, config: EngineConfig, with_ticks: bool, mesh, default_init: bool):
    """Compiled shard_map wrapper for a segment engine (``run_fleet``,
    ``run_fleet_gram``, or ``run_fleet_stream``).

    Each device traces the *unsharded* engine on its local ``B/n`` node
    block — per-node Kalman/disaggregation math is node-independent, so the
    sharded program contains no collectives at all; fleet-level reductions
    live in ``distributed.sharding.fleet_attribution_totals``.  Cached per
    (engine, config, with_ticks, mesh, default_init) so repeated calls
    (benchmarks, the control plane's per-segment loop) reuse one
    executable.  ``default_init`` selects the no-init-block variant, which
    lets the engine derive X_0 from its (mask-folded) local inputs on
    device instead of the host pre-computing masked defaults.
    """
    from jax.sharding import PartitionSpec as P

    node = P(mesh.axis)

    if default_init:
        def local(inputs):
            return fn(inputs, config, with_ticks=with_ticks)

        in_specs = (node,)
    else:
        def local(inputs, init_c, init_w):
            return fn(inputs, config, init_c=init_c, init_w=init_w, with_ticks=with_ticks)

        in_specs = (node, node, node)

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh.mesh,
            in_specs=in_specs,
            out_specs=node,
            check_vma=False,
        )
    )


def _run_sharded(fn, inputs, config, init_c, init_w, with_ticks, mesh) -> FleetResult:
    """Dispatch a segment engine over a ``FleetMesh`` (see docs/architecture.md)."""
    mesh.validate(inputs.c.shape[0])
    default_init = init_c is None and init_w is None
    runner = _sharded_segment_runner(fn, config, with_ticks, mesh, default_init)
    if default_init:
        # The engine folds the mask and derives X_0 per local shard.
        return runner(inputs)
    if init_c is None or init_w is None:
        # Mixed case: the missing default must be the MASKED inputs, or a
        # ragged fleet's padding would leak into the init gram.
        masked = _apply_mask(inputs)
        init_c = masked.c if init_c is None else init_c
        init_w = masked.w if init_w is None else init_w
    return runner(inputs, init_c, init_w)


@functools.lru_cache(maxsize=None)
def _sharded_step_runner(step_impl, config: EngineConfig, mesh, has_valid: bool):
    """shard_map of the streaming step over a ``FleetMesh`` (cached per
    (step body, config, mesh, has_valid) — together with the jit cache this
    keeps the sharded stream at exactly one trace for its whole lifetime).

    Array state/step/attribution leaves shard over the node axis — the
    ragged-fleet ``valid`` flag included, so each device only ever sees its
    own node block's liveness; the scalar
    ``tick_in_step``/``step_idx``/``step_completed`` counters are
    replicated (every device advances them identically).
    """
    from jax.sharding import PartitionSpec as P

    node, rep = P(mesh.axis), P()
    state_specs = FleetStreamState(
        kalman=node, c_buf=node, w_buf=node, a=node,
        lat_sum=node, lat_sumsq=node, tick_in_step=rep, step_idx=rep,
    )
    step_specs = FleetStep(
        c=node, w=node, a=node, lat_sum=node, lat_sumsq=node,
        valid=node if has_valid else None,
    )
    att_specs = TickAttribution(
        tick_power=node, unattributed=node, x=node, step_completed=rep
    )
    return jax.shard_map(
        functools.partial(step_impl, config=config),
        mesh=mesh.mesh,
        in_specs=(state_specs, step_specs),
        out_specs=(state_specs, att_specs),
        check_vma=False,
    )


@functools.lru_cache(maxsize=None)
def _sharded_reset_runner(reset_local, mesh):
    """shard_map of the slot reset over a ``FleetMesh`` (cached per
    (reset body, mesh)).

    The reset flags and replacement X_0 rows shard with the node axis —
    each device rewrites only its own slot block; the replicated step
    counters pass through untouched, so the reset composes with a live
    sharded stream without any collective."""
    from jax.sharding import PartitionSpec as P

    node, rep = P(mesh.axis), P()
    state_specs = FleetStreamState(
        kalman=node, c_buf=node, w_buf=node, a=node,
        lat_sum=node, lat_sumsq=node, tick_in_step=rep, step_idx=rep,
    )
    return jax.shard_map(
        reset_local,
        mesh=mesh.mesh,
        in_specs=(state_specs, node, node),
        out_specs=state_specs,
        check_vma=False,
    )
