"""Streaming filter stage: one jitted update per telemetry tick.

``fleet_step`` is the live metering hot path — a single
``(FleetStreamState, FleetStep) -> (FleetStreamState, TickAttribution)``
update per tick, with gram/rhs/innovation statistics accumulating inside
the carried state and the Kalman update firing at step boundaries via
``lax.cond``, so the control plane can meter, price, and cap *live*
instead of replaying a finished segment (docs/streaming.md).
``run_fleet_stream`` is the same step re-expressed as ``lax.scan`` over a
segment — one code path for online and offline, pinned against
``run_fleet`` and the sequential oracle through the shared
``resolve_plan``/``finish_result`` stages (``core.engine.plan``).
``fleet_stream_reset_slots`` is the slot pool's claim primitive
(docs/serving.md).  Mesh dispatch lives in ``core.engine.sharding``; the
per-node liveness fold lives in ``core.engine.masking``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine.estimate import _init_states
from repro.core.engine.masking import _apply_mask, fold_step_valid
from repro.core.engine.plan import finish_result, resolve_plan
from repro.core.engine.sharding import (
    _run_sharded,
    _sharded_reset_runner,
    _sharded_step_runner,
)
from repro.core.engine.attribution import _conserved_split
from repro.core.engine.targets import combined_rest_target
from repro.core.engine.types import (
    Array,
    EngineConfig,
    FleetInputs,
    FleetResult,
    FleetStep,
    FleetStreamState,
    TickAttribution,
)
from repro.core.kalman import KalmanState, kalman_step_gram, precompute_step_inputs


def fleet_stream_init(
    x0: Array, n_w: int, config: EngineConfig = EngineConfig(), *, mesh=None
) -> FleetStreamState:
    """Initial streaming state from a (B, M) whole-trace estimate X_0.

    Args:
      x0: (B, M) initial estimate — from ``fleet_initial_estimate`` over the
        init segment (§4.2), a previous session's final state, or another
        node's estimate (warm handoff *at a step boundary*; a handoff into
        a slot whose previous tenant wrote ticks earlier in the current
        partial step must go through ``fleet_stream_reset_slots``, which
        also clears the slot's ring-buffer rows).
      n_w: ticks per Kalman step (sizes the partial-step ring buffer; must
        match the ``n_w`` later passed to ``fleet_step``).
      config: engine configuration.
      mesh: optional ``distributed.sharding.FleetMesh``; the state is placed
        sharded over the node axis (scalar counters replicated), so the
        donated buffers live distributed for the whole stream — pass the
        same mesh to every subsequent ``fleet_step``.

    Returns:
      ``FleetStreamState`` with an empty partial step.
    """
    b, m = x0.shape
    zf = functools.partial(jnp.zeros, dtype=jnp.float32)
    # Copy x0: the returned state is donated by ``fleet_step``, and the
    # filter's initial x would otherwise alias the caller's buffer.
    x0 = jnp.array(x0, jnp.float32, copy=True)
    state = FleetStreamState(
        kalman=_init_states(x0),
        c_buf=zf((b, n_w, m)),
        w_buf=zf((b, n_w)),
        a=zf((b, m)),
        lat_sum=zf((b, m)),
        lat_sumsq=zf((b, m)),
        tick_in_step=jnp.zeros((), jnp.int32),
        step_idx=jnp.zeros((), jnp.int32),
    )
    if mesh is not None:
        mesh.validate(b)
        state = mesh.put(state)
    return state


def _fleet_step_impl(
    state: FleetStreamState,
    step: FleetStep,
    config: EngineConfig,
    mesh=None,
) -> tuple[FleetStreamState, TickAttribution]:
    """One streaming tick: buffer the tick, update at step boundaries.

    The step length n_w is the ring buffer's static shape
    (``state.c_buf.shape[1]``, fixed by ``fleet_stream_init``).  Mid-step
    ticks are O(B M): the tick's contribution/power rows are written in
    place into the carried ring buffer (the donated state makes these true
    in-place updates) and the invocation/latency sums accumulate.  Every
    ``n_w``-th tick closes the step behind ``lax.cond`` — only the taken
    branch executes — reducing the full buffer through the segment gram
    engine's own ``precompute_step_inputs`` and running the batched
    gram-domain Kalman update: the same update rule as ``run_fleet_gram``.

    With ``mesh`` the whole update runs under ``shard_map`` over the node
    axis: the carried state stays sharded on-device (each device owns its
    node block's ring buffer and filter state), the per-tick math is
    collective-free, and the replicated ``tick_in_step``/``step_idx``
    counters drive the *same* boundary ``lax.cond`` on every device.

    Ragged fleets (``step.valid``): invalid node-ticks write zero rows
    into the ring buffer and add nothing to the invocation sums, so the
    boundary update reduces each node's step over exactly its valid ticks
    — the same semantics as the segment engines' ``_apply_mask``, folded
    by the same masking stage (``masking.fold_step_valid``) — and their
    attribution is exactly zero.  ``valid`` is data: a stream keeps its
    single trace as nodes come and go.
    """
    if mesh is not None:
        step_fn = _sharded_step_runner(
            _fleet_step_impl, config, mesh, step.valid is not None
        )
        return step_fn(state, step)
    step = fold_step_valid(step)
    kcfg = config.kalman
    n_w = state.c_buf.shape[1]
    c_buf = jax.lax.dynamic_update_index_in_dim(
        state.c_buf, step.c, state.tick_in_step, axis=1
    )
    w_buf = jax.lax.dynamic_update_index_in_dim(
        state.w_buf, step.w, state.tick_in_step, axis=1
    )
    a = state.a + step.a
    lat_sum = state.lat_sum + step.lat_sum
    lat_sumsq = state.lat_sumsq + step.lat_sumsq
    tick = state.tick_in_step + 1
    boundary = tick >= n_w

    acc = (a, lat_sum, lat_sumsq)

    def do_update(operand):
        kal, (a, ls, lq) = operand
        inp = precompute_step_inputs(c_buf, w_buf, a, ls, lq, kcfg)
        kal, _ = jax.vmap(lambda st, i: kalman_step_gram(st, i, kcfg))(kal, inp)
        return kal, jax.tree.map(jnp.zeros_like, (a, ls, lq))

    def no_update(operand):
        return operand

    kal, acc = jax.lax.cond(boundary, do_update, no_update, (state.kalman, acc))
    a, lat_sum, lat_sumsq = acc

    # Causal conserved attribution under the freshest estimate.
    tick_power, unattributed = _conserved_split(step.c * kal.x, step.w, config.delta)
    att = TickAttribution(
        tick_power=tick_power,
        unattributed=unattributed,
        x=kal.x,
        step_completed=boundary,
    )
    new_state = FleetStreamState(
        kalman=kal, c_buf=c_buf, w_buf=w_buf,
        a=a, lat_sum=lat_sum, lat_sumsq=lat_sumsq,
        tick_in_step=jnp.where(boundary, 0, tick),
        step_idx=state.step_idx + boundary.astype(jnp.int32),
    )
    return new_state, att


fleet_step = functools.partial(
    jax.jit, static_argnames=("config", "mesh"), donate_argnums=(0,)
)(_fleet_step_impl)
fleet_step.__doc__ = """Jitted streaming tick update (donates ``state``).

``fleet_step(state, step, config=..., mesh=...)`` — the live metering hot
path.  ``config`` and ``mesh`` are static and the step length n_w comes
from the state's ring buffer shape (set by ``fleet_stream_init``), so
there is one trace per (fleet shape, config, mesh, has-valid) tuple,
reused for every subsequent tick — ``step.valid``'s *values* are data, so
ragged fleets with changing liveness never retrace; the retracing guards
in tests/test_streaming_engine.py, tests/test_sharded_fleet.py, and
tests/test_ragged_fleet.py pin this.
The input ``state`` is donated — its buffers are reused for the output
state (in place, and still sharded when a ``FleetMesh`` is active), so the
caller must rebind (``state, att = fleet_step(state, step, ...)``) and must
not touch the old state afterwards.
"""


def pack_tick_feed(w_sync, chip, idle, c, a, lat_sum, lat_sumsq, valid=None) -> np.ndarray:
    """One tick's whole feed as one float32 host array, the layout
    ``tick_feed`` unpacks: (B, 3 + 4 m), one more column with ``valid``.

    The synced system window, the chip window and the rest idle, then the
    contribution, invocation, latency-sum and latency-sum-of-squares rows
    (m columns each), then the liveness flag.  A pure-mode feed carries a
    zero chip column and the node's idle, which gives the target
    ``max(W_sys - idle, 0)`` exactly.
    """
    cols = [w_sync[:, None], chip[:, None], idle[:, None], c, a, lat_sum, lat_sumsq]
    if valid is not None:
        cols.append(valid[:, None])
    return np.concatenate(cols, axis=1, dtype=np.float32)


@functools.partial(jax.jit, static_argnames=("m", "has_valid"))
def tick_feed(packed: Array, *, m: int, has_valid: bool) -> FleetStep:
    """One tick's ``FleetStep`` from the array ``pack_tick_feed`` built.

    ``w`` is the combined rest target (§4.3), ``combined_rest_target`` of
    the first three columns.  One host->device transfer then carries the
    tick.  Under a ``FleetMesh`` it arrives node-sharded and every device
    unpacks its own nodes, so each leaf reaches ``fleet_step`` in its node
    shards with no collective.
    """
    rows = [packed[:, 3 + k * m:3 + (k + 1) * m] for k in range(4)]
    return FleetStep(
        c=rows[0], w=combined_rest_target(packed[:, 0], packed[:, 1], packed[:, 2]),
        a=rows[1], lat_sum=rows[2], lat_sumsq=rows[3],
        valid=packed[:, 3 + 4 * m] if has_valid else None,
    )


def _reset_slots_local(
    state: FleetStreamState, reset: Array, x0: Array
) -> FleetStreamState:
    """Unsharded slot-reset body (see ``fleet_stream_reset_slots``)."""
    r = reset.astype(jnp.float32)                       # (B,) 1 = reset
    rb = r[:, None] > 0                                 # (B, 1)
    fresh = _init_states(x0.astype(jnp.float32))
    kal = KalmanState(
        x=jnp.where(rb, fresh.x, state.kalman.x),
        p=jnp.where(rb, fresh.p, state.kalman.p),
        seen=jnp.where(rb, fresh.seen, state.kalman.seen),
        lat_mean=jnp.where(rb, fresh.lat_mean, state.kalman.lat_mean),
        lat_m2=jnp.where(rb, fresh.lat_m2, state.kalman.lat_m2),
        lat_count=jnp.where(rb, fresh.lat_count, state.kalman.lat_count),
    )
    keep = 1.0 - r
    return FleetStreamState(
        kalman=kal,
        c_buf=state.c_buf * keep[:, None, None],
        w_buf=state.w_buf * keep[:, None],
        a=state.a * keep[:, None],
        lat_sum=state.lat_sum * keep[:, None],
        lat_sumsq=state.lat_sumsq * keep[:, None],
        tick_in_step=state.tick_in_step,
        step_idx=state.step_idx,
    )


def _reset_slots_impl(
    state: FleetStreamState, reset: Array, x0: Array, mesh=None
) -> FleetStreamState:
    if mesh is not None:
        return _sharded_reset_runner(_reset_slots_local, mesh)(state, reset, x0)
    return _reset_slots_local(state, reset, x0)


fleet_stream_reset_slots = functools.partial(
    jax.jit, static_argnames=("mesh",), donate_argnums=(0,)
)(_reset_slots_impl)
fleet_stream_reset_slots.__doc__ = """Jitted slot reset on a live stream (donates ``state``).

``fleet_stream_reset_slots(state, reset, x0, mesh=...)`` rewrites the rows
of every slot flagged in ``reset`` ((B,) 1.0/0.0, *data* — any combination
of slots reuses one trace) to a fresh tenant: the Kalman row becomes
``kalman_init`` of that slot's row of ``x0`` ((B, M); ignored where
``reset`` is 0), and the slot's ring-buffer rows and partial-step
invocation/latency accumulators are zeroed.  The global
``tick_in_step``/``step_idx`` counters are untouched — the new tenant
joins the fleet's step clock mid-step.

This is the claim primitive of the slot pool
(``core.sessions.SlotFleetSession.admit``) and the fix for the
die-and-rejoin leak: ``FleetStep.valid`` only zeroes ticks from the moment
a node goes invalid, so rows its slot wrote *earlier in the current
partial step* (a dead tenant's last ticks, or a previous tenant entirely)
would otherwise be reduced into the next boundary update of whoever holds
the slot next.  Resetting at claim time makes a reused slot
indistinguishable from one in a freshly initialized pool.

Like ``fleet_step`` the input ``state`` is donated and ``mesh`` is static:
callers must rebind, and with a ``FleetMesh`` the rewrite runs under
``shard_map`` with flags and ``x0`` sharded over the node axis.
"""


@functools.partial(jax.jit, static_argnames=("config",))
def _scan_stream(
    state: FleetStreamState, ticks: FleetStep, config: EngineConfig
) -> tuple[FleetStreamState, TickAttribution]:
    """``lax.scan`` of the streaming step over time-major (T, B, ...) ticks."""

    def body(st, tk):
        return _fleet_step_impl(st, tk, config)

    return jax.lax.scan(body, state, ticks)


def fleet_ticks(inputs: FleetInputs) -> FleetStep:
    """Explode segment inputs into a time-major (T, B, ...) tick stream.

    Inverse of the (B, S, n_w) step grouping: T = S * n_w ticks, with each
    step's invocation/latency statistics placed on its first *valid* tick
    (the engine only reads their sums at boundaries, so placement among
    the valid ticks is free — an invalid tick would drop them, since the
    streaming step zeroes invalid node-ticks).  A ragged ``inputs.mask``
    becomes the per-tick ``FleetStep.valid`` flags.  Feed the result to
    ``lax.scan`` (``run_fleet_stream``) or slice ticks off it to drive
    ``fleet_step`` one dispatch at a time.
    """
    return _fleet_ticks_masked(_apply_mask(inputs))


def _fleet_ticks_masked(inputs: FleetInputs) -> FleetStep:
    """``fleet_ticks`` body for inputs whose mask is already folded in
    (``run_fleet_stream`` folds once and reuses the result for the init
    solve, the tick stream, and the final attribution)."""
    b, s, n_w, m = inputs.c.shape
    tm = lambda x: jnp.moveaxis(x.reshape((b, s * n_w) + x.shape[3:]), 0, 1)
    if inputs.mask is None:
        first = jnp.zeros((b, s), jnp.int32)
        valid = None
    else:
        first = jnp.argmax(inputs.mask, axis=-1).astype(jnp.int32)  # (B, S)
        valid = tm(inputs.mask.astype(inputs.w.dtype))              # (T, B)
    onehot = jax.nn.one_hot(first, n_w, dtype=inputs.a.dtype)       # (B, S, n_w)
    place = lambda x: onehot[..., None] * x[:, :, None, :]
    return FleetStep(
        c=tm(inputs.c), w=tm(inputs.w), a=tm(place(inputs.a)),
        lat_sum=tm(place(inputs.lat_sum)), lat_sumsq=tm(place(inputs.lat_sumsq)),
        valid=valid,
    )


def run_fleet_stream(
    inputs: FleetInputs,
    config: EngineConfig = EngineConfig(),
    *,
    init_c: Array | None = None,
    init_w: Array | None = None,
    with_ticks: bool = True,
    mesh=None,
) -> FleetResult:
    """The segment engine re-expressed as a scan over the streaming step.

    Same contract as ``run_fleet``: X_0 from one batched NNLS over the init
    block, then ``lax.scan`` of ``_fleet_step_impl`` over all T = S * n_w
    ticks — the *identical* code path the online ``fleet_step`` runs, so the
    streaming engine is pinned to the segment engines by construction.  The
    returned trajectory collects the boundary-tick estimates; ``tick_power``
    uses the segment engine's smoothed-within-step attribution for
    comparability (the causal live variant is what ``fleet_step`` emits).

    Args:
      inputs: (B, S, n_w, M) step-grouped fleet batch; a ragged
        ``inputs.mask`` flows into per-tick ``FleetStep.valid`` flags via
        ``fleet_ticks`` (same masked semantics as ``run_fleet``).
      config: engine configuration (``backend`` is ignored here — streaming
        accumulation is tick-wise by definition).
      init_c/init_w: optional dedicated init block for X_0 (profiler-style);
        defaults to the whole segment.
      with_ticks: also compute (B, T, M) conserved per-tick attribution.
      mesh: optional ``distributed.sharding.FleetMesh``; shards the node
        axis over the mesh devices exactly as in ``run_fleet``.

    Returns:
      ``FleetResult`` with ``state`` holding the final *Kalman* state of the
      stream (identical pytree to the other engines').
    """
    if mesh is not None:
        return _run_sharded(
            run_fleet_stream, inputs, config, init_c, init_w, with_ticks, mesh
        )
    plan = resolve_plan(inputs, config, init_c=init_c, init_w=init_w)
    inputs = plan.inputs
    x0 = plan.initial_estimate()
    b, s, n_w, m = inputs.c.shape
    state0 = fleet_stream_init(x0, n_w, config)
    final, att = _scan_stream(state0, _fleet_ticks_masked(inputs), config)
    # Boundary ticks carry each step's post-update estimate: the trajectory.
    traj = jnp.moveaxis(att.x.reshape(s, n_w, b, m)[:, -1], 1, 0)  # (B, S, M)
    return finish_result(
        plan, final_state=final.kalman, traj=traj, x0=x0, with_ticks=with_ticks
    )
