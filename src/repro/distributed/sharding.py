"""Sharding layers: logical-axis rules for models, fleet-axis mesh for the
fleet controller.

Two independent partitioning surfaces live here:

1. **Logical-axis rules** (MaxText-style, with divisibility fallback) for
   the model zoo — parameters/activations annotated with logical axes
   ("embed", "qkv", ...) mapped onto mesh axes by rule tables.
2. **Fleet-axis sharding** (:class:`FleetMesh`) for the FaasMeter fleet
   controller — the B-node axis of the batched/streaming disaggregation
   engines is sharded over a 1-D device mesh via ``shard_map``: per-node
   Kalman/disaggregation math runs entirely node-local (no collectives on
   the hot path) while fleet-level reductions
   (:func:`fleet_attribution_totals`) ``psum`` along the node axis.

Logical-axis rules (surface 1) in detail:

Parameters and activations are annotated with *logical* axes ("embed",
"qkv", "mlp", "vocab", "expert", "batch", "seq", "kv_heads", ...); rule
tables map logical axes onto mesh axes.  A mapping is applied only when

  1. the dimension is divisible by the product of the mesh-axis sizes, and
  2. none of those mesh axes is already used by another dimension of the
     same tensor (GSPMD requires each mesh axis at most once per spec).

Otherwise the dimension falls back along the rule's candidate chain and
ultimately to replication.  This is what lets one rule table cover all ten
assigned architectures (e.g. qwen2.5's 40 heads are not divisible by
model=16, but its flattened 40*128=5120 projection dim is).

Two built-in rule tables:

- ``TRAIN_RULES``: FSDP over "data" (weights' embed dim), TP over "model"
  (qkv/mlp/vocab/expert dims), batch over ("pod", "data"); gradients
  all-reduce over "pod" (pure DP across pods).
- ``SERVE_RULES``: weights TP over "model" and replicated over "data"
  (low-latency serving), batch over ("pod", "data"), KV cache batch-sharded
  with kv-heads on "model" when divisible (falls back to sequence).

Models call :func:`shard_activation` at block boundaries; it is a no-op
unless a rule context is active (set by the caller via
:func:`use_rules`), keeping model code mesh-agnostic.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

# Each rule: logical axis -> tuple of candidate mesh-axis tuples, tried in
# order; () means replicate.
Rules = dict[str, tuple[tuple[str, ...], ...]]

TRAIN_RULES: Rules = {
    "batch": (("pod", "data"), ("data",), ()),
    "seq": ((),),
    "embed": (("data",), ()),          # FSDP shard of weight rows
    "act_embed": ((),),                # activations keep embed replicated
    "qkv": (("model",), ()),           # flattened heads*head_dim
    "heads": (("model",), ()),
    "kv_heads": (("model",), ()),
    "o_in": (("model",), ()),
    "mlp": (("model",), ()),
    "vocab": (("model",), ()),
    "lm_head": (("model",), ()),      # unembed output dim (logits vocab)
    "expert": (("model",), ()),
    "expert_mlp": ((),),
    "kv_seq": (("model",), ()),        # decode KV-cache sequence fallback
    "layers": ((),),
    "state": ((),),
    "conv": ((),),
    "cap": (("pod", "data"), ("data",), ()),  # MoE capacity slots
    "frontend": ((),),
}

SERVE_RULES: Rules = {
    **TRAIN_RULES,
    "batch": (("pod", "data"), ("data",), ()),
    "embed": ((),),                    # weights replicated over data for serve
    "kv_heads": (("model",), ()),
}

#: Expert-parallel-first variant (§Perf H-B3): the "model" axis is reserved
#: for experts; attention/shared-MLP weights drop TP (their per-layer
#: activation all-reduces vanish — they are small relative to expert FFNs
#: in fine-grained MoE), FSDP over "data" stays.
EP_RULES: Rules = {
    **TRAIN_RULES,
    "qkv": ((),),
    "heads": ((),),
    "kv_heads": ((),),
    "o_in": ((),),
    "mlp": ((),),
}

#: ZeRO-3 / pure-FSDP variant (§Perf H-A2): the "model" axis joins the batch
#: axis (TP degree 1) so per-layer TP activation all-reduces vanish; weights
#: shard their row dim over the combined (data x model) = 256-way axis and
#: are all-gathered per layer per pass.  Wins when activation-AR bytes
#: exceed weight-gather bytes (dense train at B_loc x S x d >> params/layer).
#: NOT for MoE archs: expert parallelism needs the "model" axis.
ZERO3_RULES: Rules = {
    **TRAIN_RULES,
    "batch": (("pod", "data", "model"), ("data", "model"), ("data",), ()),
    "embed": (("data", "model"), ("data",), ()),
    "qkv": ((),),
    "heads": ((),),
    "kv_heads": ((),),
    "o_in": ((),),
    "mlp": ((),),
    # vocab REPLICATED, embed-dim sharded: `take` gathers over a sharded
    # vocab dim force SPMD to replicate the whole table (measured: +6.3 GB
    # on nemotron's 256 k-vocab); with the embed dim sharded the lookup is
    # local and the (much smaller) activation gathers/psums do the work.
    # The unembed ("lm_head") stays vocab-sharded: it only feeds einsums,
    # and sharding it keeps logits AND the unembed gradient sharded
    # (replicated dW was +12.6 GB on nemotron).
    "vocab": ((),),
    "lm_head": (("data", "model"), ("model",), ()),
    "expert": ((),),
    "kv_seq": ((),),
}


_ctx = threading.local()


def _active() -> tuple[Mesh, Rules] | None:
    return getattr(_ctx, "active", None)


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: Rules):
    """Activate (mesh, rules) so model-internal ``shard_activation`` calls
    emit with_sharding_constraint; no-op outside the context."""
    prev = _active()
    _ctx.active = (mesh, rules)
    try:
        yield
    finally:
        _ctx.active = prev


def _mesh_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


#: When two dims of one tensor compete for the same mesh axis, the higher-
#: priority logical axis wins (e.g. a KV cache prefers kv_heads on "model",
#: falling back to kv_seq only when the head count is not divisible).
_PRIORITY = (
    "batch", "vocab", "lm_head", "expert", "qkv", "mlp", "kv_heads", "heads",
    "o_in", "embed", "kv_seq", "cap", "seq",
)
_PRIO = {name: i for i, name in enumerate(_PRIORITY)}


def spec_for(
    logical: Sequence[str | None], shape: Sequence[int], mesh: Mesh, rules: Rules
) -> P:
    """Resolve logical axes -> PartitionSpec under divisibility + axis-reuse
    constraints, visiting dims in logical-axis priority order."""
    used: set[str] = set()
    entries: list[Any] = [None] * len(logical)
    order = sorted(
        range(len(logical)),
        key=lambda i: _PRIO.get(logical[i], len(_PRIORITY)) if logical[i] else 1e9,
    )
    for i in order:
        name, dim = logical[i], shape[i]
        if name is None:
            continue
        for cand in rules.get(name, ((),)):
            if not cand:
                break
            if any(a in used for a in cand):
                continue
            if any(a not in mesh.shape for a in cand):
                continue
            if dim % _mesh_size(mesh, cand) != 0:
                continue
            entries[i] = cand if len(cand) > 1 else cand[0]
            used.update(cand)
            break
    # Trim trailing Nones (canonical form).
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def sharding_for(
    logical: Sequence[str | None], shape: Sequence[int], mesh: Mesh, rules: Rules
) -> NamedSharding:
    """``spec_for`` wrapped into a concrete ``NamedSharding`` on ``mesh``."""
    return NamedSharding(mesh, spec_for(logical, shape, mesh, rules))


def tree_shardings(logical_tree: Any, abstract_tree: Any, mesh: Mesh, rules: Rules) -> Any:
    """Map a pytree of logical-axis tuples + ShapeDtypeStructs to shardings."""
    return jax.tree.map(
        lambda axes, a: sharding_for(axes, a.shape, mesh, rules),
        logical_tree,
        abstract_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def shard_activation(x: Array, logical: Sequence[str | None]) -> Array:
    """Constrain an activation's sharding if a rule context is active."""
    active = _active()
    if active is None:
        return x
    mesh, rules = active
    spec = spec_for(logical, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def abstract_with_sharding(abstract_tree: Any, logical_tree: Any, mesh: Mesh, rules: Rules) -> Any:
    """Attach shardings to ShapeDtypeStructs (dry-run input specs)."""
    return jax.tree.map(
        lambda a, axes: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding_for(axes, a.shape, mesh, rules)
        ),
        abstract_tree,
        logical_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


# ---------------------------------------------------------------------------
# Fleet-axis sharding: the B-node axis of the disaggregation engines over a
# 1-D device mesh (docs/architecture.md, "Sharded fleet").
# ---------------------------------------------------------------------------

#: Mesh-axis name of the fleet's node dimension.
FLEET_AXIS = "node"


@dataclasses.dataclass(frozen=True)
class FleetMesh:
    """A 1-D device mesh over the fleet's node (B) axis.

    Frozen and hashable so it can travel as a *static* jit argument — the
    streaming ``fleet_step`` keys its single trace on (config, mesh), which
    is what keeps the sharded stream at one compile for its whole lifetime.

    The node axis is the outermost dimension of every fleet array
    (``FleetInputs``, ``FleetStreamState`` buffers, ``FleetResult`` leaves);
    under this mesh each of the ``num_devices`` devices owns a contiguous
    ``B / num_devices`` block of nodes.  Per-node math needs no
    communication; fleet-level totals cross devices only through explicit
    ``psum`` (:func:`fleet_attribution_totals`).
    """

    mesh: Mesh
    axis: str = FLEET_AXIS

    @property
    def num_devices(self) -> int:
        """Devices along the node axis."""
        return self.mesh.shape[self.axis]

    def validate(self, num_nodes: int) -> None:
        """Reject fleets whose node count does not tile the mesh evenly."""
        if num_nodes % self.num_devices != 0:
            raise ValueError(
                f"fleet of {num_nodes} node(s) is not divisible by the "
                f"{self.num_devices}-device '{self.axis}' mesh; pad the fleet "
                f"or build the mesh with fleet_mesh(num_nodes={num_nodes})"
            )

    def node_sharding(self) -> NamedSharding:
        """Sharding that splits an array's leading axis over the nodes."""
        return NamedSharding(self.mesh, P(self.axis))

    def replicated_sharding(self) -> NamedSharding:
        """Sharding that replicates a leaf on every mesh device."""
        return NamedSharding(self.mesh, P())

    def put(self, tree: Any) -> Any:
        """Place a pytree on the mesh: leading axis sharded, scalars replicated.

        Every leaf with rank >= 1 is split over the node axis (its leading
        dimension must be divisible); rank-0 leaves (e.g. the streaming
        state's ``tick_in_step``/``step_idx`` counters) are replicated.
        Donated state placed this way stays sharded in place across
        ``fleet_step`` calls — no gather ever materializes the full fleet
        on one device.  A numpy leaf is split on the host and each block
        sent to its own device; it never passes through the default device.
        """

        def _place(leaf):
            arr = leaf if isinstance(leaf, np.ndarray) else jnp.asarray(leaf)
            if arr.ndim == 0:
                return jax.device_put(arr, self.replicated_sharding())
            self.validate(arr.shape[0])
            return jax.device_put(arr, self.node_sharding())

        return jax.tree.map(_place, tree)

    def specs_like(self, tree: Any) -> Any:
        """Per-leaf ``PartitionSpec`` pytree: node-sharded unless rank-0."""
        node, rep = P(self.axis), P()
        return jax.tree.map(lambda l: rep if jnp.ndim(l) == 0 else node, tree)


def fleet_mesh(
    num_nodes: int | None = None,
    *,
    devices: Sequence[Any] | None = None,
    axis: str = FLEET_AXIS,
) -> FleetMesh:
    """Build a :class:`FleetMesh` from the available devices.

    With ``num_nodes`` given, the mesh uses the *largest* device count that
    divides the fleet evenly (so an awkward fleet size degrades to fewer
    devices instead of failing).  Works on a single device too — the 1-device
    mesh is the identity sharding, which is what lets every ``mesh=`` code
    path run (and be tested) without multi-device hardware.
    """
    devs = list(jax.devices() if devices is None else devices)
    d = len(devs)
    if num_nodes is not None:
        while d > 1 and num_nodes % d != 0:
            d -= 1
    return FleetMesh(mesh=Mesh(np.asarray(devs[:d]), (axis,)), axis=axis)


def fleet_mesh_auto(num_nodes: int) -> FleetMesh | None:
    """``fleet_mesh`` for controllers: None unless sharding actually helps.

    Returns a mesh only when more than one device is visible *and* the
    fleet divides onto more than one of them — the control plane's
    ``profile_fleet(mesh="auto")`` uses this so single-device deployments
    keep the exact unsharded code path.
    """
    if len(jax.devices()) <= 1:
        return None
    fm = fleet_mesh(num_nodes)
    return fm if fm.num_devices > 1 else None


def reshard(tree: Any, mesh: FleetMesh | None = None) -> Any:
    """Re-place a live pytree onto a (new) mesh mid-stream — mesh elasticity.

    The checkpoint-and-resume primitive for a device set that changes under
    a running stream (devices added, removed, or re-fitted into a different
    ``FleetMesh``): every leaf is pulled to host (``jax.device_get`` — the
    checkpoint barrier; safe on donated state, which the caller rebinds
    anyway) and re-placed with ``mesh.put`` — leading axes sharded over the
    new node axis, scalars replicated.  ``mesh=None`` re-places the state
    unsharded on the default device (scaling *down* to a single device).

    Values are bit-identical across the move; only the next ``fleet_step``
    trace changes (the mesh is a static jit arg), so a resharded stream is
    pinned at 1e-5 against an uninterrupted run — one deliberate compile
    per new mesh, never a per-tick retrace (tests/test_slot_serving.py).
    """
    host = jax.device_get(tree)
    if mesh is None:
        return jax.tree.map(jnp.asarray, host)
    return mesh.put(host)


class FleetTotals(NamedTuple):
    """Fleet-wide conserved-attribution totals (one controller-level view).

    ``per_fn.sum() + unattributed == attributed + unattributed`` equals the
    fleet's total measured active power-ticks: the per-tick efficiency
    property survives the cross-node reduction by linearity.

    Combined mode (§4.3) keeps the chip and 'rest' sides split all the way
    up: ``per_fn``/``attributed`` cover the disaggregated rest power, while
    ``chip_per_fn``/``chip_total`` aggregate the counter-model X_CPU (zeros
    when profiling pure mode) — a controller can bill the two spectra
    separately or sum them for full-spectrum totals.
    """

    per_fn: Array        # (M,) attributed power summed over nodes and ticks (W)
    attributed: Array    # ()   total attributed power-ticks across the fleet
    unattributed: Array  # ()   total unattributed power-ticks across the fleet
    cp_total: Array      # ()   control-plane power summed over nodes (0 if absent)
    chip_per_fn: Array   # (M,) counter-model chip power summed over nodes (W)
    chip_total: Array    # ()   fleet chip-side total (0 in pure mode)


def fleet_attribution_totals(
    tick_power: Array,            # (B, T, M) conserved per-tick power
    unattributed: Array,          # (B, T)
    cp_power: Array | None = None,  # (B,) per-node control-plane power estimate
    *,
    chip_power: Array | None = None,  # (B, M) per-node per-function X_CPU (§4.3)
    mask: Array | None = None,    # (B, T) tick validity for ragged fleets
    mesh: FleetMesh | None = None,
) -> FleetTotals:
    """Reduce per-node attribution to fleet totals (the ``psum`` path).

    Unsharded this is a handful of ``jnp.sum`` calls.  With a
    :class:`FleetMesh` the inputs stay sharded over the node axis: each
    device reduces its local node block and a single ``psum`` along the
    axis produces the replicated fleet totals — the only collective in the
    sharded controller (per-node Kalman/disaggregation math never
    communicates).

    ``chip_power`` is combined mode's (B, M) per-function chip-side power
    (``StreamingFleetSession.x_cpu`` / the counter-model split): it rides
    the same local-reduce + psum as the rest-side partials, keeping the
    §4.3 chip/rest split intact at fleet level (``chip_per_fn`` /
    ``chip_total``; zeros when absent).

    ``mask`` is the ragged fleet's ``(B, T)`` tick-validity mask
    (``FleetInputs.mask`` flattened over steps): padded ticks are excluded
    from every total *before* the reduction.  The masked engines already
    emit exactly-zero attribution on padded ticks, so for engine outputs
    the mask changes nothing — it exists so totals computed from any
    per-tick source (replayed logs, external meters) honor the same
    contract, and, sharded, it travels split over the node axis with the
    partials it masks (no device ever sees another shard's rag pattern).
    """
    cp = jnp.zeros((tick_power.shape[0],), tick_power.dtype) if cp_power is None else cp_power
    if mask is not None:
        mask = mask.reshape(unattributed.shape).astype(tick_power.dtype)

    def _local(tp, ua, cpv, m, chip):
        # Dense fleets (mask=None) keep the original plain-sum cost: no
        # ones-mask is ever materialized or multiplied through.
        if m is not None:
            tp = tp * m[..., None]
            ua = ua * m
        return _part(tp, ua, cpv, chip)

    if mesh is None:
        return _local(tick_power, unattributed, cp, mask, chip_power)
    mesh.validate(tick_power.shape[0])
    args = [tick_power, unattributed, cp]
    if mask is not None:
        args.append(mask)
    if chip_power is not None:
        args.append(chip_power)
    return _totals_runner(mesh, mask is not None, chip_power is not None)(*args)


def _part(tp, ua, cpv, chip) -> FleetTotals:
    """Node-local (single-shard) totals; ``chip=None`` fills zeros."""
    m = tp.shape[-1]
    return FleetTotals(
        per_fn=jnp.sum(tp, axis=(0, 1)),
        attributed=jnp.sum(tp),
        unattributed=jnp.sum(ua),
        cp_total=jnp.sum(cpv),
        chip_per_fn=(
            jnp.zeros((m,), tp.dtype) if chip is None else jnp.sum(chip, axis=0)
        ),
        chip_total=jnp.zeros((), tp.dtype) if chip is None else jnp.sum(chip),
    )


@functools.lru_cache(maxsize=None)
def _totals_runner(mesh: FleetMesh, has_mask: bool, has_chip: bool):
    """Compiled psum reduction for ``fleet_attribution_totals`` (cached per
    (mesh, has_mask, has_chip) so repeated controller ticks reuse one
    executable).  The ragged variant takes the tick mask as an extra
    input, the combined variant the (B, M) chip split — each sharded
    along the node axis like every other per-node array; the plain dense
    variant keeps the original three-input plain-sum program."""
    node = P(mesh.axis)

    def _psum(part: FleetTotals) -> FleetTotals:
        return jax.tree.map(lambda v: jax.lax.psum(v, mesh.axis), part)

    def _local_psum(tp, ua, cpv, *rest):
        it = iter(rest)
        m = next(it) if has_mask else None
        chip = next(it) if has_chip else None
        if m is not None:
            tp = tp * m[..., None]
            ua = ua * m
        return _psum(_part(tp, ua, cpv, chip))

    in_specs = (node, node, node) + (node,) * (int(has_mask) + int(has_chip))

    return jax.jit(
        jax.shard_map(
            _local_psum,
            mesh=mesh.mesh,
            in_specs=in_specs,
            out_specs=P(),
            check_vma=False,
        )
    )
