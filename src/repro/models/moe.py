"""Mixture-of-experts channel mixer (deepseek-moe fine-grained, olmoe).

Three dispatch implementations, selected by ``cfg.router_impl`` and the
active mesh:

- ``ep`` (production default on meshes with a "model" axis): explicit
  expert parallelism under ``shard_map`` — tokens stay sharded over
  ("pod","data"), experts are sharded over "model"; each device routes its
  *local* tokens into per-expert capacity buffers (a local scatter), one
  ``all_to_all`` over the model axis moves buffers to the expert owners,
  the expert FFNs run as local einsums, and a reverse ``all_to_all`` brings
  results home.  This is the GShard/MaxText EP schedule stated explicitly —
  GSPMD cannot infer it from the scatter formulation (it replicates the
  dispatch instead; we measured 211 GiB/device and 445 GB of collectives on
  deepseek-moe train_4k before this path existed — see EXPERIMENTS §Perf).
- ``capacity``: single-shard scatter dispatch into (E, C, d) buffers with
  dense einsums; exact same math as ``ep`` on one device (tests use this).
- ``ragged``: dropless sort-based dispatch through ``jax.lax.ragged_dot`` —
  FLOPs-exact oracle for drop-free comparison.

Auxiliary load-balancing loss (Switch-style) is returned alongside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.distributed.sharding import shard_activation
from repro.models.common import Param
from repro.models.mlp import mlp_apply, mlp_params

Array = jax.Array


def moe_params(cfg: ArchConfig) -> dict:
    """Parameter spec tree for the mixture-of-experts block."""
    d, e, f = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    p = {
        "router": Param((d, e), ("embed", "expert"), scale=0.1),
        "w_gate": Param((e, d, f), ("expert", "embed", "expert_mlp")),
        "w_up": Param((e, d, f), ("expert", "embed", "expert_mlp")),
        "w_down": Param((e, f, d), ("expert", "expert_mlp", "embed")),
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = mlp_params(cfg, d_ff=cfg.num_shared_experts * f)
    return p


def _router(p: dict, x: Array, cfg: ArchConfig):
    """Top-k routing.  Returns (idx (T,k), weight (T,k), aux_loss)."""
    t = x.shape[0]
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weight, idx = jax.lax.top_k(probs, cfg.top_k)
    weight = weight / jnp.maximum(weight.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e (fraction of tokens to e) * (mean prob of e).
    # bincount instead of a (T*k, E) one-hot — O(T) memory at 1M-token scale.
    counts = jnp.bincount(idx.reshape(-1), length=cfg.num_experts)
    frac = counts.astype(jnp.float32) / jnp.maximum(t * cfg.top_k, 1)
    aux = cfg.num_experts * jnp.sum(frac * probs.mean(0))
    return idx, weight.astype(x.dtype), aux


def _expert_positions(flat_idx: Array, e: int):
    """Rank of each dispatch entry within its expert, via one sort.

    Returns pos (T*k,) int32.  Ties broken by dispatch order (stable sort),
    matching GShard's in-order capacity assignment.
    """
    n = flat_idx.shape[0]
    order = jnp.argsort(flat_idx, stable=True)
    sorted_idx = flat_idx[order]
    starts = jnp.cumsum(jnp.bincount(flat_idx, length=e)) - jnp.bincount(flat_idx, length=e)
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_idx].astype(jnp.int32)
    inv = jnp.argsort(order)
    return pos_sorted[inv]


def _moe_capacity(p: dict, x: Array, cfg: ArchConfig):
    """Capacity-buffer dispatch.  x: (T, d) -> (T, d), aux_loss."""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    cap = int(cfg.capacity_factor * t * k / e)
    cap = max(((cap + 3) // 4) * 4, 4)

    idx, weight, aux = _router(p, x, cfg)
    flat_idx = idx.reshape(t * k)
    pos = _expert_positions(flat_idx, e)
    keep = pos < cap

    # Scatter tokens into (E, C, d) buffers; dropped tokens scatter nowhere.
    src = jnp.repeat(x, k, axis=0)  # (T*k, d)
    safe_e = jnp.where(keep, flat_idx, 0)
    safe_c = jnp.where(keep, pos, cap)  # out-of-range row "cap" is clipped off
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[safe_e, safe_c].add(jnp.where(keep[:, None], src, 0))
    buf = buf[:, :cap]
    buf = shard_activation(buf, ("expert", "cap", None))

    # Expert FFNs: dense einsums over (E, C, *).
    dt = x.dtype
    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    h = jax.nn.silu(gate) * up
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))
    out_buf = jnp.concatenate([out_buf, jnp.zeros((e, 1, d), dt)], axis=1)

    # Gather back and combine with router weights.
    gathered = out_buf[safe_e, jnp.where(keep, pos, cap)]  # (T*k, d)
    combined = (gathered.reshape(t, k, d) * weight[..., None]).sum(axis=1)
    return combined, aux


def _moe_ragged(p: dict, x: Array, cfg: ArchConfig):
    """Dropless sort-based dispatch via ragged_dot.  x: (T, d)."""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    idx, weight, aux = _router(p, x, cfg)
    flat_idx = idx.reshape(t * k)
    order = jnp.argsort(flat_idx)
    inv = jnp.argsort(order)
    xs = jnp.repeat(x, k, axis=0)[order]
    group_sizes = jnp.bincount(flat_idx, length=e).astype(jnp.int32)
    dt = x.dtype
    gate = jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), group_sizes)
    up = jax.lax.ragged_dot(xs, p["w_up"].astype(dt), group_sizes)
    h = jax.nn.silu(gate) * up
    out = jax.lax.ragged_dot(h, p["w_down"].astype(dt), group_sizes)
    out = out[inv].reshape(t, k, d)
    combined = (out * weight[..., None]).sum(axis=1)
    return combined, aux


# ---------------------------------------------------------------------------
# Explicit expert parallelism (shard_map)
# ---------------------------------------------------------------------------


def _local_dispatch(p_router, x_flat: Array, cfg: ArchConfig, cap: int):
    """Route local tokens into (E, cap, d) buffers.  Returns
    (buf, safe_e, pos, keep, weight, aux)."""
    t, d = x_flat.shape
    e, k = cfg.num_experts, cfg.top_k
    idx, weight, aux = _router({"router": p_router}, x_flat, cfg)
    flat_idx = idx.reshape(t * k)
    pos = _expert_positions(flat_idx, e)
    keep = pos < cap
    src = jnp.repeat(x_flat, k, axis=0)
    safe_e = jnp.where(keep, flat_idx, 0)
    safe_c = jnp.where(keep, pos, cap)
    buf = jnp.zeros((e, cap + 1, d), x_flat.dtype)
    buf = buf.at[safe_e, safe_c].add(jnp.where(keep[:, None], src, 0))
    return buf[:, :cap], safe_e, pos, keep, weight, aux


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _int8_all_to_all(x: Array, axis_name: str, split_axis: int, concat_axis: int):
    """all_to_all with int8 payload in BOTH directions (fwd + cotangent).

    Rows (last dim) are symmetrically quantized; the f32 row scales travel
    alongside (<1 % of payload).  Production MoE dispatch commonly ships
    fp8/int8 activations across ICI — this halves the dominant collective
    of every MoE train/prefill cell (EXPERIMENTS §Perf H-B2).
    """
    out, _ = _int8_a2a_fwd(x, axis_name, split_axis, concat_axis)
    return out


def _q_a2a(x, axis_name, split_axis, concat_axis):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q = jax.lax.all_to_all(q, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True)
    s = jax.lax.all_to_all(
        scale.astype(jnp.float32), axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )
    return (q.astype(jnp.float32) * s).astype(x.dtype)


def _int8_a2a_fwd(x, axis_name, split_axis, concat_axis):
    return _q_a2a(x, axis_name, split_axis, concat_axis), None


def _int8_a2a_bwd(axis_name, split_axis, concat_axis, _, g):
    # all_to_all transpose swaps split/concat axes; quantize the cotangent.
    return (_q_a2a(g, axis_name, concat_axis, split_axis),)


_int8_all_to_all.defvjp(_int8_a2a_fwd, _int8_a2a_bwd)


def _a2a(x, axis_name, split_axis, concat_axis, dtype: str):
    if dtype == "int8":
        return _int8_all_to_all(x, axis_name, split_axis, concat_axis)
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def _moe_ep_body(router_w, w_gate, w_up, w_down, x_loc, cfg: ArchConfig,
                 model_axis: str, model_size: int, token_axes: tuple):
    """Per-device EP body (inside shard_map).

    x_loc: (b_loc, S, d) local tokens; w_*: (E_loc, ...) local expert shards.
    """
    b, s, d = x_loc.shape
    e, k = cfg.num_experts, cfg.top_k
    e_loc = e // model_size
    t = b * s
    cap = int(cfg.capacity_factor * t * k / e)
    cap = max(((cap + 3) // 4) * 4, 4)

    flat = x_loc.reshape(t, d)
    buf, safe_e, pos, keep, weight, aux = _local_dispatch(router_w, flat, cfg, cap)

    # Tiled all-to-all over the model axis: (E, C, d) -> (E_loc, ms*C, d);
    # each device keeps its expert group, sources concatenated along C.
    dt = x_loc.dtype
    a2a_dtype = cfg.moe_a2a_dtype
    if model_size > 1:
        buf = _a2a(buf, model_axis, 0, 1, a2a_dtype)
    # Expert FFNs on local experts.
    gate = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dt))
    up = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dt))
    h = jax.nn.silu(gate) * up
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))
    # Reverse tiled all-to-all home: (E_loc, ms*C, d) -> (E, C, d).
    if model_size > 1:
        out_buf = _a2a(out_buf, model_axis, 1, 0, a2a_dtype)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((e, 1, d), dt)], axis=1)
    gathered = out_buf[safe_e, jnp.where(keep, pos, cap)]
    combined = (gathered.reshape(t, k, d) * weight[..., None]).sum(axis=1)
    # Aux loss: average over all token shards (identical on every device).
    aux = jax.lax.pmean(aux, token_axes + (model_axis,))
    return combined.reshape(b, s, d), aux


def _moe_ep(p: dict, x: Array, cfg: ArchConfig, mesh) -> tuple[Array, Array]:
    """shard_map EP dispatch on the active mesh."""
    token_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    model_axis = "model"
    model_size = mesh.shape[model_axis]
    x_spec = P(token_axes if len(token_axes) > 1 else (token_axes[0] if token_axes else None))
    expert_spec = P("model")

    def body(router_w, w_gate, w_up, w_down, xl):
        return _moe_ep_body(
            router_w, w_gate, w_up, w_down, xl, cfg, model_axis, model_size, token_axes
        )

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), expert_spec, expert_spec, expert_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)


def moe_apply(p: dict, x: Array, cfg: ArchConfig):
    """(B, S, d) -> (B, S, d), aux_loss.  Shared experts (deepseek) run
    densely on every token and add to the routed output.

    Dispatch selection: explicit shard_map EP whenever a sharding-rule
    context with a "model" axis is active (production meshes); otherwise the
    single-shard scatter/ragged implementations.
    """
    b, s, d = x.shape
    active = shd._active()
    if cfg.router_impl != "ragged" and active is not None and "model" in active[0].shape:
        out, aux = _moe_ep(p, x, cfg, active[0])
    else:
        flat = x.reshape(b * s, d)
        if cfg.router_impl == "ragged":
            routed, aux = _moe_ragged(p, flat, cfg)
        else:
            routed, aux = _moe_capacity(p, flat, cfg)
        out = routed.reshape(b, s, d)
    if "shared" in p:
        out = out + mlp_apply(p["shared"], x, cfg)
    return out, aux
