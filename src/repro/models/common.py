"""Model substrate: parameter definitions with logical sharding axes, norms,
rotary embeddings, and the LM loss.

Parameters are declared as ``Param`` leaves (shape + logical axes + init
law); ``materialize(tree, rng)`` instantiates them, and
``repro.distributed.sharding`` maps the logical axes onto a mesh.

Logical axis vocabulary (mapped to mesh axes by the sharding rules):

  "batch" "seq" "embed" "qkv" "o_in" "mlp" "vocab" "expert" "heads" "kv"
  "layers" "state" "conv" (None entries are never sharded)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Param:
    """Declarative parameter leaf."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"        # normal | zeros | ones | embed
    scale: float = 1.0          # multiplier on the init law's std
    fan_in: int | None = None   # override fan-in for 'normal'

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_param(x: Any) -> bool:
    """True for ``Param`` spec leaves (tree-traversal predicate)."""
    return isinstance(x, Param)


def _leaf_init(p: Param, key: jax.Array, dtype) -> Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "embed":
        return jax.random.normal(key, p.shape, dtype) * p.scale
    # truncated-normal fan-in scaling (maxtext-style default)
    fan_in = p.fan_in or (p.shape[-2] if len(p.shape) >= 2 else p.shape[-1])
    std = p.scale / math.sqrt(max(fan_in, 1))
    return jax.random.truncated_normal(key, -2.0, 2.0, p.shape, dtype) * std


def materialize(tree: Any, rng: jax.Array, dtype=jnp.float32) -> Any:
    """Instantiate every Param leaf with its init law."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_param)
    keys = jax.random.split(rng, len(leaves))
    vals = [_leaf_init(p, k, dtype) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def param_count(tree: Any) -> int:
    """Total element count over a ``Param`` spec tree."""
    return sum(
        math.prod(p.shape) for p in jax.tree.leaves(tree, is_leaf=is_param)
    )


def stack_params(tree: Any, n: int) -> Any:
    """Stack a per-layer Param tree ``n`` times along a leading "layers" axis.

    This is what makes scan-over-layers work: one declaration per block, one
    stacked tree per stack, one ``lax.scan`` over the leading axis — the HLO
    stays O(1) in depth, which keeps 40-80-layer dry-run compiles tractable.
    Fan-in for 'normal' init is pinned to the *unstacked* value so the init
    law is identical to materializing n independent layers.
    """

    def _stack(p: Param) -> Param:
        fan_in = p.fan_in
        if fan_in is None and p.init == "normal":
            fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
        return Param(
            shape=(n, *p.shape),
            axes=("layers", *p.axes),
            init=p.init,
            scale=p.scale,
            fan_in=fan_in,
        )

    return jax.tree.map(_stack, tree, is_leaf=is_param)


def maybe_remat(fn: Callable, policy: str) -> Callable:
    """Wrap a block fn with the config's activation-checkpoint policy."""
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        pol = getattr(jax.checkpoint_policies, "dots_with_no_batch_dims_saveable", None)
        if pol is None:  # older jax spelling
            pol = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=pol)
    raise ValueError(f"unknown remat policy {policy!r}")


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


def rms_norm(x: Array, gamma: Array, eps: float = 1e-5) -> Array:
    """RMSNorm in fp32 accumulation, cast back to input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float) -> Array:
    """(head_dim/2,) inverse frequencies."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary position embedding.

    Args:
      x: (..., S, H, head_dim)
      positions: (..., S) integer positions (broadcastable to x[..., :, 0, 0]).
    """
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]  # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(logits: Array, cap: float) -> Array:
    """Gemma-style tanh logit soft-capping; identity when ``cap <= 0``."""
    if cap <= 0.0:
        return logits
    return jnp.tanh(logits / cap) * cap


def _ce_sums(logits: Array, labels: Array, vocab_size: int, z_loss: float):
    """Masked CE partial sums: (nll_sum, z_sum, valid_count), fp32."""
    logits = logits.astype(jnp.float32)
    v_pad = logits.shape[-1]
    if v_pad > vocab_size:
        mask = jnp.arange(v_pad) < vocab_size
        logits = jnp.where(mask, logits, -1e30)
    valid = labels >= 0
    labels_c = jnp.clip(labels, 0, vocab_size - 1)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, logz - gold, 0.0)
    zl = jnp.where(valid, z_loss * jnp.square(logz), 0.0)
    return jnp.sum(nll), jnp.sum(zl), jnp.sum(valid)


def cross_entropy_loss(
    logits: Array,          # (B, S, V_padded) in compute dtype
    labels: Array,          # (B, S) int32; < 0 entries are ignored
    vocab_size: int,        # true vocab; padded tail is masked out
    z_loss: float = 1e-4,
) -> tuple[Array, dict[str, Array]]:
    """Masked softmax cross-entropy with z-loss, fp32 accumulation.

    Padded-vocab logits are masked to -inf so the pad entries get zero
    probability mass regardless of initialization.
    """
    nll_sum, z_sum, n_valid = _ce_sums(logits, labels, vocab_size, z_loss)
    denom = jnp.maximum(n_valid, 1)
    loss = (nll_sum + z_sum) / denom
    metrics = {
        "loss": loss,
        "nll": nll_sum / denom,
        "tokens": denom.astype(jnp.float32),
    }
    return loss, metrics


def chunked_lm_loss(
    h: Array,               # (B, S, d) final hidden states (pre-norm applied)
    unembed: Array,         # (d, V_padded)
    labels: Array,          # (B, S)
    vocab_size: int,
    chunk: int,
    z_loss: float = 1e-4,
    logit_softcap: float = 0.0,
) -> tuple[Array, dict[str, Array]]:
    """Sequence-chunked CE: logits are materialized one (B, chunk, V) slice
    at a time inside a scan, never as the full (B, S, V) fp32 tensor —
    memory /(S/chunk) for large-vocab models (§Perf: nemotron's 256 k vocab
    at fp32 logits is 4.2 GB/device under ZeRO-3; chunked it is ~0.5 GB).
    """
    b, s, d = h.shape
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, [(0, 0), (0, pad), (0, 0)])
        labels = jnp.pad(labels, [(0, 0), (0, pad)], constant_values=-1)
    n = h.shape[1] // chunk
    h_c = h.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lab_c = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        hc, lc = inp
        logits = jnp.einsum("bsd,dv->bsv", hc, unembed.astype(hc.dtype))
        logits = softcap(logits, logit_softcap)
        nll, zl, cnt = _ce_sums(logits, lc, vocab_size, z_loss)
        a, b_, c = carry
        return (a + nll, b_ + zl, c + cnt), None

    (nll_sum, z_sum, n_valid), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros((), jnp.int32)), (h_c, lab_c)
    )
    denom = jnp.maximum(n_valid, 1)
    loss = (nll_sum + z_sum) / denom
    return loss, {"loss": loss, "nll": nll_sum / denom, "tokens": denom.astype(jnp.float32)}


def dense(x: Array, w: Array, b: Array | None = None) -> Array:
    """x @ w in the compute dtype of x, optional bias."""
    out = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        out = out + b.astype(x.dtype)
    return out
