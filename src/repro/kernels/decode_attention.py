"""Pallas TPU decode attention: one query token vs a long KV cache.

The 32k/500k decode cells are memory-bound: the step reads the whole KV
cache once at ~O(1) compute per byte.  The kernel streams KV blocks through
VMEM with the online-softmax carried in scratch — grid (batch, kv_head,
kv_blocks), the group's G query heads processed together so each staged KV
block is reused G times (GQA's arithmetic-intensity advantage made
explicit).  ``lengths`` masks the unfilled cache tail.

Tiles: (kv_block x d) K and V in VMEM (+ the (G x d) query tile); default
kv_block=2048, d=128 => 2 MB staged per step, double-buffered by the
pipeline.  Validated against ``ref.decode_attention`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _dec_kernel(
    len_ref,                    # scalar-prefetch: (B,) lengths
    q_ref, k_ref, v_ref,        # (1, G, d), (1, kvb, 1, d) x2
    o_ref,                      # (1, G, d)
    acc_ref, m_ref, l_ref,      # scratch: (G, d), (G,), (G,)
    *, kv_block: int, nk: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    live = ki * kv_block < length

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (G, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)           # (kvb, d)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        d = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (1.0 / (d ** 0.5))                               # (G, kvb)
        k_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_block", "interpret"))
def decode_attention(
    q: jax.Array,         # (B, H, d)
    k_cache: jax.Array,   # (B, S, Hkv, d)
    v_cache: jax.Array,
    lengths: jax.Array,   # (B,) int32 valid KV length
    *,
    kv_block: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Single-query decode attention over a KV cache (Pallas, KV-blocked)."""
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    kv_block = min(kv_block, max(s, 8))
    rem = (-s) % kv_block
    if rem:
        pad = [(0, 0)] * 4
        pad[1] = (0, rem)
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    sp = k_cache.shape[1]
    nk = sp // kv_block
    # (B, H, d) -> (B, Hkv, G, d) so one program handles one kv head's group.
    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(_dec_kernel, kv_block=kv_block, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, ki, lens: (b_, h_, 0, 0)),
            pl.BlockSpec((1, kv_block, 1, d), lambda b_, h_, ki, lens: (b_, ki, h_, 0)),
            pl.BlockSpec((1, kv_block, 1, d), lambda b_, h_, ki, lens: (b_, ki, h_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, ki, lens: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, d)
