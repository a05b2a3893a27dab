"""Pallas TPU flash attention (forward): blocked online-softmax GQA.

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv dimension
"arbitrary" (sequential) — the standard TPU flash schedule: VMEM scratch
carries (acc, m, l) across kv blocks, initialized at the first kv block and
finalized (acc / l) at the last.  Causal block-skipping: fully-masked
(q_block, kv_block) pairs skip their compute via ``pl.when``.

BlockSpecs stage one (q_block x head_dim) query tile and one
(kv_block x head_dim) K/V tile in VMEM per program — working set
``q_block*d + 2*kv_block*d + q_block*kv_block`` fp32 words; the default
(512, 1024) tiles with d=128 stay under ~3.5 MB, comfortably inside the
~16 MB v5e VMEM alongside double-buffering.  MXU alignment: tiles are
multiples of (128, 128); the wrapper pads S/T up and slices the output.

Training uses the recomputing custom-VJP in ``ref.py`` (same blocked
semantics); this kernel is the serving/prefill forward hot path.  Validated
against ``ref.flash_attention`` in interpret mode over shape/dtype sweeps
(tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref,       # VMEM tiles
    o_ref,                     # output tile
    acc_ref, m_ref, l_ref,     # VMEM scratch carried over kv blocks
    *, causal: bool, scale: float, q_block: int, kv_block: int,
    nk: int, offset: int, kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal block skip: last q position < first k position => fully masked.
    q_last = qi * q_block + q_block - 1 + offset
    k_first = ki * kv_block
    live = (q_last >= k_first) if causal else (k_first < kv_len)

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)          # (qb, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (kvb, d)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                           # (qb, kvb)
        k_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            q_pos = qi * q_block + offset + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        # Mask padded keys (kv padded up to a block multiple).
        s = jnp.where(k_pos < kv_len, s, NEG_INF)

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
        o_ref[0, :, 0, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _pad_to(x, axis: int, mult: int):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit, static_argnames=("causal", "q_block", "kv_block", "interpret")
)
def flash_attention(
    q: jax.Array,               # (B, S, H, d)
    k: jax.Array,               # (B, T, Hkv, d)
    v: jax.Array,
    *,
    causal: bool = True,
    q_block: int = 512,
    kv_block: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Blocked online-softmax attention (Pallas); matches ``ref.attention_ref``."""
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    q_block = min(q_block, max(s, 8))
    kv_block = min(kv_block, max(t, 8))
    offset = t - s

    qp = _pad_to(q, 1, q_block)
    kp = _pad_to(k, 1, kv_block)
    vp = _pad_to(v, 1, kv_block)
    sp, tp = qp.shape[1], kp.shape[1]
    nq, nk = sp // q_block, tp // kv_block
    scale = float(1.0 / (d ** 0.5))

    kernel = functools.partial(
        _fa_kernel,
        causal=causal, scale=scale, q_block=q_block, kv_block=kv_block,
        nk=nk, offset=offset, kv_len=t,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, 1, d), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
            pl.BlockSpec((1, kv_block, 1, d), lambda b_, h_, qi, ki: (b_, ki, h_ // group, 0)),
            pl.BlockSpec((1, kv_block, 1, d), lambda b_, h_, qi, ki: (b_, ki, h_ // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, 1, d), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sp, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, d), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :s]
