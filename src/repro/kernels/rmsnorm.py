"""Pallas TPU fused RMSNorm: one pass, fp32 accumulation, row-blocked.

Unfused, RMSNorm reads x twice (square-reduce, then scale) and round-trips
an fp32 intermediate through HBM.  The kernel stages a (rows x d) tile in
VMEM, computes the row rsqrt statistics and writes the scaled tile once —
bandwidth 2x better, which matters on the decode path where every block is
memory-bound.  Validated against ``ref.rmsnorm`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rms_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * g_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("eps", "row_block", "interpret"))
def rmsnorm(
    x: jax.Array,          # (..., d)
    gamma: jax.Array,      # (d,)
    *,
    eps: float = 1e-5,
    row_block: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Row-blocked Pallas RMSNorm over the last axis (matches ``ref.rmsnorm_ref``)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    xf = x.reshape(rows, d)
    rb = min(row_block, max(rows, 8))
    rem = (-rows) % rb
    if rem:
        xf = jnp.pad(xf, [(0, rem), (0, 0)])
    nr = xf.shape[0] // rb

    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(xf, gamma)
    return out[:rows].reshape(orig_shape)
