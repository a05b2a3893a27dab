"""Pallas TPU kernel for the paper's core computation at fleet scale:
batched normal-equation assembly for the disaggregation solve (Eq. 1).

The paper solves ``min_X ||C X - W||`` per server with scipy on the host.
A fleet controller solves it for (nodes x Kalman-windows) batches each
step.  TPU-native rethink: assemble ``G = C^T C`` (M x M) and ``r = C^T W``
(M) for the whole batch in one MXU-tiled pass.  ``W`` rides as one more
column of ``C``, so a single gram ``[C | W]^T [C | W]`` holds both: ``G`` is
its leading M x M block and ``r`` the first M entries of its last column.
The window dimension N (thousands) is the contraction dim, streamed through
VMEM in ``n_block`` tiles and accumulated in the resident f32 output block;
M + 1 is padded to the 128-lane MXU width.  The small SPD solves then run
as a batched Cholesky or FISTA on the assembled grams (they are O(M^3)
with tiny constants — the bandwidth-heavy part is this assembly, which is
what the kernel owns).

Grid: (batch, n_blocks); n_blocks is the sequential axis carrying the
accumulator.  Validated against ``ref.disagg_gram`` in interpret mode and
compiled for v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.disaggregation import MATMUL_PRECISION, solve_nnls_gram


def _gram_kernel(x_ref, g_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    x = x_ref[0].astype(jnp.float32)                        # (nb, K)
    g_ref[0] += jax.lax.dot_general(
        x, x, (((0,), (0,)), ((), ())),
        precision=MATMUL_PRECISION, preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("n_block", "interpret"))
def disagg_gram(
    c: jax.Array,     # (G, N, M) contribution windows (zero rows are inert)
    w: jax.Array,     # (G, N) power targets
    *,
    n_block: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (gram (G, M, M), rhs (G, M)) in fp32."""
    squeeze = False
    if c.ndim == 2:
        c, w, squeeze = c[None], w[None], True
    g_b, n, m = c.shape
    # A block of N must be a multiple of 8 rows or the whole (padded) axis.
    n_block = min(n_block, max(n, 8))
    # Pad M + 1 to the 128-lane MXU width and N to the block size; zero
    # padding contributes nothing to the gram.
    k = ((m + 1 + 127) // 128) * 128
    x = jnp.concatenate([c, w[..., None].astype(c.dtype)], axis=-1)
    x = jnp.pad(x, [(0, 0), (0, (-n) % n_block), (0, k - m - 1)])
    nn = x.shape[1] // n_block

    gram = pl.pallas_call(
        _gram_kernel,
        grid=(g_b, nn),
        in_specs=[pl.BlockSpec((1, n_block, k), lambda b, ni: (b, ni, 0))],
        out_specs=pl.BlockSpec((1, k, k), lambda b, ni: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g_b, k, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x)
    rhs = gram[:, :m, m]
    gram = gram[:, :m, :m]
    if squeeze:
        return gram[0], rhs[0]
    return gram, rhs


def default_backend() -> str:
    """Gram-assembly backend for the batched engine: the Pallas kernel owns
    the contraction on TPU; elsewhere a plain XLA einsum is both faster and
    exact (interpret-mode Pallas runs at Python speed)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def disagg_solve_nnls(
    c: jax.Array, w: jax.Array, lam: float = 1e-3,
    *, iters: int = 200, interpret: bool = False,
) -> jax.Array:
    """Kernel-assembled NNLS: Pallas gram pass + batched gram-domain FISTA.

    The fleet engine's per-tick solve: (G, N, M) contribution batches in,
    (G, M) non-negative power estimates out, with the window dimension
    touched exactly once (inside the kernel).
    """
    gram, rhs = disagg_gram(c, w, interpret=interpret)
    m = gram.shape[-1]
    gram = gram + lam * jnp.eye(m, dtype=gram.dtype)
    return solve_nnls_gram(gram, rhs, iters=iters)


@functools.partial(jax.jit, static_argnames=("interpret", "nonneg"))
def disagg_solve(
    c: jax.Array, w: jax.Array, lam: float = 1e-3,
    *, nonneg: bool = True, interpret: bool = False,
) -> jax.Array:
    """Kernel-assembled ridge solve: Cholesky on the (G, M, M) grams."""
    gram, rhs = disagg_gram(c, w, interpret=interpret)
    m = gram.shape[-1]
    gram = gram + lam * jnp.eye(m, dtype=gram.dtype)
    chol = jnp.linalg.cholesky(gram)
    x = jax.scipy.linalg.cho_solve((chol, True), rhs[..., None])[..., 0]
    return jnp.maximum(x, 0.0) if nonneg else x
