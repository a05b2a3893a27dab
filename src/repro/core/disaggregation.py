"""Statistical power disaggregation (paper §4.1, Eq. 1).

Estimate per-function *power* X (watts) from window-level contribution
matrices and power measurements:

    X_full    = argmin_X || C X - W ||            (Eq. 1)
    X_no_idle = argmin_X || C X - (W - W_idle) ||
    X_rest    = argmin_X || C X - (W_sys - W_cpu) ||   (combined mode, §4.3)

Per-invocation energy follows as J = X * tau (tau = mean function latency).

Two solvers are provided:

- ``solve_ridge``: Tikhonov-regularized normal equations, closed form.  The
  regularizer handles the rank deficiency the paper notes (columns of C for
  inactive functions are identically zero; at small delta the active set is
  sparse).  Zero columns provably yield X_j = 0 (the null-player property is
  obtained *by construction of C*, §4.4).
- ``solve_nnls``: projected-gradient (FISTA) non-negative least squares.
  Power draws are physically non-negative; NNLS keeps footprints
  interpretable when measurement noise would otherwise drive small functions
  negative.

Both are pure-jnp, jit/vmap-friendly (the fleet profiler vmaps them over
nodes and windows); the TPU hot path is the Pallas batched normal-equation
kernel in ``repro.kernels.disagg_solve`` which fuses C^T C / C^T W assembly
with the Cholesky solve for (nodes x windows) batches.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

Array = jax.Array

#: Precision of every f32 contraction in the energy math (grams, right-hand
#: sides, innovations, NNLS matvecs, counter features and models, the
#: reconstruction).  A TPU's default takes one bf16 pass, which the
#: whole-trace X_0 solve amplifies far past float32 noise; HIGHEST keeps the
#: chip within the engine's CPU and oracle pins.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DisaggregationConfig:
    """Configuration for one disaggregation solve."""

    mode: str = "no_idle"  # full | no_idle | rest
    ridge_lambda: float = 1e-3
    nonneg: bool = True
    nnls_iters: int = 200


@functools.partial(jax.jit, static_argnames=("nonneg",))
def solve_ridge(c: Array, w: Array, lam: float = 1e-3, *, nonneg: bool = True) -> Array:
    """Closed-form ridge solution of min_X ||C X - W||^2 + lam ||X||^2.

    Args:
      c: (N, M) contribution matrix (seconds per window per function).
      w: (N,) power measurements per window (watts).
      lam: Tikhonov regularizer; also what sends zero-column functions to 0.
      nonneg: clip the solution at zero (power is physical).

    Returns:
      (M,) per-function power estimate in watts.
    """
    m = c.shape[1]
    gram = jnp.matmul(c.T, c, precision=MATMUL_PRECISION) + lam * jnp.eye(m, dtype=c.dtype)
    rhs = jnp.matmul(c.T, w, precision=MATMUL_PRECISION)
    # Normal equations via Cholesky: gram is SPD by construction.
    chol = jnp.linalg.cholesky(gram)
    x = jax.scipy.linalg.cho_solve((chol, True), rhs)
    return jnp.maximum(x, 0.0) if nonneg else x


@functools.partial(jax.jit, static_argnames=("iters",))
def solve_nnls_gram(gram: Array, rhs: Array, *, iters: int = 200) -> Array:
    """Gram-domain FISTA NNLS: min_{X >= 0} 0.5 X^T G X - r^T X.

    ``gram`` must already include the ridge term (G = C^T C + lam I).  This
    is the batched engine's per-tick solve: once G/r are assembled (Pallas
    kernel on TPU, one einsum pass elsewhere) every iteration is O(M^2) with
    no window-dimension work, so a ``lax.scan`` over Kalman steps carries
    only (M, M) state.  Broadcasts over any leading batch dims.
    """
    lip = jnp.trace(gram, axis1=-2, axis2=-1)  # >= spectral norm for SPD
    step = (1.0 / jnp.maximum(lip, 1e-12))[..., None]

    def body(i, carry):
        x, y, t = carry
        grad = jnp.einsum("...ij,...j->...i", gram, y, precision=MATMUL_PRECISION) - rhs
        x_new = jnp.maximum(y - step * grad, 0.0)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_new = x_new + ((t - 1.0) / t_new) * (x_new - x)
        return x_new, y_new, t_new

    x0 = jnp.zeros_like(rhs)
    x, _, _ = jax.lax.fori_loop(0, iters, body, (x0, x0, jnp.asarray(1.0, rhs.dtype)))
    return x


@functools.partial(jax.jit, static_argnames=("iters",))
def solve_nnls(c: Array, w: Array, lam: float = 1e-3, *, iters: int = 200) -> Array:
    """FISTA-accelerated projected gradient NNLS.

    min_{X >= 0} 0.5||C X - W||^2 + 0.5 lam ||X||^2, with Lipschitz step
    1/L, L = ||C^T C||_2 + lam bounded by its trace (cheap, safe).
    """
    gram = jnp.matmul(c.T, c, precision=MATMUL_PRECISION) + lam * jnp.eye(
        c.shape[1], dtype=c.dtype
    )
    rhs = jnp.matmul(c.T, w, precision=MATMUL_PRECISION)
    return solve_nnls_gram(gram, rhs, iters=iters)


def disaggregate(
    c: Array,
    w: Array,
    config: DisaggregationConfig = DisaggregationConfig(),
    *,
    w_idle: float | Array = 0.0,
    w_cpu: Array | None = None,
) -> Array:
    """Dispatch on disaggregation mode (paper §4.1 / §4.3).

    - ``full``: solve against raw system power W.
    - ``no_idle``: solve against W - W_idle (gives X_No_Idle / J_indiv).
    - ``rest``: solve against W_sys - W_cpu (the combined mode's residual,
      to be added to the CPU-model estimate X_CPU).
    """
    if config.mode == "full":
        target = w
    elif config.mode == "no_idle":
        target = w - w_idle
    elif config.mode == "rest":
        if w_cpu is None:
            raise ValueError("mode='rest' requires w_cpu")
        target = w - w_cpu
    else:
        raise ValueError(f"unknown disaggregation mode: {config.mode!r}")
    target = jnp.maximum(target, 0.0)
    if config.nonneg:
        return solve_nnls(c, target, config.ridge_lambda, iters=config.nnls_iters)
    return solve_ridge(c, target, config.ridge_lambda, nonneg=False)


@jax.jit
def per_invocation_energy(x_power: Array, latency: Array) -> Array:
    """J = X * tau (paper §4.1): per-invocation energy in joules.

    Args:
      x_power: (M,) per-function power (watts) while running.
      latency: (M,) mean per-invocation latency (seconds).
    """
    return x_power * latency


# ---------------------------------------------------------------------------
# Fleet-batched entry points (the scale-up beyond the paper's single server).
# ---------------------------------------------------------------------------

#: vmapped over a leading node axis: (B, N, M), (B, N) -> (B, M)
solve_ridge_batched = jax.jit(
    jax.vmap(lambda c, w: solve_ridge(c, w, 1e-3, nonneg=True)), static_argnames=()
)

solve_nnls_batched = jax.jit(jax.vmap(lambda c, w: solve_nnls(c, w, 1e-3, iters=200)))
