"""Online estimation with Kalman filtering (paper §4.2, Fig. 4).

FaasMeter continuously updates the per-function power estimates X based on
new measurements.  Per Kalman step i (time-step N_K ~ 1-2 min, containing a
batch of delta-sized windows):

    U_i = argmin_X || C_i X - W_i ||          (fresh disaggregation)
    Z_i = W_i - C_i X_hat_{i-1}               (innovation)
    P   = alpha * P_{i-1} + gamma * sigma(T)  (process noise)
    K   = P A_i^T / (A_i P A_i^T + r)         (gain; r ~ 1/delta)
    P_i = (1 - K A_i) P
    X_i = alpha X_hat_{i-1} + beta U_i + K Z_i

Design intents carried over from the paper:

- functions *not executed* in the step see no change in their footprint
  (masked update);
- functions with higher historical latency variance sigma(T) receive a
  smaller share of the innovation (variance enters the process noise);
- new functions take the fresh estimate directly (alpha=0, beta=1, K=0).

The filter state is a pytree; ``run_kalman`` drives it with ``lax.scan`` so a
full multi-hour trace filters in a single jitted call, and the fleet profiler
vmaps it over nodes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.disaggregation import MATMUL_PRECISION, solve_nnls, solve_nnls_gram

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class KalmanConfig:
    alpha: float = 0.8  # memory on the previous estimate
    beta: float = 0.2   # weight on the fresh disaggregation U_i
    gamma: float = 0.1  # weight of latency variance in process noise
    delta: float = 1.0  # measurement window (s); r proportional to 1/delta
    ridge_lambda: float = 1e-3
    nnls_iters: int = 200
    r_scale: float = 1.0  # measurement noise r = r_scale / delta


class KalmanState(NamedTuple):
    x: Array          # (M,) per-function power estimate (watts)
    p: Array          # (M,) process-noise variance (diagonal)
    seen: Array       # (M,) bool: has the function ever been active
    lat_mean: Array   # (M,) running mean of latency (Welford)
    lat_m2: Array     # (M,) running sum of squared deviations
    lat_count: Array  # (M,) number of latency observations


def kalman_init(num_fns: int, x0: Array | None = None, p0: float = 1.0) -> KalmanState:
    """Initial state.  ``x0`` comes from statistical disaggregation over the
    large initial time-step (N_init ~ 2 min, §4.2), or from a previous
    profiling run / another server in the cluster."""
    x = jnp.zeros((num_fns,), jnp.float32) if x0 is None else x0.astype(jnp.float32)
    seen = jnp.zeros((num_fns,), bool) if x0 is None else x > 0
    return KalmanState(
        x=x,
        p=jnp.full((num_fns,), p0, jnp.float32),
        seen=seen,
        lat_mean=jnp.zeros((num_fns,), jnp.float32),
        lat_m2=jnp.zeros((num_fns,), jnp.float32),
        lat_count=jnp.zeros((num_fns,), jnp.float32),
    )


def _welford_update(state: KalmanState, lat_sum: Array, lat_sumsq: Array, n: Array):
    """Batch Welford merge of per-step latency moments into the running ones.

    ``lat_sum/lat_sumsq/n`` are per-function sums over the step's invocations.
    """
    n_old = state.lat_count
    n_new = n_old + n
    safe = jnp.maximum(n_new, 1.0)
    batch_mean = lat_sum / jnp.maximum(n, 1.0)
    delta = batch_mean - state.lat_mean
    mean = jnp.where(n > 0, state.lat_mean + delta * n / safe, state.lat_mean)
    batch_m2 = jnp.maximum(lat_sumsq - n * batch_mean**2, 0.0)
    m2 = jnp.where(
        n > 0, state.lat_m2 + batch_m2 + delta**2 * n_old * n / safe, state.lat_m2
    )
    return mean, m2, n_new


def latency_variance(state: KalmanState) -> Array:
    """sigma^2(T): running per-function latency variance."""
    return state.lat_m2 / jnp.maximum(state.lat_count - 1.0, 1.0)


def _apply_update(
    state: KalmanState,
    u: Array,          # (M,) fresh disaggregation U_i
    z: Array,          # scalar innovation
    a_step: Array,
    lat_sum: Array,
    lat_sumsq: Array,
    config: KalmanConfig,
) -> tuple[KalmanState, Array]:
    """Shared gain/covariance/masked-update tail of one Kalman step.

    Both the raw windowed step and the gram-hoisted step call this, so the
    update rule cannot drift between the sequential oracle and the batched
    engine (their 1e-5 equivalence is a tested invariant).
    """
    alpha, beta, gamma = config.alpha, config.beta, config.gamma
    r = config.r_scale / config.delta
    active = a_step > 0

    # Process noise folds in historical latency variance (high-variance
    # functions get larger P -> but their share of the innovation is tempered
    # below through the joint gain denominator).
    mean, m2, n_new = _welford_update(state, lat_sum, lat_sumsq, a_step)
    sigma_t = m2 / jnp.maximum(n_new - 1.0, 1.0)
    p = alpha * state.p + gamma * sigma_t

    # Gain: K = P A^T / (A P A^T + r); A P A^T is a scalar contraction.
    # K_j A_j = P_j A_j^2 / (sum_i P_i A_i^2 + r) <= 1, so the covariance
    # update below is non-negative in exact arithmetic; the clamp guards the
    # float32 edge case so P stays PSD over arbitrarily long scan horizons.
    apat = jnp.sum(a_step * p * a_step)
    k = p * a_step / (apat + r)
    p_new = jnp.maximum((1.0 - k * a_step) * p, 0.0)

    x_update = alpha * state.x + beta * u + k * z
    # New functions (first activity): take the fresh estimate directly.
    is_new = active & (~state.seen)
    x_update = jnp.where(is_new, u, x_update)
    # Inactive functions: footprint unchanged (paper: "functions not executed
    # in the interval should see no changes").
    x_new = jnp.where(active, jnp.maximum(x_update, 0.0), state.x)
    p_new = jnp.where(active, p_new, state.p)

    new_state = KalmanState(
        x=x_new,
        p=p_new,
        seen=state.seen | active,
        lat_mean=mean,
        lat_m2=m2,
        lat_count=n_new,
    )
    return new_state, x_new


@functools.partial(jax.jit, static_argnames=("config",))
def kalman_step(
    state: KalmanState,
    c_step: Array,      # (n_w, M) contribution windows in this Kalman step
    w_step: Array,      # (n_w,)  power measurements (already idle-adjusted)
    a_step: Array,      # (M,)    invocation counts in this step
    lat_sum: Array,     # (M,)    sum of latencies of invocations in step
    lat_sumsq: Array,   # (M,)    sum of squared latencies
    config: KalmanConfig = KalmanConfig(),
) -> tuple[KalmanState, Array]:
    """One Kalman update (Fig. 4).  Returns (new_state, X_hat_i)."""
    # Fresh disaggregation on this step's windows: U_i.
    u = solve_nnls(c_step, w_step, config.ridge_lambda, iters=config.nnls_iters)

    # Innovation: mean residual of the previous estimate on new measurements.
    resid = w_step - jnp.matmul(c_step, state.x, precision=MATMUL_PRECISION)
    window_active = jnp.sum(c_step, axis=1) > 0
    z = jnp.sum(resid * window_active) / jnp.maximum(jnp.sum(window_active), 1.0)

    return _apply_update(state, u, z, a_step, lat_sum, lat_sumsq, config)


@functools.partial(jax.jit, static_argnames=("config",))
def run_kalman(
    state: KalmanState,
    c_steps: Array,     # (S, n_w, M)
    w_steps: Array,     # (S, n_w)
    a_steps: Array,     # (S, M)
    lat_sums: Array,    # (S, M)
    lat_sumsqs: Array,  # (S, M)
    config: KalmanConfig = KalmanConfig(),
) -> tuple[KalmanState, Array]:
    """Scan ``kalman_step`` over S sequential Kalman steps.

    Returns the final state and the (S, M) trajectory of estimates.
    """

    def body(st, inp):
        c, w, a, ls, lq = inp
        st, x = kalman_step(st, c, w, a, ls, lq, config)
        return st, x

    return jax.lax.scan(body, state, (c_steps, w_steps, a_steps, lat_sums, lat_sumsqs))


# ---------------------------------------------------------------------------
# Fleet-batched engine: N functions x B nodes x S steps in one jitted call.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",))
def run_kalman_fleet(
    states: KalmanState,  # leading node axis B on every leaf
    c_steps: Array,       # (B, S, n_w, M)
    w_steps: Array,       # (B, S, n_w)
    a_steps: Array,       # (B, S, M)
    lat_sums: Array,      # (B, S, M)
    lat_sumsqs: Array,    # (B, S, M)
    config: KalmanConfig = KalmanConfig(),
) -> tuple[KalmanState, Array]:
    """Whole-fleet Kalman: vmap ``run_kalman`` over the node axis so every
    node's full step sequence filters in a single jitted call.  Returns the
    batched final states and the (B, S, M) estimate trajectories."""

    def one_node(st, c, w, a, ls, lq):
        return run_kalman(st, c, w, a, ls, lq, config)

    return jax.vmap(one_node)(states, c_steps, w_steps, a_steps, lat_sums, lat_sumsqs)


class KalmanStepInputs(NamedTuple):
    """Per-step sufficient statistics with the window dimension pre-reduced.

    The raw ``kalman_step`` touches its (n_w, M) window block three times
    (gram assembly, rhs, innovation).  All three are linear in the windows,
    so they can be hoisted out of the scan into one batched pass — on TPU
    the Pallas gram kernel (``kernels.disagg_solve``) owns that pass — and
    the scan body then carries only O(M^2) state per step.
    """

    gram: Array      # (..., M, M) C^T C + lam I per step
    rhs: Array       # (..., M)    C^T W per step
    s_w: Array       # (...)       sum of W over active windows
    s_c: Array       # (..., M)    column sums of C over active windows
    n_act: Array     # (...)       number of active windows
    a: Array         # (..., M)    invocation counts
    lat_sum: Array   # (..., M)
    lat_sumsq: Array  # (..., M)


def precompute_step_inputs(
    c_steps: Array,     # (..., n_w, M) with any leading batch dims
    w_steps: Array,     # (..., n_w)
    a_steps: Array,
    lat_sums: Array,
    lat_sumsqs: Array,
    config: KalmanConfig = KalmanConfig(),
    *,
    gram_fn=None,
) -> KalmanStepInputs:
    """Reduce the window dimension for every step in one batched pass.

    ``gram_fn(c, w) -> (gram, rhs)`` overrides the assembly backend (the
    Pallas kernel path); the default is a pair of XLA contractions.
    """
    m = c_steps.shape[-1]
    if gram_fn is None:
        gram = jnp.einsum(
            "...nm,...nk->...mk", c_steps, c_steps, precision=MATMUL_PRECISION
        )
        rhs = jnp.einsum(
            "...nm,...n->...m", c_steps, w_steps, precision=MATMUL_PRECISION
        )
    else:
        lead = c_steps.shape[:-2]
        gram, rhs = gram_fn(
            c_steps.reshape((-1,) + c_steps.shape[-2:]), w_steps.reshape((-1, w_steps.shape[-1]))
        )
        gram = gram.reshape(lead + (m, m))
        rhs = rhs.reshape(lead + (m,))
    gram = gram + config.ridge_lambda * jnp.eye(m, dtype=gram.dtype)
    window_active = jnp.sum(c_steps, axis=-1) > 0
    wa = window_active.astype(c_steps.dtype)
    return KalmanStepInputs(
        gram=gram,
        rhs=rhs,
        s_w=jnp.sum(w_steps * wa, axis=-1),
        s_c=jnp.einsum("...nm,...n->...m", c_steps, wa, precision=MATMUL_PRECISION),
        n_act=jnp.sum(wa, axis=-1),
        a=a_steps,
        lat_sum=lat_sums,
        lat_sumsq=lat_sumsqs,
    )


@functools.partial(jax.jit, static_argnames=("config",))
def kalman_step_gram(
    state: KalmanState,
    inp: KalmanStepInputs,  # one step: gram (M, M), rhs (M,), ...
    config: KalmanConfig = KalmanConfig(),
) -> tuple[KalmanState, Array]:
    """``kalman_step`` on pre-reduced window statistics (same update rule)."""
    u = solve_nnls_gram(inp.gram, inp.rhs, iters=config.nnls_iters)

    # Innovation from the hoisted linear statistics:
    # sum_w (W - C X) * active = s_w - s_c . X.
    z = (
        inp.s_w - jnp.dot(inp.s_c, state.x, precision=MATMUL_PRECISION)
    ) / jnp.maximum(inp.n_act, 1.0)

    return _apply_update(state, u, z, inp.a, inp.lat_sum, inp.lat_sumsq, config)


@functools.partial(jax.jit, static_argnames=("config",))
def run_kalman_gram(
    state: KalmanState,
    inputs: KalmanStepInputs,   # leading (S,) on every leaf
    config: KalmanConfig = KalmanConfig(),
) -> tuple[KalmanState, Array]:
    """Single-node scan over pre-reduced steps."""

    def body(s, inp):
        return kalman_step_gram(s, inp, config)

    return jax.lax.scan(body, state, inputs)


@functools.partial(jax.jit, static_argnames=("config",))
def run_kalman_fleet_gram(
    states: KalmanState,        # leading node axis B
    inputs: KalmanStepInputs,   # leading (B, S) on every leaf
    config: KalmanConfig = KalmanConfig(),
) -> tuple[KalmanState, Array]:
    """Fleet scan over pre-reduced steps: the O(M^2)-per-step hot path."""
    return jax.vmap(lambda st, ni: run_kalman_gram(st, ni, config))(states, inputs)
