"""CPU/chip power modeling from performance counters (paper §4.3).

A linear model theta maps a function's *normalized* counter vector S to its
chip-level power:  X_CPU = theta(S).  The paper trains a linear-kernel SVR
(SmartWatts/PowerAPI-style) over the standard counters (unhalted core/
reference cycles, LLC misses, instructions retired); we keep the model linear
and explainable, per the paper's design requirement.

TPU adaptation: the counter vector is the step-counter analogue —
(FLOPs, HBM bytes, collective bytes, duty cycle), each normalized by the
system-wide totals of the interval; same normalization scheme as the paper
(function counters / system counters).

Two trainers:

- ``fit_ridge``: closed-form ridge regression (default; exact, fast).  The
  normal equations are solved in *standardized* feature space: the raw
  counter scales ``telemetry.counters.window_counters`` emits differ by
  ~1e3 (GFLOP/s vs duty cycle), which made the raw-space gram
  ill-conditioned in float32.
- ``fit_linear_svr``: epsilon-insensitive linear SVR via proximal subgradient
  descent in ``lax.fori_loop`` — the in-JAX stand-in for the paper's
  sklearn SVR (no sklearn on the target hosts).

Every inference/training entry point is *fleet-batched*: a model whose
``weights``/``bias`` carry a leading ``(B,)`` node axis (one model per node,
as stacked by ``stack_models`` or a batched ``fit_ridge`` call) is applied
to ``(B, ...)`` feature arrays in one jitted call — this is what lets the
fleet engines run combined mode (§4.3) without per-node Python loops.

Model health is monitored (observed chip power vs sum of predicted function
powers); ``needs_retrain`` flags drift beyond the threshold (default 5 %),
matching the paper's continuous-retraining loop — ``retrain_flags`` is its
traceable fleet-shaped twin used by the streaming session.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.disaggregation import MATMUL_PRECISION

Array = jax.Array


class LinearPowerModel(NamedTuple):
    """theta: weights (F,) watts-per-counter + bias () watts.

    Fleet-batched models carry a leading node axis — weights ``(B, F)``,
    bias ``(B,)`` — and every predictor in this module broadcasts over it.
    """

    weights: Array  # (F,) per-counter watts; (B, F) for a fleet of models
    bias: Array     # scalar watts; (B,) for a fleet of models


@dataclasses.dataclass(frozen=True)
class CpuModelConfig:
    ridge_lambda: float = 1e-4
    svr_epsilon: float = 0.5     # watts of insensitivity
    svr_lr: float = 3e-2
    svr_iters: int = 20_000
    retrain_threshold: float = 0.05  # 5 % model error triggers retraining


def stack_models(models: Sequence[LinearPowerModel]) -> LinearPowerModel:
    """Stack per-node models into one fleet-batched ``LinearPowerModel``.

    The result has ``weights (B, F)`` / ``bias (B,)`` and can be fed
    directly to the batched predictors (``predict_power``,
    ``predict_function_power_split``, ``model_error``)."""
    return LinearPowerModel(
        weights=jnp.stack([jnp.asarray(m.weights) for m in models]),
        bias=jnp.stack([jnp.reshape(jnp.asarray(m.bias), ()) for m in models]),
    )


def model_row(model: LinearPowerModel, i: int) -> LinearPowerModel:
    """Slice node ``i``'s model out of a fleet-batched model."""
    return LinearPowerModel(weights=model.weights[i], bias=model.bias[i])


def _fit_ridge_one(features: Array, power: Array, lam, mask=None) -> LinearPowerModel:
    # Standardize (as fit_linear_svr already did): the counter features span
    # ~3 orders of magnitude, and the raw-space normal equations are
    # ill-conditioned in float32.  The ridge penalty applies to the
    # standardized weights, so lam is scale-free.  ``mask`` (N,) weights the
    # solve (ragged sliding windows: dead windows carry weight 0); the
    # moments and normal equations become mask-weighted, and an all-masked
    # input degenerates to the zero-weights / zero-bias model instead of a
    # singular solve.
    n, f = features.shape
    if mask is None:
        m = jnp.ones((n,), features.dtype)
    else:
        m = mask.astype(features.dtype)
    msum = jnp.maximum(jnp.sum(m), 1e-9)
    x_mean = jnp.sum(features * m[:, None], axis=0) / msum
    x_var = jnp.sum((features - x_mean) ** 2 * m[:, None], axis=0) / msum
    x_std = jnp.maximum(jnp.sqrt(x_var), 1e-8)
    xs = (features - x_mean) / x_std
    ones = jnp.ones((n, 1), features.dtype)
    xb = jnp.concatenate([xs, ones], axis=1)
    reg = lam * jnp.eye(f + 1, dtype=features.dtype)
    # Don't penalize the bias — except, under a mask, by a vanishing epsilon
    # that keeps the gram invertible when every sample is masked out (the
    # unmasked path stays bit-identical to the pre-mask solve).
    reg = reg.at[f, f].set(0.0 if mask is None else 1e-9)
    xm = (xb * m[:, None]).T
    theta = jnp.linalg.solve(
        jnp.matmul(xm, xb, precision=MATMUL_PRECISION) + reg,
        jnp.matmul(xm, power, precision=MATMUL_PRECISION),
    )
    w = theta[:f] / x_std
    b = theta[f] - jnp.sum(theta[:f] * x_mean / x_std)
    return LinearPowerModel(weights=w, bias=b)


@jax.jit
def fit_ridge(
    features: Array, power: Array, lam: float = 1e-4, *, mask: Array | None = None
) -> LinearPowerModel:
    """Closed-form ridge fit of power ~ features (standardized solve).

    Args:
      features: (N, F) system-interval counter vectors, or (B, N, F) for a
        fleet — one independent model is fit per node, vmapped.
      power: (N,) observed chip power (watts), or (B, N).
      mask: optional (N,)/(B, N) sample weights — the streaming refit passes
        each node's live-window mask so a ragged fleet's dead (zero-padded)
        windows don't drag the fit (mask-weighted moments + normal
        equations).

    Returns:
      ``LinearPowerModel`` with (F,)/() leaves, or (B, F)/(B,) when batched.
    """
    if features.ndim == 3:
        return jax.vmap(_fit_ridge_one, in_axes=(0, 0, None, None if mask is None else 0))(
            features, power, lam, mask
        )
    return _fit_ridge_one(features, power, lam, mask)


def merge_models(
    old: LinearPowerModel, new: LinearPowerModel, flags: Array
) -> LinearPowerModel:
    """Row-wise swap of fleet-batched models: nodes with ``flags`` take
    ``new``'s (weights, bias), the rest keep ``old``'s.

    This is the streaming retrain swap: model parameters are *data* to the
    jitted engine/predictor calls, so replacing rows triggers no retrace —
    the next ``predict_*`` simply contracts against the new weights.
    """
    f = jnp.asarray(flags)
    return LinearPowerModel(
        weights=jnp.where(f[:, None], new.weights, old.weights),
        bias=jnp.where(f, new.bias, old.bias),
    )


def _fit_svr_one(features: Array, power: Array, lam, epsilon, lr, iters) -> LinearPowerModel:
    n, f = features.shape
    x_mean = jnp.mean(features, axis=0)
    x_std = jnp.maximum(jnp.std(features, axis=0), 1e-8)
    xs = (features - x_mean) / x_std

    def loss(params):
        w, b = params
        resid = jnp.matmul(xs, w, precision=MATMUL_PRECISION) + b - power
        hinge = jnp.maximum(jnp.abs(resid) - epsilon, 0.0)
        return jnp.mean(hinge) + 0.5 * lam * jnp.sum(w * w)

    grad = jax.grad(loss)

    def body(i, params):
        g = grad(params)
        step = lr / jnp.sqrt(1.0 + i)  # diminishing step for convergence
        return (params[0] - step * g[0], params[1] - step * g[1])

    w0 = jnp.zeros((f,), features.dtype)
    b0 = jnp.asarray(jnp.mean(power), features.dtype)
    w, b = jax.lax.fori_loop(0, iters, body, (w0, b0))
    # De-standardize back to raw feature space.
    w_raw = w / x_std
    b_raw = b - jnp.sum(w * x_mean / x_std)
    return LinearPowerModel(weights=w_raw, bias=b_raw)


@functools.partial(jax.jit, static_argnames=("iters",))
def fit_linear_svr(
    features: Array,
    power: Array,
    lam: float = 1e-4,
    epsilon: float = 0.5,
    lr: float = 3e-2,
    *,
    iters: int = 20_000,
) -> LinearPowerModel:
    """Linear epsilon-SVR via subgradient descent on the primal.

    loss = mean(max(|Xw + b - y| - eps, 0)) + lam/2 ||w||^2

    Like ``fit_ridge``, the trainer is fleet-batched: ``(B, N, F)`` features
    with ``(B, N)`` power fit one independent model per node by vmapping the
    whole subgradient loop — a heterogeneous fleet trains every node's SVR
    in one jitted call, and each row matches the sequential per-node fit.

    Returns:
      ``LinearPowerModel`` with (F,)/() leaves, or (B, F)/(B,) when batched.
    """
    if features.ndim == 3:
        return jax.vmap(_fit_svr_one, in_axes=(0, 0, None, None, None, None))(
            features, power, lam, epsilon, lr, iters
        )
    return _fit_svr_one(features, power, lam, epsilon, lr, iters)


def _dynamic_power(model: LinearPowerModel, features: Array) -> Array:
    """features (..., F) x weights -> (...); fleet-batched models contract
    each node's features against that node's own weight row."""
    w = model.weights
    if w.ndim == 1:
        return jnp.matmul(features, w, precision=MATMUL_PRECISION)
    return jnp.einsum("b...f,bf->b...", features, w, precision=MATMUL_PRECISION)


def _bias_like(model: LinearPowerModel, out_ndim: int) -> Array:
    """Bias broadcast against a (...,) prediction of rank ``out_ndim``."""
    b = model.bias
    if b.ndim == 0:
        return b
    return b.reshape(b.shape + (1,) * (out_ndim - 1))


@jax.jit
def predict_power(model: LinearPowerModel, features: Array) -> Array:
    """X_CPU = theta(S).  features: (..., F) -> (...,) watts.

    With a fleet-batched model (weights (B, F)), features are (B, ..., F)
    and each node is evaluated under its own model."""
    dyn = _dynamic_power(model, features)
    return dyn + _bias_like(model, dyn.ndim)


@jax.jit
def predict_function_power_split(
    model: LinearPowerModel, fn_features: Array, fn_active_frac: Array
) -> tuple[Array, Array]:
    """Per-function chip power plus the *un-attributed* static bias.

    The bias (static chip power) is amortized over functions by activity
    fraction so summing over functions reproduces the interval's chip power
    estimate.  On an idle interval (``sum(fn_active_frac) ~ 0``) there is no
    activity to amortize over; instead of silently dropping the bias (which
    made combined-mode footprints violate conservation on quiet segments)
    it is returned as the second element, for the caller to route into the
    report's idle/offset term:

        sum(per_fn) + residual == relu-clamped theta(total counters)

    Args:
      fn_features: (M, F) per-function counters normalized by system totals,
        or (B, M, F) for a fleet (with a fleet-batched model).
      fn_active_frac: (M,) or (B, M) fraction of the interval each function
        was running.

    Returns:
      ``(per_fn, residual)`` — (M,)/(B, M) watts per function and the ()/
      (B,) watts of static bias left un-attributed (non-zero only on idle
      intervals).
    """
    dynamic = _dynamic_power(model, fn_features)          # (..., M)
    bias = _bias_like(model, dynamic.ndim)                # broadcastable
    total = jnp.sum(fn_active_frac, axis=-1, keepdims=True)
    has = total > 1e-9
    static_share = jnp.where(
        has, bias * fn_active_frac / jnp.where(has, total, 1.0), 0.0
    )
    residual = jnp.where(has[..., 0], 0.0, model.bias)
    return jnp.maximum(dynamic, 0.0) + static_share, residual


@jax.jit
def predict_function_power(
    model: LinearPowerModel, fn_features: Array, fn_active_frac: Array
) -> Array:
    """Per-function chip power from per-function normalized counters.

    The attributed half of ``predict_function_power_split``; callers that
    must conserve energy on idle intervals (the combined-mode profiler)
    use the split form and route the residual bias into their idle term.
    """
    per_fn, _ = predict_function_power_split(model, fn_features, fn_active_frac)
    return per_fn


@jax.jit
def model_error(
    model: LinearPowerModel,
    features: Array,
    power: Array,
    *,
    mask: Array | None = None,
) -> Array:
    """Relative error of the model on held-out intervals (retraining signal).

    (N, F)/(N,) inputs give a scalar; fleet-batched (B, N, F)/(B, N) inputs
    give one error per node, (B,).  ``mask`` (matching ``power``) restricts
    the mean to valid intervals — a ragged fleet's dead windows score 0 and
    a node with none stays at error 0.  This is the single definition of
    the retraining criterion; ``retrain_flags``/``needs_retrain`` and the
    streaming session's per-step checks all reduce through it.
    """
    pred = predict_power(model, features)
    rel = jnp.abs(pred - power) / jnp.maximum(power, 1e-9)
    if mask is None:
        return jnp.mean(rel, axis=-1)
    m = mask.astype(rel.dtype)
    return jnp.sum(rel * m, axis=-1) / jnp.maximum(jnp.sum(m, axis=-1), 1.0)


def retrain_flags(
    model: LinearPowerModel,
    features: Array,
    power: Array,
    config: CpuModelConfig = CpuModelConfig(),
    *,
    mask: Array | None = None,
) -> Array:
    """Traceable fleet retrain signal: (B,) bool, no host sync.

    The streaming session evaluates this at every Kalman-step boundary
    (paper: retrain when observed-vs-predicted error exceeds 5 %), with
    ``mask`` marking each node's live windows on a ragged fleet."""
    return model_error(model, features, power, mask=mask) > config.retrain_threshold


def needs_retrain(
    model: LinearPowerModel,
    features: Array,
    power: Array,
    config: CpuModelConfig = CpuModelConfig(),
) -> bool:
    """Paper: retrain when observed-vs-predicted error exceeds 5 %."""
    return float(model_error(model, features, power)) > config.retrain_threshold
