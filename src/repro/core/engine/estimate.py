"""Initial-estimate stage: whole-trace X_0 solves (§4.2) for every path.

One gram-domain NNLS family, three entry points:

  ``fleet_initial_estimate``     batched over the node axis (segment paths);
  ``bucketed_initial_estimate``  one node, length-bucketed compile (serving
                                 admissions — see ``core.engine.buckets``);
  ``_node_init_gram``            the shared per-node gram/rhs contraction.

``_init_states`` turns a (B, M) X_0 into the batched Kalman start state —
the hand-off point between this stage and the filter stages.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.disaggregation import MATMUL_PRECISION
from repro.core.engine.types import Array, EngineConfig
from repro.core.kalman import KalmanState, kalman_init


def _gram_fn(backend: str) -> Callable | None:
    """Resolve the gram-assembly backend (None = XLA einsum)."""
    if backend == "auto":
        from repro.kernels.disagg_solve import default_backend

        backend = default_backend()
    if backend == "pallas":
        from repro.kernels.disagg_solve import disagg_gram

        # Off-TPU the kernel only runs in interpret mode (Python-speed;
        # for correctness work, which is why explicit backend="pallas"
        # still honors it rather than failing at compile time).
        return functools.partial(
            disagg_gram, interpret=jax.default_backend() != "tpu"
        )
    if backend == "xla":
        return None
    raise ValueError(f"unknown gram backend: {backend!r}")


def _node_init_gram(c_node: Array, w_node: Array) -> tuple[Array, Array]:
    """Whole-trace gram/rhs for one node via flat matmuls.

    The flat (S*n_w, M) contraction is used (rather than a stepwise einsum)
    because XLA keeps its reduction order identical under vmap — the batched
    engine and the sequential oracle see bitwise-equal grams.
    """
    cf = c_node.reshape(-1, c_node.shape[-1])
    return (
        jnp.matmul(cf.T, cf, precision=MATMUL_PRECISION),
        jnp.matmul(cf.T, w_node.reshape(-1), precision=MATMUL_PRECISION),
    )


def fleet_initial_estimate(
    c: Array, w: Array, config: EngineConfig = EngineConfig(), *, gram_fn=None
) -> Array:
    """(B, M) statistical disaggregation X_0 per node (§4.2).

    Accepts (B, N, M)/(B, N) window blocks or (B, S, n_w, M)/(B, S, n_w)
    step blocks — grams are additive over windows either way — and runs one
    batched gram-domain NNLS, no per-node loop.
    """
    from repro.core.disaggregation import solve_nnls_gram

    m = c.shape[-1]
    eye = config.init_lam * jnp.eye(m, dtype=c.dtype)
    if gram_fn is None:
        if c.shape[0] == 1:
            # XLA lowers batch-1 contractions differently from both the
            # plain and batch-N forms; route through the plain form so a
            # one-node fleet still matches the sequential oracle bitwise.
            g1, r1 = _node_init_gram(c[0], w[0])
            return solve_nnls_gram(g1 + eye, r1, iters=config.init_iters)[None]
        gram, rhs = jax.vmap(_node_init_gram)(c, w)
    else:
        gram, rhs = gram_fn(c.reshape(c.shape[0], -1, m), w.reshape(w.shape[0], -1))
    return solve_nnls_gram(gram + eye, rhs, iters=config.init_iters)


def _init_states(x0: Array) -> KalmanState:
    """Batched ``kalman_init`` from a (B, M) initial estimate."""
    return jax.vmap(lambda x: kalman_init(x.shape[-1], x0=x))(x0)
