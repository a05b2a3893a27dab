"""Step counters: the TPU-native analogue of perf counters (paper §4.3).

The paper's CPU model consumes UNHALTED_CYCLES / LLC_MISSES /
INSTRUCTIONS_RETIRED per function, normalized by the system-wide totals.
Our invocation classes carry (FLOPs, HBM bytes) per invocation — the
quantities a compiled step's ``cost_analysis()`` exposes — plus busy time.
Features per interval (F = 3): [gflop rate, hbm GB rate, duty cycle], each
normalized exactly like the paper normalizes counters.

Both builders are *fleet-shaped*: they accept one node's ``(N, M)``
contribution matrix or a whole fleet's ``(B, N, M)`` stack and emit the
``(B, N, F)`` / ``(B, M, F)`` feature batches the combined-mode fleet
engines consume — jnp throughout, so they compose under jit/vmap.  A
ragged fleet passes its ``(…, N)`` tick-validity ``mask``: padded windows
are zeroed before any reduction, so junk past a node's real span feeds
neither the per-window features nor the per-function normalization totals.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.disaggregation import MATMUL_PRECISION

Array = jax.Array

NUM_FEATURES = 3


def _prep(c_matrix, mean_latency, mask):
    c = jnp.asarray(c_matrix, jnp.float32)
    lat = jnp.maximum(jnp.asarray(mean_latency, jnp.float32), 1e-6)
    if mask is not None:
        c = c * jnp.asarray(mask, c.dtype)[..., None]
    return c, lat


@jax.jit
def window_counters(
    c_matrix: Array,      # (..., N, M) seconds of runtime per window
    gflops: Array,        # (M,) per invocation
    hbm_gb: Array,        # (M,)
    mean_latency: Array,  # (M,)
    delta: float,
    *,
    mask: Array | None = None,  # (..., N) window validity; None = all real
) -> Array:
    """(..., N, F) system-wide counter features per window.

    Works per node (``(N, M)`` in, ``(N, F)`` out) or fleet-batched
    (``(B, N, M)`` in, ``(B, N, F)`` out) in one shot; masked (padded)
    windows produce all-zero feature rows.
    """
    c, lat = _prep(c_matrix, mean_latency, mask)
    gflop_rate = jnp.asarray(gflops, jnp.float32) / lat   # GFLOP/s while running
    hbm_rate = jnp.asarray(hbm_gb, jnp.float32) / lat
    feats = jnp.stack(
        [
            jnp.matmul(c, gflop_rate, precision=MATMUL_PRECISION),  # GFLOPs in window
            jnp.matmul(c, hbm_rate, precision=MATMUL_PRECISION),    # HBM GB in window
            jnp.sum(c, axis=-1),         # busy seconds in window
        ],
        axis=-1,
    )
    return feats / delta


@jax.jit
def function_counters(
    c_matrix: Array,      # (..., N, M)
    gflops: Array,        # (M,)
    hbm_gb: Array,        # (M,)
    mean_latency: Array,  # (M,)
    *,
    mask: Array | None = None,  # (..., N) window validity; None = all real
) -> Array:
    """(..., M, F) per-function counters normalized by system totals (the
    paper's 'function counters / system-wide counters' scheme).

    Fleet-batched input normalizes each node by its *own* totals; masked
    windows contribute to neither the numerators nor the totals.
    """
    c, lat = _prep(c_matrix, mean_latency, mask)
    busy = jnp.sum(c, axis=-2)                            # (..., M) seconds
    rates = jnp.stack(
        [
            jnp.asarray(gflops, jnp.float32) / lat,
            jnp.asarray(hbm_gb, jnp.float32) / lat,
            jnp.ones_like(lat),
        ],
        axis=-1,
    )                                                     # (M, F)
    per_fn = busy[..., None] * rates                      # (..., M, F)
    totals = jnp.maximum(jnp.sum(per_fn, axis=-2, keepdims=True), 1e-9)
    return per_fn / totals
