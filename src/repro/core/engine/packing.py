"""Packing stage: per-window host arrays → (B, S, n_w, ...) engine batches.

``pack_fleet_inputs`` is the one place the ragged-fleet pad-and-mask
contract is defined on the way *in* (its mask is then folded exactly once
by ``plan.resolve_plan``); the ``synthetic_*`` generators are the shared
input factories the equivalence tests and benchmarks both draw from, so
they exercise the same contract the real telemetry path does.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from repro.core.engine.types import Array, FleetInputs


def synthetic_fleet(
    b: int, s: int, n_w: int, m: int, *, seed: int = 0, density: float = 0.2
) -> FleetInputs:
    """Randomized synthetic fleet batch: sparse contributions, true power
    plus noise.  Shared input generator of the equivalence tests."""
    import numpy as np

    rng = np.random.default_rng(seed)
    c = np.abs(rng.standard_normal((b, s, n_w, m))) * (
        rng.random((b, s, n_w, m)) > 1 - density
    )
    x_true = np.abs(rng.standard_normal((b, m))) * 20.0 + 2.0
    w = np.einsum("bsnm,bm->bsn", c, x_true) + 0.1 * rng.standard_normal((b, s, n_w))
    a = (rng.random((b, s, m)) > 0.5) * rng.integers(0, 4, (b, s, m))
    lat = np.abs(rng.standard_normal((b, s, m)))
    return FleetInputs(
        c=jnp.asarray(c, jnp.float32),
        w=jnp.asarray(np.maximum(w, 0.0), jnp.float32),
        a=jnp.asarray(a, jnp.float32),
        lat_sum=jnp.asarray(lat * a, jnp.float32),
        lat_sumsq=jnp.asarray(lat**2 * a, jnp.float32),
    )


def pack_fleet_inputs(
    c_windows: Array,    # (B, N, M) per-node contribution matrices
    w_windows: Array,    # (B, N) per-node idle-adjusted power
    a_windows: Array,    # (B, N, M) per-node invocation counts
    lat_sum_w: Array,    # (B, N, M) per-window latency sums
    lat_sumsq_w: Array,  # (B, N, M)
    *,
    step_windows: int,
    lengths: Sequence[int] | Array | None = None,
    fn_lengths: Sequence[int] | Array | None = None,
    strict: bool = False,
) -> FleetInputs:
    """Group per-window arrays into (B, S, n_w, ...) Kalman-step blocks,
    padding + masking ragged fleets instead of truncating them.

    Each node ``i`` contributes ``lengths[i]`` real windows (arrays are
    padded to a common N on the window axis; values past a node's length
    are ignored).  A Kalman update is defined over a full ``step_windows``
    block, so node ``i`` yields ``S_i = lengths[i] // step_windows`` steps
    — the sub-step remainder feeds no update, exactly like the per-node
    profiler's ``segment_plan`` tail — and the fleet packs to
    ``S = max_i S_i`` steps with a ``(B, S, n_w)`` validity mask marking
    each node's real ticks.  Everything outside a node's valid region is
    zeroed and masked, so junk in the padded tail of the caller's arrays
    can never leak into grams, innovations, or attribution.  A uniform
    fleet whose window count divides ``step_windows`` packs with
    ``mask=None`` — the dense engines' exact pre-ragged inputs.

    Args:
      c_windows/w_windows: (B, N, M)/(B, N) per-window contributions/power.
      a_windows/lat_sum_w/lat_sumsq_w: (B, N, M) per-window invocation
        counts and latency moments (summed into per-step statistics).
      step_windows: n_w, ticks per Kalman step.
      lengths: per-node real window counts; ``None`` means every node has
        all N windows.
      fn_lengths: per-node real *function* counts over the padded M axis
        (heterogeneous fleets whose nodes host different function sets pad
        M to the fleet max); ``None`` means every node hosts all M
        functions.  Sets ``FleetInputs.fn_mask`` so the engines zero the
        padded functions' statistics and output rows exactly.
      strict: require the old equal-length contract — every node must have
        exactly N windows and N must divide ``step_windows`` evenly;
        anything ragged raises ``ValueError`` instead of being masked.

    Returns:
      ``FleetInputs`` with S = max_i(lengths[i] // step_windows) steps and
      ``mask`` set iff the fleet is actually ragged.
    """
    b, n, m = c_windows.shape
    if lengths is None:
        lengths_arr = jnp.full((b,), n, jnp.int32)
    else:
        import numpy as np

        lengths_np = np.asarray(lengths, np.int64)
        if lengths_np.shape != (b,):
            raise ValueError(
                f"lengths must have shape ({b},), got {lengths_np.shape}"
            )
        if np.any(lengths_np < 0) or np.any(lengths_np > n):
            raise ValueError(
                f"lengths must lie in [0, {n}] (the padded window axis); "
                f"got {lengths_np.tolist()}"
            )
        lengths_arr = jnp.asarray(lengths_np, jnp.int32)
    if strict:
        import numpy as np

        lens = np.asarray(lengths_arr)
        if np.any(lens != n) or n % step_windows != 0:
            raise ValueError(
                f"pack_fleet_inputs(strict=True) requires every node to "
                f"have exactly N={n} windows with N divisible by "
                f"step_windows={step_windows}; got lengths="
                f"{lens.tolist()} (use strict=False for pad-and-mask)"
            )
    s_nodes = lengths_arr // step_windows            # (B,) full steps per node
    s = int(jnp.max(s_nodes))
    if s == 0:
        raise ValueError(
            f"need at least step_windows={step_windows} windows on at "
            f"least one node, got lengths "
            f"{jnp.asarray(lengths_arr).tolist()} (N={n})"
        )
    n_used = s * step_windows
    if n < n_used:
        raise ValueError(f"window axis N={n} shorter than S*n_w={n_used}")
    # Per-node valid region: the first S_i full steps' ticks, nothing else.
    tick_valid = (
        jnp.arange(n_used, dtype=jnp.int32)[None, :]
        < (s_nodes * step_windows)[:, None]
    )                                                # (B, n_used) bool
    mask = tick_valid.reshape(b, s, step_windows).astype(jnp.float32)
    mv = mask[..., None]
    fn_mask = None
    if fn_lengths is not None:
        import numpy as np

        fn_lens = np.asarray(fn_lengths, np.int64)
        if fn_lens.shape != (b,):
            raise ValueError(
                f"fn_lengths must have shape ({b},), got {fn_lens.shape}"
            )
        if np.any(fn_lens < 0) or np.any(fn_lens > m):
            raise ValueError(
                f"fn_lengths must lie in [0, {m}] (the padded function "
                f"axis); got {fn_lens.tolist()}"
            )
        if np.any(fn_lens != m):
            fn_mask = jnp.asarray(
                np.arange(m)[None, :] < fn_lens[:, None], jnp.float32
            )
    grp = lambda x: x[:, :n_used].reshape(b, s, step_windows, m)
    inputs = FleetInputs(
        c=grp(c_windows) * mv,
        w=w_windows[:, :n_used].reshape(b, s, step_windows) * mask,
        a=(grp(a_windows) * mv).sum(axis=2),
        lat_sum=(grp(lat_sum_w) * mv).sum(axis=2),
        lat_sumsq=(grp(lat_sumsq_w) * mv).sum(axis=2),
        mask=None if bool(jnp.all(tick_valid)) else mask,
        fn_mask=fn_mask,
    )
    return inputs


def synthetic_ragged_windows(
    b: int, n: int, m: int, *, lengths: Sequence[int], seed: int = 0,
    density: float = 0.2,
):
    """Per-*window* synthetic fleet arrays for ragged packing.

    The window-granular twin of ``synthetic_fleet``: returns
    ``(c, w, a, lat_sum, lat_sumsq)`` with shape (B, N, ...) plus the
    given per-node ``lengths``, ready for ``pack_fleet_inputs``.  Windows
    past each node's length are filled with *non-zero junk* on purpose —
    the pad-and-mask contract says they must not be able to leak into any
    result, and the ragged tests rely on that property being exercised,
    not vacuously true.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    c = np.abs(rng.standard_normal((b, n, m))) * (rng.random((b, n, m)) > 1 - density)
    x_true = np.abs(rng.standard_normal((b, m))) * 20.0 + 2.0
    w = np.maximum(
        np.einsum("bnm,bm->bn", c, x_true) + 0.1 * rng.standard_normal((b, n)), 0.0
    )
    a = ((rng.random((b, n, m)) > 0.8) * rng.integers(0, 3, (b, n, m))).astype(np.float32)
    lat = np.abs(rng.standard_normal((b, n, m)))
    ls, lq = lat * a, lat**2 * a
    # Junk beyond each node's real windows: masking must erase it exactly.
    for i, li in enumerate(lengths):
        c[i, li:] = 7.7
        w[i, li:] = 123.0
        a[i, li:] = 3.0
        ls[i, li:] = 9.9
        lq[i, li:] = 9.9
    return (
        jnp.asarray(c, jnp.float32),
        jnp.asarray(w, jnp.float32),
        jnp.asarray(a, jnp.float32),
        jnp.asarray(ls, jnp.float32),
        jnp.asarray(lq, jnp.float32),
    )
