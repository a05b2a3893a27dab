"""Invocation traces for a configuration, from a seed.

A vectorised numpy copy of the Azure-Functions arrival model that the
program's own generator (``repro.workload.azure``) implements per
invocation: per-function rates from log-normal popularity multipliers,
normalised so that the expected concurrency per node is ``load * M / 2``;
Poisson or Markov on/off (bursty) arrivals; log-normal execution times
around each function's mean with its coefficient of variation.

Every seed gets the same work: the number of invocations of each function
on each node is fixed by the configuration (its rate times the segment),
and the seed draws only where they fall and how long each runs.  Arrival
times given their count are uniform under a Poisson process, and follow
the on/off intensity under the bursty one.  Every node's arrays have the
same fixed length, padded with ``fn_id = -1``, so that the controller
compiles the same programs for every seed.
"""

from __future__ import annotations

import numpy as np


def function_rates(cfg: dict) -> np.ndarray:
    """(B, M) invocations per second, fixed by the configuration's seed."""
    w = cfg["workload"]
    lat = np.asarray([f["mean_latency_s"] for f in cfg["functions"]])
    m = lat.shape[0]
    rng = np.random.default_rng(cfg["config_seed"])
    mult = rng.lognormal(0.0, w["popularity_sigma"], size=(cfg["nodes"], m))
    base = mult / np.sum(mult * lat, axis=1, keepdims=True)
    return base * w["load"] * m / 2.0


def invocation_counts(cfg: dict, duration: float) -> np.ndarray:
    """(B, M) invocations of each function on each node over ``duration``."""
    return np.rint(function_rates(cfg) * duration).astype(np.int64)


def slots(cfg: dict, duration: float) -> int:
    """Fixed per-node array length: the busiest node, rounded up to 1024."""
    k = int(invocation_counts(cfg, duration).sum(1).max())
    return max(-(-k // 1024) * 1024, 1024)


def _onoff_times(rng, counts: np.ndarray, duration: float, w: dict) -> np.ndarray:
    """Arrival times with the density of a Markov on/off rate, per row."""
    rows = counts.shape[0]
    mean_cycle = w["burst_on_s"] + w["burst_off_s"]
    n_per = int(np.ceil(2.0 * duration / mean_cycle)) + 8
    on = rng.exponential(w["burst_on_s"], size=(rows, n_per))
    off = rng.exponential(w["burst_off_s"], size=(rows, n_per))
    lengths = np.stack([on, off], -1).reshape(rows, 2 * n_per)
    # Each row starts in the "on" state (as the program's generator does)
    # and alternates; the intensity in each period is factor or 1/factor.
    level = np.tile([w["burst_factor"], 1.0 / w["burst_factor"]], n_per)[None, :]
    edges = np.concatenate([np.zeros((rows, 1)), np.cumsum(lengths, 1)], 1)
    if np.any(edges[:, -1] < duration):
        raise ValueError("on/off schedule shorter than the segment")
    clipped = np.minimum(edges, duration)
    mass = np.cumsum(np.diff(clipped, axis=1) * level, 1)
    mass = np.concatenate([np.zeros((rows, 1)), mass], 1)
    total = mass[:, -1:]
    out = []
    for r in range(rows):
        u = np.sort(rng.uniform(0.0, total[r, 0], size=counts[r]))
        p = np.searchsorted(mass[r], u, side="right") - 1
        p = np.clip(p, 0, lengths.shape[1] - 1)
        out.append(clipped[r, p] + (u - mass[r, p]) / level[0, p])
    return np.concatenate(out) if out else np.zeros(0)


def generate(cfg: dict, duration: float, seed: int):
    """Per-node ``(fn_id, start, end)`` arrays of one fixed length.

    Returns ``(traces, k)``: ``traces[i]`` is node ``i``'s int32 ids and
    float32 start/end times (seconds, ends clipped to the segment), sorted
    by start with the padding last; ``k`` is the common length.
    """
    w = cfg["workload"]
    fns = cfg["functions"]
    lat = np.asarray([f["mean_latency_s"] for f in fns])
    cov = np.maximum(np.asarray([f["latency_cov"] for f in fns]), 1e-3)
    counts = invocation_counts(cfg, duration)                       # (B, M)
    b, m = counts.shape
    k = slots(cfg, duration)
    rng = np.random.default_rng(seed)
    flat = counts.reshape(-1)
    if w["arrival"] == "poisson":
        starts = rng.uniform(0.0, duration, size=int(flat.sum()))
    elif w["arrival"] == "bursty":
        starts = _onoff_times(rng, flat, duration, w)
    else:
        raise ValueError(f"unknown arrival process {w['arrival']!r}")
    fn = np.repeat(np.tile(np.arange(m), b), flat)
    node = np.repeat(np.repeat(np.arange(b), m), flat)
    sigma2 = np.log1p(cov * cov)
    mu = np.log(lat) - 0.5 * sigma2
    dur = rng.lognormal(mu[fn], np.sqrt(sigma2[fn]))
    ends = np.minimum(starts + dur, duration)
    order = np.lexsort((starts, node))
    fn, node, starts, ends = fn[order], node[order], starts[order], ends[order]
    bounds = np.concatenate([[0], np.cumsum(counts.sum(1))])
    traces = []
    for i in range(b):
        lo, hi = bounds[i], bounds[i + 1]
        ids = np.full(k, -1, np.int32)
        st = np.zeros(k, np.float32)
        en = np.zeros(k, np.float32)
        ids[: hi - lo] = fn[lo:hi]
        st[: hi - lo] = starts[lo:hi]
        en[: hi - lo] = ends[lo:hi]
        traces.append((ids, st, en))
    return traces, k
