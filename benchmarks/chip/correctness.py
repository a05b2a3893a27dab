"""The comparison that decides a run's ``correct``.

What the consumer received for every tick, and what ``profile_fleet``
returned where the run finalizes, is compared with the plain reference
(``reference.py``) run over the same inputs:

- ``ticks_missing``: ticks from the first after the init block to the last
  one received that did not arrive exactly once and in order.  Exact, so
  its limit is 0.
- ``x_gap``, ``split_gap``: the relative L2 distance, over the whole fleet,
  between the estimate in force at each tick (``x``), and the per-tick split
  of the measured power (``tick_power`` with ``unattributed`` as one more
  column), and the reference's, over the ticks of the first two Kalman
  steps: X_0 (sync, rest target, contribution rows, the init NNLS) and the
  first updates.
- ``window_x_gap``: the estimate in force at every tick after those two
  steps (the measured window and what follows it), as the mean over the
  nodes of each node's relative L2 distance.
- ``conservation_w``: the largest gap, over every node and every tick
  received, between what the program split (attributed plus unattributed)
  and the reference's rest target, in watts.  The configuration states its
  limit (``conservation_tolerance_w``).
- ``report_x_gap`` (runs that finalize): each node's Kalman trajectory in
  its footprint report against the reference's, mean over the nodes.
- ``report_invocations_missing`` (runs that finalize): node-function pairs
  whose invocation count in the report differs from the trace's.  Exact.

Why the window is judged on ``x`` and conservation but not on the split
function by function: the program's contribution rows come from float32
running times that lose digits as the segment's clock grows, so its split
drifts from the reference's by more, late in a segment, than the split of
the low-precision control does (PERF.md, "How correct is decided").

Nodes whose X_0 decides a function's "seen" flag by rounding alone (the
reference marks them ``ambiguous``) take part in every number up to the
first Kalman update and in ``conservation_w`` always; after the first update
they are left out of the gaps, and their own gap is printed.

Each limit lies between the largest reading of sound runs of the program
and the smallest reading of the control, per configuration, in
``limits/<config>.json``.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

EARLY_STEPS = 2
HERE = Path(__file__).resolve().parent


def load_limits(cfg: dict) -> dict:
    with open(HERE / "limits" / f"{cfg['name']}.json") as f:
        limits = dict(json.load(f)["limits"])
    limits["conservation_w"] = cfg["conservation_tolerance_w"]
    return limits


def _rel_l2(a: np.ndarray, r: np.ndarray, axis=None) -> np.ndarray:
    """||a - r|| / ||r|| over ``axis`` (all axes by default)."""
    num = np.sqrt(np.sum((a - r) ** 2, axis=axis))
    return num / np.maximum(np.sqrt(np.sum(r**2, axis=axis)), 1e-9)


def _masked_rel_l2(a: np.ndarray, r: np.ndarray, w: np.ndarray) -> float:
    """Fleet relative L2 over (T, B, ...) with per (tick, node) weights ``w``."""
    w = w.reshape(w.shape + (1,) * (a.ndim - 2))
    num = np.sqrt(np.sum(w * (a - r) ** 2))
    return float(num / max(np.sqrt(np.sum(w * r**2)), 1e-9))


def gaps(received: dict, ref, step_windows: int, reports: dict | None = None,
         ref_reports: dict | None = None) -> dict:
    """Compared numbers and unjudged diagnostics.

    ``received`` holds the consumer's record: ``t`` (T,) tick indices in
    arrival order and, stacked in that order, ``x`` (T, B, M_aug),
    ``tick_power`` (T, B, M_aug), ``unattributed`` (T, B).  ``ref`` is a
    ``reference.TickOutput`` from tick ``ref.t0``.  ``reports``, for a run
    that finalized, holds ``x_trajectory`` (B, S, M_aug) and
    ``invocations`` (B, M) from its footprint reports, and ``ref_reports``
    the same from ``reference.report``.  A number that cannot be read is
    None.
    """
    t = np.asarray(received["t"])
    expect = np.arange(ref.t0, ref.t0 + ref.x.shape[0])
    n = min(t.shape[0], expect.shape[0])
    missing = int(expect.shape[0] - np.sum(t[:n] == expect[:n])) + max(t.shape[0] - n, 0)
    out: dict = {"ticks_missing": float(missing)}
    names = ("x_gap", "split_gap", "window_x_gap", "conservation_w")
    if reports is not None:
        names += ("report_x_gap", "report_invocations_missing")
    if missing:
        # Misaligned ticks cannot be paired with the reference's.
        out.update(dict.fromkeys(names))
        return out
    b = ref.x.shape[1]
    keep = ~ref.ambiguous
    out["nodes_left_out"] = float(ref.ambiguous.sum())
    split = np.concatenate([received["tick_power"], received["unattributed"][..., None]], -1)
    split_ref = np.concatenate([ref.tick_power, ref.unattributed[..., None]], -1)
    out["conservation_w"] = float(np.max(np.abs(split.sum(-1) - ref.target)))

    # Every node up to the first update; after it, the nodes kept.
    n_early = min(EARLY_STEPS * step_windows, ref.x.shape[0])
    w = np.ones((n_early, b))
    w[step_windows - 1 :] = keep
    for name, got, want in (("x", received["x"], ref.x), ("split", split, split_ref)):
        out[f"{name}_gap"] = _masked_rel_l2(got[:n_early], want[:n_early], w)
    late = slice(n_early, None)
    if ref.x.shape[0] > n_early:
        per_node = _rel_l2(received["x"][late], ref.x[late], axis=(0, 2))
        out["window_x_gap"] = float(per_node[keep].mean()) if keep.any() else None
        if not keep.all():
            out["left_out_window_x_gap_max"] = float(per_node[~keep].max())
        out["window_split_gap"] = float(_rel_l2(split[late][:, keep], split_ref[late][:, keep]))
    else:
        out["window_x_gap"] = None
    if reports is not None:
        traj, traj_ref = reports["x_trajectory"], ref_reports["x_trajectory"]
        if traj.shape != traj_ref.shape:
            out["report_x_gap"] = None
        else:
            per_node = _rel_l2(traj, traj_ref, axis=(1, 2))
            out["report_x_gap"] = float(per_node[keep].mean()) if keep.any() else None
        out["report_invocations_missing"] = float(
            np.sum(reports["invocations"] != ref_reports["invocations"])
        )
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers with a limit.

    A number the run does not produce (the report's, where the run stops
    before finalizing) is not judged; one it produces as None fails.
    """
    checks = {}
    ok = True
    for name, limit in limits.items():
        if name not in numbers:
            continue
        value = numbers[name]
        checks[name] = {"value": value, "limit": limit}
        ok &= value is not None and value <= limit
    return ok, checks
