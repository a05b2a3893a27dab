"""Plain reference of the streaming energy controller's per-tick output.

A straightforward numpy implementation of what FaasMeter's live tick path
must produce (paper §4.1-§4.3, §5), written from the paper's equations and
the configuration file alone: it imports nothing of the program and takes
none of its intermediate results.  Its inputs are the invocation traces the
benchmark generated and the raw telemetry windows exactly as the benchmark's
pacer handed them to the controller.

Per node, in combined mode (§4.3) with the control-plane principal (§4.1):

1. Contribution matrix C (N windows x M functions): seconds each function
   ran in each window, plus the control-plane column
   ``clip(cp_cpu / sys_cpu, 0, 1) * delta`` (Eq. 2).
2. Sensor skew (Eq. 5) of system power against chip power over the init
   block: chi^2 at every integer shift in ``[-max_shift, max_shift]``,
   parabolic refinement around the minimum; windows are read at
   ``t + skew`` by linear interpolation with the node's edges held.
3. Rest target ``max(w_sync - w_chip - rest_idle, 0)`` with
   ``rest_idle = max(idle - min(chip over the init block), 0)``.
4. X_0: FISTA non-negative least squares on the init block's normal
   equations (``C^T C + lambda I``), step ``1 / trace``, a fixed iteration
   count.
5. Kalman steps of ``step_windows`` ticks (Fig. 4): fresh NNLS estimate U,
   innovation ``Z`` = mean residual of the previous estimate over the step's
   active windows, process noise from the running latency variance, the
   gain ``K = P a / (a P a + r)``, masked update (inactive functions keep
   their estimate, new ones take U).
6. Every tick's attribution under the freshest estimate: measured target
   split over the functions in proportion to ``C[t] * X``, the remainder
   (ticks where no function ran) unattributed.

``precision="reference"`` computes in float64.  ``precision="high"`` is the
control: float32, with every contraction in three bfloat16 passes (the
split TPUs use for ``Precision.HIGH``), one step below the ``highest`` that
the configuration states.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np


@dataclasses.dataclass(frozen=True)
class Profiler:
    """Profiler settings, read from a configuration file's ``profiler``."""

    delta: float
    init_windows: int
    step_windows: int
    sync_max_shift: int
    alpha: float
    beta: float
    gamma: float
    r_scale: float
    ridge_lambda: float
    nnls_iters: int
    init_iters: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Profiler":
        return cls(**{f.name: cfg["profiler"][f.name] for f in dataclasses.fields(cls)})


class _Arith:
    """Float type and contraction of one precision."""

    def __init__(self, precision: str):
        if precision == "reference":
            self.dtype = np.float64
            self.einsum = lambda sub, a, b: np.einsum(sub, a, b)
        elif precision == "high":
            self.dtype = np.float32
            self.einsum = _einsum_bf16x3
        else:
            raise ValueError(f"unknown precision {precision!r}")

    def __call__(self, x) -> np.ndarray:
        return np.asarray(x, self.dtype)


def _split_bf16(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def _einsum_bf16x3(sub: str, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """float32 contraction as three bfloat16 products, float32 accumulate."""
    ah, al = _split_bf16(np.asarray(a, np.float32))
    bh, bl = _split_bf16(np.asarray(b, np.float32))
    return (
        np.einsum(sub, ah, bh) + np.einsum(sub, ah, bl) + np.einsum(sub, al, bh)
    ).astype(np.float32)


def contribution(fn_id, start, end, num_fns: int, num_windows: int, delta: float):
    """(N, M) seconds of runtime per window and function, exact in float64.

    The running time of one invocation up to time t is
    ``ramp(t - start) - ramp(t - end)``; summed over invocations at the
    window edges, its differences are the windows' contributions.
    """
    ok = fn_id >= 0
    fn = fn_id[ok].astype(np.int64)
    s = start[ok].astype(np.float64)
    e = np.maximum(end[ok].astype(np.float64), s)
    edges = delta * np.arange(num_windows + 1, dtype=np.float64)
    # Sum over events of sign * ramp(edge - t) = edge * sum(sign) - sum(sign * t)
    # over the events before the edge.
    n_sign = np.zeros((num_windows + 2, num_fns))
    n_time = np.zeros((num_windows + 2, num_fns))
    for t, sign in ((s, 1.0), (e, -1.0)):
        first = np.clip(np.floor(t / delta).astype(np.int64) + 1, 0, num_windows + 1)
        np.add.at(n_sign, (first, fn), sign)
        np.add.at(n_time, (first, fn), sign * t)
    cum = edges[:, None] * np.cumsum(n_sign, 0)[: num_windows + 1] - np.cumsum(n_time, 0)[
        : num_windows + 1
    ]
    return np.diff(cum, axis=0)


def window_stats(fn_id, start, end, num_fns: int, init_n: int, n_post: int, delta: float):
    """Per post-init window: invocations starting in it and their latency sums."""
    ok = fn_id >= 0
    # Window index from the float32 start time, as the trace records it.
    k = np.floor((start - np.float32(init_n * delta)) / np.float32(delta)).astype(np.int64)
    ok &= (k >= 0) & (k < n_post)
    dur = np.maximum(end.astype(np.float64) - start.astype(np.float64), 0.0)[ok]
    idx = (k[ok], fn_id[ok].astype(np.int64))
    out = np.zeros((3, n_post, num_fns))
    for row, vals in enumerate((np.ones_like(dur), dur, dur * dur)):
        np.add.at(out[row], idx, vals)
    return out


def estimate_skew(w: np.ndarray, r: np.ndarray, max_shift: int) -> np.ndarray:
    """(B,) lag of system power ``w`` behind chip power ``r``, both (B, n)."""
    wn = w / np.maximum(w.mean(-1, keepdims=True), 1e-12)
    rn = r / np.maximum(r.mean(-1, keepdims=True), 1e-12)
    n = w.shape[-1]
    chi = []
    for s in range(-max_shift, max_shift + 1):
        idx = np.arange(n) + s
        valid = (idx >= 0) & (idx < n)
        d2 = (wn[:, np.clip(idx, 0, n - 1)] - rn) ** 2 * valid
        chi.append(d2.sum(-1) / max(valid.sum(), 1))
    chi = np.stack(chi, -1)
    i = np.argmin(chi, -1)
    rows = np.arange(chi.shape[0])
    y0 = chi[rows, np.clip(i - 1, 0, 2 * max_shift)]
    y1 = chi[rows, i]
    y2 = chi[rows, np.clip(i + 1, 0, 2 * max_shift)]
    denom = y0 - 2.0 * y1 + y2
    safe = np.where(np.abs(denom) > 1e-12, denom, 1.0)
    frac = np.clip(np.where(np.abs(denom) > 1e-12, 0.5 * (y0 - y2) / safe, 0.0), -0.5, 0.5)
    interior = (i > 0) & (i < 2 * max_shift)
    return (i - max_shift) + np.where(interior, frac, 0.0)


def synced(raw: np.ndarray, skew: np.ndarray, n_nodes: int, ticks: np.ndarray):
    """(len(ticks), B) system power read at ``t + skew``, edges held.

    A read past the windows that have arrived takes the last one: with an
    integral skew its weight is zero.
    """
    pos = np.clip(ticks[:, None] + skew[None, :], 0.0, n_nodes - 1.0)
    lo = np.floor(pos).astype(np.int64)
    hi = np.minimum(np.minimum(lo + 1, n_nodes - 1), raw.shape[0] - 1)
    frac = pos - lo
    cols = np.arange(raw.shape[1])[None, :]
    return raw[lo, cols] * (1.0 - frac) + raw[hi, cols] * frac


def nnls(ar: _Arith, gram: np.ndarray, rhs: np.ndarray, iters: int):
    """FISTA for min_{x >= 0} 0.5 x^T G x - r^T x, batched over rows.

    Returns the iterate and, for its last step before the projection onto
    ``x >= 0``, the step's value and the size of the terms it sums (the
    scale of its rounding).
    """
    step = 1.0 / np.maximum(np.trace(gram, axis1=-2, axis2=-1), 1e-12)
    step = ar(step)[..., None]
    x = np.zeros_like(rhs)
    y = x
    t = 1.0
    for _ in range(iters):
        z = y - step * (ar.einsum("bij,bj->bi", gram, y) - rhs)
        x_new = np.maximum(z, 0.0)
        t_new = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * t * t))
        y_last, y = y, x_new + ar((t - 1.0) / t_new) * (x_new - x)
        x, t = x_new, t_new
    scale = np.abs(y_last) + step * (
        np.einsum("bij,bj->bi", np.abs(gram), np.abs(y_last)) + np.abs(rhs)
    )
    return x, z, scale


@dataclasses.dataclass
class TickOutput:
    """Reference per-tick attribution for ticks ``t0 .. t0 + T - 1``."""

    t0: int
    x: np.ndarray             # (T, B, M_aug) estimate in force at the tick
    tick_power: np.ndarray    # (T, B, M_aug) attributed watts
    unattributed: np.ndarray  # (T, B) watts no function ran to take
    target: np.ndarray        # (T, B) rest target the split conserves
    ambiguous: np.ndarray     # (B,) nodes whose X_0 sits on x = 0 within rounding


def reference_ticks(
    prof: Profiler,
    traces: list[tuple[np.ndarray, np.ndarray, np.ndarray]],
    num_fns: int,
    duration: float,
    idle_w,
    raw: dict[str, np.ndarray],
    last_tick: int,
    precision: str = "reference",
) -> TickOutput:
    """Reference attribution of ticks ``init_windows .. last_tick`` for every node.

    Args:
      prof: profiler settings.
      traces: per-node ``(fn_id, start, end)`` as given to the controller.
      num_fns: functions M (the principal column makes M + 1).
      duration: segment seconds, the same for every node.
      idle_w: the platform's idle power (W), one number or one per node.
      raw: the telemetry windows handed over, each (n_seen, B):
        ``w_sys``, ``w_chip``, ``cp_frac``, ``sys_frac``.
      last_tick: the last tick to attribute (its sync reads must be among
        the windows in ``raw``).
      precision: ``"reference"`` or ``"high"`` (the control).
    """
    ar = _Arith(precision)
    d = prof.delta
    n = int(round(duration / d))
    init_n = min(prof.init_windows, n)
    n_w = prof.step_windows
    b = len(traces)
    m_aug = num_fns + 1
    n_ticks = last_tick - init_n + 1
    if n_ticks < 1:
        raise ValueError("no tick after the init block to attribute")
    steps = -(-n_ticks // n_w)
    n_post = steps * n_w
    t_hi = init_n + n_post

    c = np.zeros((b, t_hi, m_aug))
    stats = np.zeros((3, b, n_post, m_aug))
    for i, (fn_id, start, end) in enumerate(traces):
        c[i, :, :num_fns] = contribution(fn_id, start, end, num_fns, n, d)[:t_hi]
        stats[:, i, :, :num_fns] = window_stats(fn_id, start, end, num_fns, init_n, n_post, d)
    seen = raw["w_sys"].shape[0]
    cp = raw["cp_frac"].astype(np.float64)
    sf = raw["sys_frac"].astype(np.float64)
    cp_col = np.clip(cp / np.maximum(sf, 1e-6), 0.0, 1.0) * d          # (seen, B)
    c[:, : min(seen, t_hi), num_fns] = cp_col[:t_hi].T
    # The principal's one pseudo-invocation per step, on the step's first tick.
    stats[0, :, ::n_w, num_fns] = 1.0
    c = ar(c)
    stats = ar(stats)

    w_sys = raw["w_sys"].astype(np.float64)
    chip = raw["w_chip"].astype(np.float64)
    skew = estimate_skew(w_sys[:init_n].T, chip[:init_n].T, prof.sync_max_shift)
    ticks = np.arange(0, last_tick + 1)
    w_sync = synced(w_sys, skew, n, ticks)                              # (T, B)
    rest_idle = np.maximum(np.asarray(idle_w, np.float64) - chip[:init_n].min(0), 0.0)
    target = ar(np.maximum(w_sync - chip[: last_tick + 1] - rest_idle, 0.0))  # (T, B)

    lam = ar(prof.ridge_lambda * np.eye(m_aug))
    ci = c[:, :init_n]
    x, z, scale = nnls(
        ar,
        ar.einsum("bnm,bnk->bmk", ci, ci) + lam,
        ar.einsum("bnm,bn->bm", ci, target[:init_n].T),
        prof.init_iters,
    )
    p = ar(np.ones((b, m_aug)))
    seen_fn = x > 0
    # A function that ran in the init block and whose X_0 ends on the
    # boundary x = 0 to within rounding is "seen" or not by rounding alone,
    # and the Kalman step treats the two cases differently (a new function
    # takes the fresh estimate).  Nodes with such a function are marked.
    ran = ci.sum(1) > 0
    ambiguous = (ran & (np.abs(z) <= 1e-3 * scale)).any(-1)
    lat_mean = ar(np.zeros((b, m_aug)))
    lat_m2 = ar(np.zeros((b, m_aug)))
    lat_n = ar(np.zeros((b, m_aug)))
    r = prof.r_scale / d

    out_x = np.zeros((n_ticks, b, m_aug), ar.dtype)
    for k in range(steps):
        lo, hi = init_n + k * n_w, init_n + (k + 1) * n_w
        take = min(hi, last_tick + 1) - lo
        out_x[k * n_w : k * n_w + take] = x                             # mid-step ticks
        if hi > last_tick + 1:
            break
        cs = c[:, lo:hi]                                                # (B, n_w, M)
        ws = target[lo:hi].T                                            # (B, n_w)
        wa = ar(cs.sum(-1) > 0)
        u, _, _ = nnls(
            ar, ar.einsum("bnm,bnk->bmk", cs, cs) + lam, ar.einsum("bnm,bn->bm", cs, ws),
            prof.nnls_iters,
        )
        s_c = ar.einsum("bnm,bn->bm", cs, wa)
        z = ((ws * wa).sum(-1) - ar.einsum("bm,bm->b", s_c, x)) / np.maximum(wa.sum(-1), 1.0)
        a, ls, lq = (stats[q, :, k * n_w : (k + 1) * n_w].sum(1) for q in range(3))
        # Welford merge of the step's latency moments.
        n_new = lat_n + a
        bmean = ls / np.maximum(a, 1.0)
        dlt = bmean - lat_mean
        lat_mean = np.where(a > 0, lat_mean + dlt * a / np.maximum(n_new, 1.0), lat_mean)
        bm2 = np.maximum(lq - a * bmean**2, 0.0)
        lat_m2 = np.where(a > 0, lat_m2 + bm2 + dlt**2 * lat_n * a / np.maximum(n_new, 1.0), lat_m2)
        lat_n = n_new
        pk = ar(prof.alpha) * p + ar(prof.gamma) * (lat_m2 / np.maximum(n_new - 1.0, 1.0))
        gain = pk * a / ((a * pk * a).sum(-1, keepdims=True) + r)
        p_new = np.maximum((1.0 - gain * a) * pk, 0.0)
        upd = ar(prof.alpha) * x + ar(prof.beta) * u + gain * z[:, None]
        active = a > 0
        upd = np.where(active & ~seen_fn, u, upd)
        x = np.where(active, np.maximum(upd, 0.0), x)
        p = np.where(active, p_new, p)
        seen_fn = seen_fn | active
        out_x[(k + 1) * n_w - 1] = x                                    # boundary tick

    c_t = c[:, init_n : last_tick + 1].transpose(1, 0, 2)               # (T, B, M)
    w_t = target[init_n : last_tick + 1]
    raw_j = c_t * out_x
    pred = raw_j.sum(-1) / d
    has = pred > 1e-9
    scale = np.where(has, w_t / np.where(has, pred, 1.0), 0.0)
    return TickOutput(
        t0=init_n,
        x=out_x,
        tick_power=(raw_j / d) * scale[..., None],
        unattributed=np.where(has, 0.0, w_t),
        target=w_t,
        ambiguous=ambiguous,
    )


def report(out: TickOutput, traces, num_fns: int, step_windows: int) -> dict:
    """What a node's footprint report holds that the tick path decides.

    ``x_trajectory`` (B, S, M_aug): the estimate after each whole Kalman
    step, the principal's last; ``invocations`` (B, M): invocations of each
    function over the whole trace.
    """
    s = out.x.shape[0] // step_windows
    traj = out.x[step_windows - 1 : s * step_windows : step_windows].transpose(1, 0, 2)
    counts = np.stack([
        np.bincount(fn_id[fn_id >= 0].astype(np.int64), minlength=num_fns)[:num_fns]
        for fn_id, _, _ in traces
    ])
    return {"x_trajectory": traj, "invocations": counts.astype(np.float64)}
