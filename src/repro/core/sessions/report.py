"""Shared report finalization: steps 5-6 of the pipeline, once for all paths.

Per-node, batched-segment, and streaming profiling all end in the same
place: a ``FootprintReport`` assembled by ``_finalize_report`` from the
(estimates, trajectory, contributions) tuple their engines produced.
Keeping the finalizer (and the small per-trace statistics helpers next to
it) in the session layer — below ``core.profiler`` — lets every session
build reports without importing the orchestration layer above it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.disaggregation import MATMUL_PRECISION
from repro.core.footprints import FootprintSpectrum, assemble_spectrum
from repro.core.metrics import total_power_error

Array = jax.Array


class FootprintReport(NamedTuple):
    """One node's profiling outcome for an accounting segment (§4.4).

    Produced by every profiling path through the shared
    ``_finalize_report``; ``total_error`` is the internal-validity metric
    (reconstruction vs the synchronized signal), not a ground-truth error.
    """

    spectrum: FootprintSpectrum      # per-function energy spectrum (M,)
    x_power: Array                   # (M,) final per-function power (watts)
    x_trajectory: Array              # (S, M) Kalman trajectory
    x_cp: Array                      # scalar: control-plane power estimate
    mean_latency: Array              # (M,)
    invocations: Array               # (M,)
    skew_windows: float              # estimated sensor skew (windows)
    total_error: float               # internal-validity Total-Error
    cp_energy: float                 # control-plane energy over segment (J)
    idle_energy: float               # idle energy over segment (J)


def _finalize_report(
    *,
    x_fns: Array,          # (M,) final per-function power (combined-adjusted)
    x_cp: Array,           # scalar: control-plane power estimate
    x0: Array,             # (M_aug,) initial whole-trace estimate
    traj: Array,           # (S', M_aug) Kalman trajectory (x0[None] if S == 0)
    c_aug: Array,          # (N, M_aug) contribution matrix incl. principals
    c_steps: Array | None,  # (S, n_w, M_aug) step-grouped contributions
    w_sys: Array,          # (N,) synchronized raw system signal
    offset,                # scalar or (N,): reconstruction offset (idle/combined)
    init_n: int,
    s: int,
    step_windows: int,
    counts: Array,         # (M,) invocation counts over the segment
    mean_lat: Array,       # (M,) mean latency per function
    cp_col: Array | None,  # (N,) control-plane contribution column
    idle_watts: float,
    duration: float,
    skew: float,
    idle_extra_watts: float = 0.0,
) -> FootprintReport:
    """Profiler steps 5-6, shared by ALL disaggregation paths (§4.3-§4.4).

    Per-node, batched-segment, and streaming profiling produce the same
    (x_fns, trajectory, contribution) tuple through different engines; this
    single finalizer turns it into a ``FootprintReport`` — control-plane and
    idle energy, the Shapley footprint spectrum, the time-varying W_hat
    reconstruction, and the internal-validity Total-Error — so the three
    paths cannot drift (the ROADMAP's shared-finalization item; equivalence
    is pinned in tests/test_streaming_engine.py).

    The reconstruction uses the *time-varying* estimates (X_0 over the init
    window, then each Kalman step's X) and scores against the synchronized
    raw signal — comparing against the raw lagged series would charge the
    sensor's reporting delay to the model.

    ``idle_extra_watts`` routes additional always-on power into the idle
    energy term: combined mode (§4.3) passes the counter model's
    *un-attributed* static bias here (non-zero only on idle intervals, see
    ``cpu_model.predict_function_power_split``) so no measured chip energy
    silently vanishes from the accounting.
    """
    cp_energy = float(x_cp * jnp.sum(cp_col)) if cp_col is not None else 0.0
    idle_energy = (idle_watts + float(idle_extra_watts)) * duration
    spectrum = assemble_spectrum(
        x_fns, mean_lat, counts, jnp.asarray(cp_energy), jnp.asarray(idle_energy)
    )

    w_hat_init = jnp.matmul(c_aug[:init_n], x0, precision=MATMUL_PRECISION) + (
        offset[:init_n] if hasattr(offset, "shape") else offset
    )
    parts = [w_hat_init]
    if s > 0:
        per_step = jnp.einsum(
            "snm,sm->sn", c_steps, traj, precision=MATMUL_PRECISION
        ).reshape(-1)
        off_steps = (
            offset[init_n : init_n + s * step_windows]
            if hasattr(offset, "shape")
            else offset
        )
        parts.append(per_step + off_steps)
    w_hat = jnp.concatenate([jnp.atleast_1d(p) for p in parts])
    n_hat = w_hat.shape[0]
    terr = float(total_power_error(w_sys[:n_hat], w_hat))
    return FootprintReport(
        spectrum=spectrum,
        x_power=x_fns,
        x_trajectory=traj,
        x_cp=x_cp,
        mean_latency=mean_lat,
        invocations=counts,
        skew_windows=skew,
        total_error=terr,
        cp_energy=cp_energy,
        idle_energy=idle_energy,
    )


def _per_fn_latency_stats(fn_id, start, end, num_fns):
    """(counts, mean, lat_sum, lat_sumsq) per function over a whole trace."""
    dur = jnp.maximum(end - start, 0.0)
    valid = fn_id >= 0
    seg = jnp.where(valid, fn_id, num_fns)
    counts = jax.ops.segment_sum(valid.astype(jnp.float32), seg, num_segments=num_fns + 1)[
        :num_fns
    ]
    lat_sum = jax.ops.segment_sum(jnp.where(valid, dur, 0.0), seg, num_segments=num_fns + 1)[
        :num_fns
    ]
    lat_sumsq = jax.ops.segment_sum(
        jnp.where(valid, dur * dur, 0.0), seg, num_segments=num_fns + 1
    )[:num_fns]
    mean = lat_sum / jnp.maximum(counts, 1.0)
    return counts, mean, lat_sum, lat_sumsq


def _node_durations(duration, b: int) -> tuple[list[float], bool]:
    """Normalize a ``duration`` argument to per-node seconds.

    Accepts one float (the homogeneous fleet) or a length-B sequence (the
    ragged fleet — nodes covering different segment spans).  Returns the
    per-node list plus whether the fleet is actually ragged.
    """
    if np.ndim(duration) == 0:
        return [float(duration)] * b, False
    durations = [float(d) for d in duration]
    if len(durations) != b:
        raise ValueError(
            f"duration sequence has {len(durations)} entries for {b} node(s)"
        )
    return durations, len(set(durations)) > 1


def finalize_streaming_session(sess) -> list[FootprintReport]:
    """Close a ``StreamingFleetSession`` segment and build per-node reports.

    The completion path of the streaming session, kept next to
    ``_finalize_report`` (the steps 5-6 it drives).  Requires the full
    ``n_windows`` segment to have been pushed (the sync lookahead then
    unlocks every remaining tick).  On a ragged fleet each node finalizes
    against its own step count S_i and duration; a node with zero post-init
    steps reports its X_0 trajectory, exactly as the per-node path would.
    """
    if sess._n_raw < sess.n_windows:
        raise ValueError(
            f"finalize needs the full segment: got {sess._n_raw} of "
            f"{sess.n_windows} windows"
        )
    sess._advance()
    assert sess._next_tick == sess.n_used and len(sess._traj) == sess.s
    cfg = sess.cfg
    traj = jnp.moveaxis(jnp.stack(sess._traj), 0, 1)           # (B, S, M_aug)
    if sess._slot_pool is not None:
        # Slot mode: gather each node's final Kalman row from its pool
        # slot (retired nodes' rows are frozen, never reused within a
        # profiling session — admissions all happen at bootstrap).
        x_final = jnp.asarray(
            np.asarray(jax.device_get(sess._slot_pool.state.kalman.x))[
                sess._slot_rows
            ]
        )
    else:
        x_final = sess._state.kalman.x
    w_sys = jnp.asarray(np.stack(sess._w_sync, axis=1))        # (B, n_used)
    c_aug = sess._c_aug_block(0, sess.n_windows)
    cp_col = (
        jnp.asarray(np.stack(sess._cp_col, axis=1)) if sess.has_cp else None
    )
    idle = np.asarray(sess.idle)
    chip = (
        np.stack(sess._raw_chip, axis=1) if sess._raw_chip else None
    )                                                          # (B, n_raw)
    reports = []
    for i in range(sess.b):
        s_i = sess.s_nodes[i]
        n_used_i = sess.init_n + s_i * cfg.step_windows
        if sess.combined:
            x_fns_i = x_final[i, : sess.num_fns] + sess.x_cpu[i]
            n_i = int(sess._n_nodes[i])
            offset_i = (
                jnp.asarray(chip[i, :n_i]) + float(sess._rest_idle_nodes[i])
            )
            idle_extra_i = float(sess._x_cpu_resid[i])
        else:
            x_fns_i = x_final[i, : sess.num_fns]
            offset_i = float(idle[i])
            idle_extra_i = 0.0
        reports.append(
            _finalize_report(
                x_fns=x_fns_i,
                x_cp=x_final[i, sess.num_fns] if sess.has_cp else jnp.asarray(0.0),
                x0=sess.x0[i],
                traj=traj[i, :s_i] if s_i > 0 else sess.x0[i][None],
                c_aug=c_aug[i],
                c_steps=(
                    c_aug[i, sess.init_n : n_used_i].reshape(
                        s_i, cfg.step_windows, sess.m_aug
                    )
                    if s_i > 0
                    else None
                ),
                w_sys=w_sys[i],
                offset=offset_i,
                init_n=sess.init_n, s=s_i, step_windows=cfg.step_windows,
                counts=sess.counts[i], mean_lat=sess.mean_latency[i],
                cp_col=cp_col[i] if sess.has_cp else None,
                idle_watts=float(idle[i]),
                duration=sess.durations[i],
                skew=float(sess.skews[i]),
                idle_extra_watts=idle_extra_i,
            )
        )
    return reports
