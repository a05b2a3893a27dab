"""Live model maintenance for streaming sessions (paper §4.3 / §5).

``RetrainMixin`` carries the continuous-retraining surface of
``StreamingFleetSession``: scoring each node's counter model at Kalman-step
boundaries, the fleet-batched sliding-window refit, and the periodic skew
re-estimate.  It is a mixin, not a base — the methods operate on the
session's own buffers (``_win_feats``, ``_raw_chip``, ``_models``, ...) and
exist in a separate module only so the hot dispatch/emit pipeline in
``streaming.py`` stays readable on its own.

Thread-safety (drained ingest): ``refit_counter_models`` and ``resync``
swap whole numpy/JAX references under CPython's atomic attribute store; a
drain-thread hook calling them races only on *when* the dispatching thread
observes the new model — bounded by the drain queue depth in ticks — never
on torn state.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import cpu_model as cpumod
from repro.core import sync as syncmod
from repro.core.sessions.combined import combined_chip_power


class RetrainMixin:
    """Continuous retraining + resync methods shared into the streaming session."""

    def _check_retrain(self, t: int) -> None:
        """Paper §4.3 continuous retraining, live: at the Kalman-step
        boundary closing at tick ``t``, score each node's counter model on
        the step's (window features, observed chip power) pairs — the
        per-tick counter feed — through ``cpu_model.model_error`` /
        ``retrain_flags`` (the one place the retraining criterion is
        defined).  Dead (ragged) nodes score only their real windows; a
        node with none stays un-flagged."""
        lo, hi = t - self.cfg.step_windows + 1, t + 1
        # On the default device, beside the counter models they score.
        feats = tracing.put(self._win_feats[:, lo:hi], "retrain.features", tick=t)
        chip = tracing.put(np.stack(self._raw_chip[lo:hi], axis=1), "retrain.chip", tick=t)
        live = tracing.put(
            np.arange(lo, hi)[None, :] < self._n_nodes[:, None], "retrain.live", tick=t
        )
        err = cpumod.model_error(self._models, feats, chip, mask=live)
        self.model_errors.append(tracing.pull(err, "retrain.error", tick=t))
        # Chipless nodes have no counter model to retrain: never flagged.
        self.retrain_needed = (
            tracing.pull(
                cpumod.retrain_flags(
                    self._models, feats, chip, self._retrain_cfg, mask=live
                ),
                "retrain.flags", tick=t,
            )
            & self._chip_mask
        )

    def refit_counter_models(
        self, flags, *, window_steps: int = 2, lam: float = 1e-4
    ) -> np.ndarray:
        """Re-fit flagged nodes' counter models on a sliding window, live.

        The paper's continuous-retraining loop (§4.3), closed: when
        ``retrain_needed`` fires at a Kalman-step boundary, the caller (the
        ``ControlLoop``, or any ``on_tick`` hook) invokes this with the
        flags.  All flagged nodes are re-fit in **one** fleet-batched
        ``cpu_model.fit_ridge`` over the trailing ``window_steps`` Kalman
        steps of (window features, observed chip power) pairs — dead ragged
        windows mask-weighted out — and swapped in row-wise
        (``cpu_model.merge_models``).  Model parameters are data to every
        jitted consumer, so the swap causes **no retrace**; the live chip
        split (``x_cpu``/``_x_cpu_resid``) is recomputed under the updated
        models so subsequent ticks and the finalized reports see the new
        attribution.  Returns the (B,) bool mask of nodes actually re-fit
        (flags on nodes with zero live windows in range are dropped).
        """
        if not self.combined or self._win_feats is None:
            raise ValueError(
                "refit_counter_models needs combined mode with "
                "window_features (see prepare_combined_fleet)"
            )
        flags = np.asarray(flags, bool).reshape(self.b) & self._chip_mask
        hi = min(self._next_tick, self._n_raw, self._win_feats.shape[1])
        lo = max(hi - window_steps * self.cfg.step_windows, 0)
        live = np.arange(lo, hi)[None, :] < self._n_nodes[:, None]
        flags = flags & live.any(axis=1)
        if not flags.any() or hi <= lo:
            return np.zeros(self.b, bool)
        feats = jnp.asarray(self._win_feats[:, lo:hi])
        chip = jnp.asarray(np.stack(self._raw_chip[lo:hi], axis=1))
        new = cpumod.fit_ridge(
            feats, chip, lam, mask=jnp.asarray(live, jnp.float32)
        )
        self._models = cpumod.merge_models(self._models, new, jnp.asarray(flags))
        self.x_cpu, self._x_cpu_resid = combined_chip_power(
            self._models, self._fnc, self._busy,
            jnp.asarray(self.durations, jnp.float32),
        )
        self._force_chipless_zero()
        self.retrain_needed = self.retrain_needed & ~flags
        self.refits.append((hi, flags))
        return flags

    def resync(self, window: int | None = None) -> np.ndarray:
        """Re-estimate per-node sensor skew over the trailing raw windows.

        The bootstrap estimates skew once on the init segment; clocks drift,
        so the control loop periodically re-estimates over the last
        ``window`` raw windows (default: the init-block length) on the live
        path.  Causality clamp: updated skews are clipped to the bootstrap
        lookahead, so every already-buffered tick still has the raw windows
        its interpolation needs — a drift estimate *larger* than the
        initial lookahead takes effect only up to the buffered horizon
        (documented bound, not acausal peeking).  Appends to
        ``skew_history`` and returns the updated (B,) skews.
        """
        if self.skews is None:
            raise ValueError("resync needs the bootstrap skew estimate first")
        if not self.has_chip:
            return self.skews
        hi = self._n_raw
        lo = max(hi - (window if window is not None else self.init_n), 0)
        if hi - lo < 4:  # too few windows for a meaningful lag estimate
            return self.skews
        w_arr = self._raw_w[lo:hi]
        r_arr = np.stack(self._raw_chip[lo:hi])
        new = np.asarray(
            [
                float(
                    syncmod.estimate_skew(
                        jnp.asarray(w_arr[:, i]), jnp.asarray(r_arr[:, i]),
                        max_shift=self.cfg.sync_max_shift,
                    )
                )
                if self._chip_mask[i]
                else 0.0
                for i in range(self.b)
            ]
        )
        self.skews = np.minimum(new, float(self._lookahead))
        self.skew_history.append((hi, self.skews.copy()))
        return self.skews
