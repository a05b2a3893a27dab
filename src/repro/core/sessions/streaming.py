"""StreamingFleetSession: telemetry in window-by-window, state out live.

The paper's actual operating mode — footprints as a control-plane
operation (docs/streaming.md).  The session is structured as a small
pipeline over the streaming engine (``core.engine.streaming``):

  ingest stage   ``push_window``/``ingest`` buffer raw fleet telemetry
                 (optionally prefetched on a background thread);
  dispatch stage ``_process_tick`` builds each tick's host-side feed and
                 dispatches one async jitted ``fleet_step``, appending the
                 (device) trajectory in order;
  emit stage     ``_emit_tick`` materializes the tick's attribution to
                 numpy, runs the retrain check, and invokes ``on_tick`` —
                 inline by default, or on a background *drain thread*
                 (``ingest(drain=True)``) so admission, host ingest, and
                 the jitted step overlap fully.

Dispatch order is identical with and without the drain thread, so the
numerics are bitwise the same — the drain only moves host-side
materialization off the dispatching thread.

Each stage is a named host span (``repro.tracing``: ``faasmeter.ingest.push``,
``faasmeter.session.dispatch``, ``faasmeter.engine.fleet_step``,
``faasmeter.session.emit``, one ``faasmeter.pull`` per device→host
transfer), which a JAX profiler trace records (docs/streaming.md).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import contribution as contrib
from repro.core import cpu_model as cpumod
from repro.core import sync as syncmod
from repro.core.engine.plan import segment_plan
from repro.core.sessions.base import FleetSession
from repro.core.sessions.combined import (
    _as_fleet_counters,
    _as_fleet_model,
    combined_chip_power,
)
from repro.core.sessions.drain import StreamTick, _DrainWorker
from repro.core.sessions.report import (
    FootprintReport,
    _node_durations,
    _per_fn_latency_stats,
    finalize_streaming_session,
)
from repro.core.sessions.retrain import RetrainMixin
from repro.core.sessions.slots import SlotFleetSession

Array = jax.Array


class StreamingFleetSession(RetrainMixin, FleetSession):
    """Online fleet profiling: telemetry in window-by-window, state out live.

    The batched profiler (``fleet_profile_batched``) consumes a *finished*
    telemetry segment.  This session is the paper's actual operating mode —
    footprints as a control-plane operation: callers push one delta-window of
    fleet telemetry at a time (``push_window``); the session bootstraps on
    the init segment (skew estimate + X_0, §4.2/§5), then advances the
    streaming engine (``engine.fleet_step``) one jitted call per
    tick, invoking ``on_tick`` with live conserved attribution so pricing
    and capping can act *during* the segment.  ``finalize`` produces the
    same ``FootprintReport`` list as the segment paths, through the shared
    ``_finalize_report`` — equivalence is pinned in
    tests/test_streaming_engine.py.

    Synchronization contract: with a chip reference, per-node skew is
    estimated once over the init segment (the batch profiler estimates over
    the full segment — a documented difference) and applied causally: tick
    ``t`` is emitted once raw window ``t + ceil(max(skew, 0))`` has arrived,
    so a positive sensor lag shows up as a small, bounded reporting delay
    instead of acausal peeking.  Tail windows are flushed with the batch
    path's edge clamp at ``finalize``.

    Restrictions (same fleet homogeneity as ``fleet_profile_batched``):
    default NNLS/no_idle disaggregation, equal num_fns across nodes, every
    node covering the common init window, and at least one node with a
    full Kalman step after it.  Durations may differ per node (a *ragged*
    fleet): pass a sequence — nodes whose stream ends mid-segment simply
    stop feeding the engine (``FleetStep.valid`` masks them out, so their
    Kalman state freezes while the live nodes keep ticking) and finalize
    against their own window count.

    Combined mode (§4.3): with ``mode="combined"`` the session disaggregates
    only the chip-subtracted 'rest' power — the per-tick target becomes
    ``max(w_sync - chip - rest_idle, 0)`` through the same engine helper as
    the segment paths, with the rest-side idle estimated over the init
    block (causal).  The chip side comes from the per-node counter models
    (``fn_counters`` + ``counter_model``; ``x_cpu`` is exposed for live
    consumers and added into the finalized footprints).  When
    ``window_features`` is given, the paper's continuous-retraining loop
    runs live: each pushed chip window is paired with that tick's counter
    features, and at every completed Kalman step the per-node model error
    over the step is appended to ``model_errors`` with ``retrain_needed``
    re-flagged (threshold ``cpu_model.CpuModelConfig.retrain_threshold``).

    Drained ingest (``ingest(drain=True)``): hooks and retrain checks run
    on a background drain thread while this (dispatching) thread moves on
    to the next tick.  Hooks that mutate session state (``resync``,
    ``refit_counter_models``) still work — their updates are single
    reference swaps the dispatch thread picks up with bounded staleness
    (at most the drain queue depth in ticks).
    """

    def __init__(
        self,
        profiler,
        traces: list[tuple[Array, Array, Array]],
        *,
        num_fns: int,
        duration: float | Sequence[float],
        idle_watts,
        has_chip,
        has_cp: bool,
        on_tick=None,
        on_bootstrap=None,
        mesh=None,
        slots: int | None = None,
        fn_counters=None,
        counter_model=None,
        window_features=None,
        retrain_config: cpumod.CpuModelConfig = cpumod.CpuModelConfig(),
    ):
        """Args:
          profiler: configured ``FaasMeterProfiler`` (pure or combined mode).
          traces: per-node (fn_id, start, end) invocation arrays.
          num_fns: number of unique functions M.
          duration: segment length in seconds — one float, or a per-node
            sequence for a ragged fleet (every node must still cover the
            N_init window; ``push_window`` spans the longest node, and
            entries for already-ended nodes are ignored).
          idle_watts: (B,) static idle power per node.
          has_chip: whether ``push_window`` will carry a chip reference
            (enables skew estimation) — one bool, or a per-node sequence
            for a heterogeneous fleet (chipless nodes' chip rows are
            zeroed on ingest; their skew is 0 and their combined target
            degenerates to pure mode).
          has_cp: whether ``push_window`` will carry control-plane/system
            CPU fractions (appends the shared principal column, §4.1).
          on_tick: ``callable(StreamTick)`` invoked per engine tick.
          on_bootstrap: ``callable(session)`` invoked once after X_0.
          mesh: optional ``distributed.sharding.FleetMesh``; the engine
            state lives sharded over the node axis and every ``fleet_step``
            runs under ``shard_map`` (B must tile the mesh evenly — the
            slot capacity instead when ``slots`` is set).
          slots: optional slot-pool capacity >= B; routes the engine
            through a ``SlotFleetSession`` (nodes admitted at bootstrap,
            ragged nodes released when their stream ends, spare slots free
            — the serving mode, docs/serving.md).
          fn_counters: (B, M, F) normalized per-function counters (combined
            mode; see ``prepare_combined_fleet``).
          counter_model: fleet-batched / per-node-list / shared
            ``LinearPowerModel`` (combined mode).
          window_features: optional (B, N, F) per-window counter features —
            enables live ``needs_retrain`` checks at step boundaries.
          retrain_config: thresholds for those checks.
        """
        cfg = profiler.config
        if cfg.mode not in ("pure", "combined"):
            raise ValueError(f"unknown profiler mode {cfg.mode!r}")
        if not cfg.disagg.nonneg or cfg.disagg.mode != "no_idle":
            raise ValueError(
                "StreamingFleetSession supports the default NNLS/no_idle "
                "disaggregation config only"
            )
        super().__init__(
            config=None,  # resolved below once the engine config is built
            mesh=mesh,
        )
        eng = self.eng
        self.profiler = profiler
        self.cfg = cfg
        self.num_fns = num_fns
        self.b = len(traces)
        self.durations, self._ragged = _node_durations(duration, self.b)
        self.duration = max(self.durations)
        if np.ndim(has_chip) == 0:
            self._chip_mask = np.full(self.b, bool(has_chip))
        else:
            self._chip_mask = np.asarray(has_chip, bool).reshape(-1)
            if self._chip_mask.shape[0] != self.b:
                raise ValueError(
                    f"has_chip sequence has {self._chip_mask.shape[0]} "
                    f"entries for {self.b} node(s)"
                )
        # Chipless rows are forced to exactly 0.0 on ingest: combined
        # targets then degenerate to pure mode per node, with no branch.
        self._chip_zero = self._chip_mask.astype(np.float32)
        self.has_chip = bool(self._chip_mask.any())
        self.combined = cfg.mode == "combined"
        if self.combined:
            if not self.has_chip:
                raise ValueError(
                    "combined mode needs a chip reference on at least one "
                    "node (has_chip)"
                )
            if fn_counters is None or counter_model is None:
                raise ValueError(
                    "combined mode needs fn_counters and counter_model "
                    "(see prepare_combined_fleet)"
                )
        self.has_cp = has_cp
        self.on_tick = on_tick
        self.on_bootstrap = on_bootstrap
        self._slots_cap = None if slots is None else int(slots)
        if self._slots_cap is not None and self._slots_cap < self.b:
            raise ValueError(
                f"slots={slots} is smaller than the fleet (B={self.b})"
            )
        self._slot_pool: "SlotFleetSession | None" = None
        self._slot_rows: np.ndarray | None = None  # node i -> its pool slot
        if mesh is not None:
            mesh.validate(self.b if self._slots_cap is None else self._slots_cap)

        plans = [segment_plan(cfg, d) for d in self.durations]
        self.s_nodes = [p[2] for p in plans]
        self.n_windows = max(p[0] for p in plans)
        self.init_n = plans[0][1]
        self.s = max(self.s_nodes)
        self.n_used = self.init_n + self.s * cfg.step_windows
        if any(p[1] != self.init_n for p in plans):
            raise ValueError(
                "ragged fleet: every node must cover the common N_init "
                f"window ({cfg.init_windows} windows); got per-node init "
                f"blocks {[p[1] for p in plans]} (use the per-node path)"
            )
        if self.s == 0:
            raise ValueError(
                "segment too short for a Kalman step; use the per-node path"
            )
        # Per-node engine span: the last tick node i really feeds.  Its
        # sub-step tail (and everything after its stream ends) is masked
        # out of the engine, mirroring the batched path's per-node S_i.
        self._n_used_nodes = np.asarray(
            [self.init_n + s_i * cfg.step_windows for s_i in self.s_nodes]
        )
        # Per-node real window counts: the sync edge clamp must stop at
        # each node's OWN last real window (matching the batch path's
        # apply_shift clamp), never read into another node's span.
        self._n_nodes = np.asarray([p[0] for p in plans], np.float64)
        self.m_aug = num_fns + (1 if has_cp else 0)
        self._idle_host = np.asarray(idle_watts, np.float32)
        self.idle = jnp.asarray(self._idle_host)
        self.init_seconds = self.init_n * cfg.delta

        # Static per-node precomputation (the trace is known; telemetry is
        # what streams): contribution rows and per-window invocation stats.
        n_post = self.s * cfg.step_windows
        c_nodes, a_nodes, ls_nodes, lq_nodes = [], [], [], []
        counts_nodes, lat_nodes, init_a = [], [], []
        for fn_id, start, end in traces:
            c_nodes.append(
                contrib.contribution_matrix(
                    fn_id, start, end, num_fns=num_fns,
                    num_windows=self.n_windows, delta=cfg.delta,
                )
            )
            a_w, ls_w, lq_w = profiler._per_step_stats(
                fn_id, start, end, num_fns, num_fns, self.init_n, n_post,
                None, step_windows=1,
            )
            a_nodes.append(a_w)
            ls_nodes.append(ls_w)
            lq_nodes.append(lq_w)
            counts, mean_lat, _, _ = _per_fn_latency_stats(fn_id, start, end, num_fns)
            counts_nodes.append(counts)
            lat_nodes.append(mean_lat)
            valid = (fn_id >= 0) & (start >= 0) & (start < self.init_seconds)
            seg = jnp.where(valid, jnp.clip(fn_id, 0, num_fns - 1), num_fns)
            a0 = jax.ops.segment_sum(
                valid.astype(jnp.float32), seg, num_segments=num_fns + 1
            )[:num_fns]
            if has_cp:
                a0 = jnp.concatenate([a0, jnp.ones((1,))])
            init_a.append(a0)
        self._c_fns = jnp.stack(c_nodes)         # (B, N, M)
        # Host copy for the tick path: each tick's row is host data the
        # engine step and the ``on_tick`` consumer share, with no eager
        # device slice and no pull back.
        self._c_host = np.asarray(self._c_fns)
        self._a_win = np.stack([np.asarray(a) for a in a_nodes])    # (B, n_post, M)
        self._ls_win = np.stack([np.asarray(a) for a in ls_nodes])
        self._lq_win = np.stack([np.asarray(a) for a in lq_nodes])
        self.counts = jnp.stack(counts_nodes)
        self.mean_latency = jnp.stack(lat_nodes)
        self.init_invocations = jnp.stack(init_a)  # (B, M_aug)

        self.config = self._engine_cfg = eng.EngineConfig(
            kalman=cfg.kalman, delta=cfg.delta,
            init_iters=cfg.disagg.nnls_iters,
            init_ridge_lambda=cfg.disagg.ridge_lambda,
        )

        # Combined mode (§4.3): the chip-side split is static per segment
        # (the trace — hence busy seconds and counters — is known up front;
        # only the power telemetry streams), so X_CPU is computed once here
        # and exposed for live consumers (the control plane adds it to every
        # tick's rest estimate before feeding footprint trackers).
        self.x_cpu: Array | None = None
        self._x_cpu_resid: Array | None = None
        self._models: cpumod.LinearPowerModel | None = None
        self._win_feats = None
        self._retrain_cfg = retrain_config
        self.model_errors: list[np.ndarray] = []
        self.retrain_needed = np.zeros(self.b, bool)
        self.refits: list[tuple[int, np.ndarray]] = []       # (window, flags)
        self.skew_history: list[tuple[int, np.ndarray]] = []  # (window, skews)
        self._fnc: Array | None = None
        self._busy: Array | None = None
        if self.combined:
            self._models = _as_fleet_model(counter_model, self.b)
            self._fnc = _as_fleet_counters(fn_counters, self.b, num_fns)
            self._busy = jnp.sum(self._c_fns, axis=1)      # (B, M) seconds
            self.x_cpu, self._x_cpu_resid = combined_chip_power(
                self._models, self._fnc, self._busy,
                jnp.asarray(self.durations, jnp.float32),
            )
            self._force_chipless_zero()
            if window_features is not None:
                self._win_feats = np.asarray(window_features, np.float32)
        self._rest_idle_nodes: np.ndarray | None = None    # (B,) set at bootstrap

        # Streaming state.
        self._raw_w = np.zeros((self.n_windows, self.b), np.float32)
        self._n_raw = 0                          # pushed system windows
        self._raw_chip: list[np.ndarray] = []
        self._cp_col: list[np.ndarray] = []      # per-window principal column
        self._w_sync: list[np.ndarray] = []      # synchronized windows, in order
        self.skews: np.ndarray | None = None     # (B,) estimated at init_n
        self._lookahead = 0
        self.booted = False
        self.x0: Array | None = None
        self.init_busy_seconds: Array | None = None
        self._state = None
        self._traj: list[Array] = []
        self._next_tick = self.init_n
        self._drain: _DrainWorker | None = None

    @property
    def state(self):
        """Live engine state (``FleetStreamState``; the pool's in slot mode)."""
        return self._slot_pool.state if self._slot_pool is not None else self._state

    # -- ingestion ---------------------------------------------------------

    def push_window(
        self,
        w_sys: np.ndarray,
        w_chip: np.ndarray | None = None,
        cp_frac: np.ndarray | None = None,
        sys_frac: np.ndarray | None = None,
    ) -> None:
        """Feed one delta-window of fleet telemetry (all shapes (B,)).

        Windows must arrive in order.  May trigger zero or more engine
        ticks (``on_tick``) depending on the sync lookahead; the bootstrap
        (skew + X_0 + ``on_bootstrap``) fires once the init segment and its
        lookahead are buffered.
        """
        if self._n_raw >= self.n_windows:
            raise ValueError("segment already fully pushed")
        if self.has_chip and w_chip is None:
            raise ValueError("session was created with has_chip=True")
        if self.has_cp and (cp_frac is None or sys_frac is None):
            raise ValueError("session was created with has_cp=True")
        k = self._n_raw
        with tracing.span("faasmeter.ingest.push", window=k):
            self._raw_w[k] = np.asarray(w_sys, np.float32).reshape(self.b)
            self._n_raw += 1
            if self.has_chip:
                # Chipless rows zeroed: whatever the caller filled them with,
                # downstream (skew, rest-idle, combined targets, retraining)
                # sees the chip series identically 0.
                self._raw_chip.append(
                    np.asarray(w_chip, np.float32).reshape(self.b) * self._chip_zero
                )
            if self.has_cp:
                col = contrib.shared_principal_contribution(
                    tracing.put(np.asarray(cp_frac, np.float32), "push.cp_frac", window=k),
                    tracing.put(np.asarray(sys_frac, np.float32), "push.sys_frac", window=k),
                    delta=self.cfg.delta,
                )
                self._cp_col.append(tracing.pull(col, "push.principal", window=k))
            self._advance()

    def ingest(self, ticks, *, prefetch: int = 2, drain: bool = False) -> None:
        """Feed a whole telemetry tick stream, prefetched ahead of the engine.

        ``ticks`` is any iterator of objects with ``w_sys`` / ``w_chip`` /
        ``cp_frac`` / ``sys_frac`` attributes (``simulator.FleetTelemetryTick``
        in practice).  With ``prefetch >= 1`` the stream is pulled on a
        background thread (``data.pipeline.prefetch_iterator``), so the
        host-side sensing/resampling that produces tick ``t + 1`` overlaps
        the jitted ``fleet_step`` dispatched for tick ``t`` — the async
        ingest stage.  ``prefetch = 0`` falls back to strict alternation
        (sense, then step, then sense ...), which is the baseline the ingest
        benchmark compares against.

        With ``drain=True`` the emit stage (device→numpy materialization,
        retrain checks, ``on_tick`` hooks) moves to a background *drain
        thread* too, so three stages overlap: sensing tick ``t+1``,
        dispatching the jitted step for tick ``t``, and emitting tick
        ``t-1``'s attribution.  Dispatch order is unchanged, so results are
        bitwise identical; hook exceptions re-raise here, and on any
        failure both background threads are joined before this call
        returns (no leaked ``session-drain``/``prefetch-producer`` threads
        — pinned in tests/test_drain.py).
        """
        if self._drain is not None:
            raise ValueError("a drained ingest is already running on this session")
        if prefetch > 0:
            from repro.data.pipeline import prefetch_iterator

            ticks = prefetch_iterator(ticks, size=prefetch)
        if drain:
            self._drain = _DrainWorker(self)
        try:
            for tk in ticks:
                self.push_window(tk.w_sys, tk.w_chip, tk.cp_frac, tk.sys_frac)
        except BaseException:
            if self._drain is not None:
                worker, self._drain = self._drain, None
                worker.close(abandon=True)
            close = getattr(ticks, "close", None)
            if close is not None:
                close()
            raise
        else:
            if self._drain is not None:
                worker, self._drain = self._drain, None
                worker.close()

    # -- internals ---------------------------------------------------------

    def _force_chipless_zero(self) -> None:
        """Pin chipless nodes' chip-side split at exactly 0.0.

        Their counter models come out zero from ``prepare_combined_fleet``
        already; this makes the guarantee independent of the caller's
        model (a shared model broadcast over a mixed fleet, say)."""
        cm = jnp.asarray(self._chip_zero)
        self.x_cpu = self.x_cpu * cm[:, None]
        self._x_cpu_resid = self._x_cpu_resid * cm

    def _synced_window(self, t: int) -> np.ndarray:
        """(B,) synchronized system power for window ``t`` (``apply_shift``
        semantics: per-node linear interpolation of ``t + skew``, edges
        clamped to each node's OWN segment — on a ragged fleet a short
        node's positively-skewed tail reads must zero-order-hold at its
        last real window, exactly like the batch path's per-node clamp,
        never interpolate into the padding after its stream ended; the
        sync lookahead guarantees the needed raw windows have arrived)."""
        n = self._n_nodes  # (B,) per-node real window counts
        pos = np.clip(t + self.skews, 0.0, n - 1.0)
        lo = np.floor(pos).astype(np.int64)
        hi = np.minimum(lo + 1, (n - 1).astype(np.int64))
        frac = (pos - lo).astype(np.float32)
        avail = self._n_raw - 1
        nodes = np.arange(self.b)
        lo_v = self._raw_w[np.minimum(lo, avail), nodes]
        hi_v = self._raw_w[np.minimum(hi, avail), nodes]
        return lo_v * (np.float32(1.0) - frac) + hi_v * frac

    def _advance(self) -> None:
        cfg = self.cfg
        raw_count = self._n_raw
        if self.skews is None and raw_count >= self.init_n:
            if self.has_chip:
                w_arr = self._raw_w[: self.init_n]               # (init_n, B)
                r_arr = np.stack(self._raw_chip[: self.init_n])
                # Chipless nodes have no reference to sync against: skew 0,
                # the same as the batch path's _prep_node fallback.
                self.skews = np.asarray(
                    [
                        float(
                            syncmod.estimate_skew(
                                jnp.asarray(w_arr[:, i]), jnp.asarray(r_arr[:, i]),
                                max_shift=cfg.sync_max_shift,
                            )
                        )
                        if self._chip_mask[i]
                        else 0.0
                        for i in range(self.b)
                    ]
                )
            else:
                self.skews = np.zeros(self.b)
            self._lookahead = int(np.ceil(max(float(np.max(self.skews)), 0.0)))
        if self.skews is None:
            return
        if not self.booted:
            if raw_count < min(self.init_n + self._lookahead, self.n_windows):
                return
            self._bootstrap()
        lim = min(self.n_used, self.n_windows)
        while self._next_tick < lim and self._n_raw >= min(
            self._next_tick + self._lookahead + 1, self.n_windows
        ):
            self._process_tick(self._next_tick)
            self._next_tick += 1

    def _bootstrap(self) -> None:
        """Init-segment solve: synchronized windows 0..init_n-1 -> X_0."""
        eng = self.eng
        for t in range(self.init_n):
            self._w_sync.append(self._synced_window(t))
        w_init = jnp.asarray(np.stack(self._w_sync, axis=1))       # (B, init_n)
        if self.combined:
            # Rest-side idle from the chip floor over the init block — the
            # same estimator (and block) as the batch paths' _rest_idle, so
            # the streaming targets are causal AND identical to theirs.
            chip_init = jnp.asarray(
                np.stack(self._raw_chip[: self.init_n], axis=1)
            )                                                      # (B, init_n)
            self._rest_idle_nodes = np.asarray(
                eng.fleet_rest_idle(chip_init, self.idle)
            )
            target = eng.combined_rest_target(
                w_init, chip_init, jnp.asarray(self._rest_idle_nodes)[:, None]
            )
        else:
            target = jnp.maximum(w_init - self.idle[:, None], 0.0)
        init_c = self._c_aug_block(0, self.init_n)                 # (B, init_n, M_aug)
        self.x0 = eng.fleet_initial_estimate(init_c, target, self._engine_cfg)
        self.init_busy_seconds = init_c.sum(axis=1)
        if self._slots_cap is not None:
            # Serving mode: the engine state is a slot pool of the requested
            # capacity.  Nodes claim slots in order (warm handoff of the
            # batched X_0 rows — no per-node re-solve); spare slots stay
            # free for tenants beyond this session's fleet.
            pool = SlotFleetSession(
                self._slots_cap, self.m_aug,
                step_windows=self.cfg.step_windows,
                config=self._engine_cfg, mesh=self.mesh,
            )
            pool.warmup()
            x0_np = np.asarray(self.x0)
            self._slot_rows = np.asarray(
                [pool.admit(i, x0=x0_np[i]) for i in range(self.b)]
            )
            self._slot_pool = pool
        else:
            self._state = eng.fleet_stream_init(
                self.x0, self.cfg.step_windows, self._engine_cfg, mesh=self.mesh
            )
        self.booted = True
        if self.on_bootstrap is not None:
            self.on_bootstrap(self)

    def _c_aug_block(self, lo: int, hi: int) -> Array:
        """(B, hi-lo, M_aug) contribution rows with the principal appended."""
        block = self._c_fns[:, lo:hi]
        if not self.has_cp:
            return block
        col = jnp.asarray(np.stack(self._cp_col[lo:hi], axis=1))   # (B, hi-lo)
        return jnp.concatenate([block, col[:, :, None]], axis=2)

    def _rest_target(self, t: int, w_sync: np.ndarray) -> Array:
        """Tick ``t``'s disaggregation target on the default device."""
        w = tracing.put(w_sync, "dispatch.w_sync", tick=t)
        if not self.combined:
            return jnp.maximum(w - self.idle, 0.0)
        return self.eng.combined_rest_target(
            w,
            tracing.put(self._raw_chip[t], "dispatch.chip", tick=t),
            tracing.put(self._rest_idle_nodes, "dispatch.rest_idle", tick=t),
        )

    def _packed_feed(self, t, w_sync, c_t, a_t, ls_t, lq_t, live) -> np.ndarray:
        """Tick ``t``'s whole feed as one host array (``engine.pack_tick_feed``)."""
        if self.combined:
            chip, idle = self._raw_chip[t], self._rest_idle_nodes
        else:
            chip, idle = np.zeros(self.b, np.float32), self._idle_host
        return self.eng.pack_tick_feed(w_sync, chip, idle, c_t, a_t, ls_t, lq_t, live)

    def _process_tick(self, t: int) -> None:
        """Dispatch stage: build tick ``t``'s feed and launch the engine step.

        Runs on the ingesting thread; never blocks on the device.  The
        Kalman-step boundary is known from the tick index alone
        (``tick_in_step`` advances deterministically), so ``completed`` is
        computed host-side and the trajectory append keeps its strict
        dispatch order.  Emission (device→numpy, retrain check, ``on_tick``)
        goes through ``_emit_tick`` — inline, or queued to the drain thread.
        """
        cfg = self.cfg
        with tracing.span("faasmeter.session.dispatch", tick=t):
            w_sync = self._synced_window(t)
            self._w_sync.append(w_sync)
            j = t - self.init_n
            a_t = self._a_win[:, j]
            ls_t = self._ls_win[:, j]
            lq_t = self._lq_win[:, j]
            # A fresh (B, M_aug) array every tick, never a view of the host
            # copy: hooks receive it as ``busy_seconds``.
            if self.has_cp:
                c_t = np.concatenate([self._c_host[:, t], self._cp_col[t][:, None]], axis=1)
                # The principal's one pseudo-invocation per step, on its first tick.
                p = np.full((self.b, 1), 1.0 if j % cfg.step_windows == 0 else 0.0, np.float32)
                a_t = np.concatenate([a_t, p], axis=1)
                z = np.zeros((self.b, 1), np.float32)
                ls_t = np.concatenate([ls_t, z], axis=1)
                lq_t = np.concatenate([lq_t, z], axis=1)
            else:
                c_t = self._c_host[:, t].copy()
            live = None
            if self._ragged:
                # Nodes whose stream (or sub-step tail) ended before t are
                # masked out of the engine: zero rows into the ring buffer,
                # frozen Kalman state, exactly-zero attribution.
                live = t < self._n_used_nodes
            if self._slot_pool is not None:
                target = self._rest_target(t, w_sync)
                with tracing.span("faasmeter.engine.fleet_step"):
                    att = self._pool_tick(t, c_t, target, a_t, ls_t, lq_t, live)
            else:
                if self.mesh is None:
                    target = self._rest_target(t, w_sync)
                    step = self.eng.FleetStep(
                        c=tracing.put(c_t, "dispatch.c", tick=t), w=target,
                        a=tracing.put(a_t, "dispatch.a", tick=t),
                        lat_sum=tracing.put(ls_t, "dispatch.lat_sum", tick=t),
                        lat_sumsq=tracing.put(lq_t, "dispatch.lat_sumsq", tick=t),
                        valid=None if live is None else tracing.put(
                            live.astype(np.float32), "dispatch.valid", tick=t),
                    )
                else:
                    # A put into node shards costs one host->device copy per
                    # device whatever its size, so the whole feed goes in
                    # one; each device unpacks its own nodes and computes
                    # their target, and every leaf reaches the step in its
                    # node shards.
                    feed = self._packed_feed(t, w_sync, c_t, a_t, ls_t, lq_t, live)
                    step = self.eng.tick_feed(
                        tracing.put(feed, "dispatch.feed", self.mesh.node_sharding(), tick=t),
                        m=self.m_aug, has_valid=live is not None,
                    )
                    target = step.w
                with tracing.span("faasmeter.engine.fleet_step"):
                    self._state, att = self.eng.fleet_step(
                        self._state, step, config=self._engine_cfg, mesh=self.mesh
                    )
        # The boundary is a function of the tick index (the engine's
        # tick_in_step counter advances identically), so no device sync.
        completed = (j + 1) % cfg.step_windows == 0
        if completed:
            self._traj.append(att.x)
        if self._drain is not None:
            self._drain.put((t, att, c_t, a_t, target, w_sync, live, completed))
        else:
            self._emit_tick(t, att, c_t, a_t, target, w_sync, live, completed)

    def _emit_tick(self, t, att, c_t, a_t, target, w_sync, live, completed) -> None:
        """Emit stage: materialize one dispatched tick for host consumers.

        Device→numpy transfer of the attribution, the live retrain check at
        step boundaries, and the ``on_tick`` hook.  Runs inline on the
        dispatching thread by default, or on the drain thread under
        ``ingest(drain=True)`` — in either case ticks emit in dispatch
        order.
        """
        with tracing.span("faasmeter.session.emit", tick=t):
            if completed and self._win_feats is not None:
                self._check_retrain(t)
            if self.on_tick is not None:
                self.on_tick(
                    StreamTick(
                        t=t,
                        x=tracing.pull(att.x, "emit.x", tick=t),
                        tick_power=tracing.pull(att.tick_power, "emit.tick_power", tick=t),
                        unattributed=tracing.pull(
                            att.unattributed, "emit.unattributed", tick=t
                        ),
                        busy_seconds=c_t,
                        a=np.asarray(a_t),
                        target=tracing.pull(target, "emit.target", tick=t),
                        w_sys=w_sync,
                        step_completed=completed,
                        valid=live,
                    )
                )

    def _pool_tick(self, t, c_t, target, a_t, ls_t, lq_t, live):
        """Drive one engine tick through the slot pool (``slots=`` mode).

        Nodes whose engine span ends at ``t`` are *released* first
        (continuous retirement: their slot returns to the pool, their
        Kalman row freezes); the remaining live nodes feed their rows, and
        the slot-major attribution is gathered back to node order for the
        session's hooks and trajectory."""
        pool = self._slot_pool
        if self._ragged:
            for i in np.nonzero(self._n_used_nodes == t)[0]:
                node = int(i)
                if node in pool._node_slot:
                    pool.release(node)
        w_np = tracing.pull(target, "pool.target", tick=t)
        a_np = np.asarray(a_t, np.float32)
        ls_np = np.asarray(ls_t, np.float32)
        lq_np = np.asarray(lq_t, np.float32)
        live_nodes = range(self.b) if live is None else np.nonzero(live)[0]
        feeds = {
            int(i): (c_t[i], w_np[i], a_np[i], ls_np[i], lq_np[i])
            for i in live_nodes
        }
        att = pool.step(feeds)
        rows = jnp.asarray(self._slot_rows)
        return self.eng.TickAttribution(
            tick_power=att.tick_power[rows],
            unattributed=att.unattributed[rows],
            x=att.x[rows],
            step_completed=att.step_completed,
        )

    # -- completion --------------------------------------------------------

    def finalize(self) -> list[FootprintReport]:
        """Close the segment and build per-node reports.

        Requires the full ``n_windows`` segment to have been pushed; runs
        the shared steps 5-6 finalizer per node
        (``sessions.report.finalize_streaming_session``).
        """
        return finalize_streaming_session(self)
