"""SlotFleetSession: slot-based live fleet serving (docs/serving.md)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core.sessions.base import FleetSession


class SlotFleetSession(FleetSession):
    """Slot-based live fleet serving session (docs/serving.md).

    The engine-level core of continuous admission/retirement: a fixed pool
    of ``capacity`` engine slots — one ``(capacity, M)``-shaped
    ``FleetStreamState`` — where live nodes *claim* and *release* slots
    while the stream keeps ticking.  Everything that changes at serving
    time is data, never shape:

    - occupancy rides ``FleetStep.valid`` (a free slot is a permanently
      invalid node: zero rows, frozen Kalman state, exactly-zero
      attribution);
    - a claim runs ``fleet_stream_reset_slots`` (one-hot flags + an X_0
      row — the rejoin fix: the new tenant's slot is scrubbed of any rows
      the previous tenant wrote earlier in the current partial step);
    - the admission-time init solve is length-bucketed
      (``bucketed_initial_estimate``), so a node joining with an arbitrary
      init-block length lands in one of the pre-warmed per-bucket compiles.

    After ``warmup()`` (one dummy step + reset + every bucket solver) a
    churn trace of joins and leaves therefore runs with **zero retraces**
    — pinned in tests/test_slot_serving.py (``test_churn_zero_retraces``).

    Mesh elasticity: the pool state may live sharded over a
    ``distributed.sharding.FleetMesh`` (``capacity`` must tile it), and
    ``reshard`` moves the *live* state onto a different mesh mid-stream
    (checkpoint to host → ``sharding.put`` → resume) at the cost of one
    deliberate compile per new mesh, pinned at 1e-5 against an
    uninterrupted run.

    The telemetry-level counterpart is ``StreamingFleetSession(slots=...)``
    / ``EnergyFirstControlPlane.profile_fleet(slots=...)``, which route a
    whole profiling segment through a pool like this one.
    """

    def __init__(
        self,
        capacity: int,
        num_fns: int,
        *,
        step_windows: int,
        config=None,
        mesh=None,
        buckets=None,
    ):
        """Args:
          capacity: number of engine slots B (the fleet's compile shape).
          num_fns: per-slot function-axis width M (M_aug with a principal).
          step_windows: ticks per Kalman step (ring-buffer shape).
          config: ``engine.EngineConfig`` (default config if None).
          mesh: optional ``FleetMesh``; capacity must tile it evenly.
          buckets: init-solve length-bucket table
            (``engine.DEFAULT_BUCKETS`` if None).
        """
        super().__init__(
            config=eng.EngineConfig() if config is None else config, mesh=mesh
        )
        self.capacity = int(capacity)
        self.num_fns = int(num_fns)
        self.step_windows = int(step_windows)
        self.buckets = tuple(eng.DEFAULT_BUCKETS if buckets is None else buckets)
        if mesh is not None:
            mesh.validate(self.capacity)
        self._state = eng.fleet_stream_init(
            jnp.zeros((self.capacity, self.num_fns), jnp.float32),
            self.step_windows,
            self.config,
            mesh=mesh,
        )
        self._slot_node: list = [-1] * self.capacity   # slot -> node (-1 free)
        self._node_slot: dict = {}                     # node -> slot
        self.ticks = 0
        self.admits = 0
        self.releases = 0

    # -- pool state --------------------------------------------------------

    @property
    def state(self):
        """Live engine state (capacity-shaped ``FleetStreamState``)."""
        return self._state

    @property
    def free_slots(self) -> int:
        """Number of unclaimed slots."""
        return self._slot_node.count(-1)

    @property
    def live_nodes(self) -> tuple:
        """Nodes currently holding slots, in slot order."""
        return tuple(n for n in self._slot_node if n != -1)

    def slot_of(self, node) -> int:
        """Slot index currently held by ``node`` (raises if none)."""
        try:
            return self._node_slot[node]
        except KeyError:
            raise ValueError(f"node {node!r} holds no slot") from None

    def estimates(self) -> dict:
        """``node -> (M,)`` current Kalman power estimate for live nodes."""
        x = np.asarray(jax.device_get(self._state.kalman.x))
        return {node: x[slot] for node, slot in self._node_slot.items()}

    # -- lifecycle ---------------------------------------------------------

    def warmup(self) -> dict:
        """Pre-compile every serving code path at the pool's shapes.

        One dummy ``fleet_step`` (on a scratch state — the live state is
        never advanced), one dummy slot reset, and every bucket's init
        solver (``warm_bucket_solvers``).  After this, admits, releases,
        dropped windows, and rag patterns are all pure data — zero
        retraces for the pool's lifetime (until ``reshard``, which
        deliberately compiles once per new mesh).  Returns the post-warmup
        ``compile_counts`` snapshot."""
        cap, m = self.capacity, self.num_fns
        zf = lambda shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
        eng.warm_bucket_solvers(m, self.config, buckets=self.buckets)
        scratch = eng.fleet_stream_init(
            zf((cap, m)), self.step_windows, self.config, mesh=self.mesh
        )
        step = eng.FleetStep(
            c=zf((cap, m)), w=zf((cap,)), a=zf((cap, m)),
            lat_sum=zf((cap, m)), lat_sumsq=zf((cap, m)), valid=zf((cap,)),
        )
        scratch, att = eng.fleet_step(
            scratch, step, config=self.config, mesh=self.mesh
        )
        scratch = eng.fleet_stream_reset_slots(
            scratch, zf((cap,)), zf((cap, m)), mesh=self.mesh
        )
        jax.block_until_ready((scratch, att))
        return self.compile_counts()

    def admit(self, node, init_c=None, init_w=None, *, x0=None) -> int:
        """Claim the lowest free slot for ``node``; returns the slot index.

        Either pass the node's init block (``init_c`` (n, M) contribution
        rows + ``init_w`` (n,) idle-adjusted power — solved to an X_0 row
        through the pre-warmed bucketed solver) or an explicit ``x0`` (M,)
        row (warm handoff from a previous session / another node).  The
        slot's Kalman row is re-initialized and its ring-buffer rows and
        partial-step accumulators are zeroed (``fleet_stream_reset_slots``)
        so nothing a previous tenant wrote in the current partial step can
        leak into the new tenant's first boundary update.  Raises
        ``ValueError`` when the node already holds a slot or the pool is
        full (queue admissions with ``serving.scheduler.SlotAdmissionQueue``).
        """
        if node in self._node_slot:
            raise ValueError(
                f"node {node!r} already holds slot {self._node_slot[node]}"
            )
        try:
            slot = self._slot_node.index(-1)
        except ValueError:
            raise ValueError(
                f"slot pool full (capacity {self.capacity}); release a node first"
            ) from None
        if x0 is None:
            if init_c is None or init_w is None:
                raise ValueError("admit needs either x0= or an (init_c, init_w) block")
            x0 = eng.bucketed_initial_estimate(
                init_c, init_w, self.config, buckets=self.buckets
            )
        x0_full = np.zeros((self.capacity, self.num_fns), np.float32)
        x0_full[slot] = np.asarray(x0, np.float32)
        flags = np.zeros((self.capacity,), np.float32)
        flags[slot] = 1.0
        self._state = eng.fleet_stream_reset_slots(
            self._state, jnp.asarray(flags), jnp.asarray(x0_full), mesh=self.mesh
        )
        self._slot_node[slot] = node
        self._node_slot[node] = slot
        self.admits += 1
        return slot

    def release(self, node) -> int:
        """Release ``node``'s slot back to the pool; returns the slot index.

        Purely host-side bookkeeping: from the next tick the slot is
        simply absent from ``feeds`` (``valid = 0``), so its Kalman row
        freezes and its attribution is exactly zero until a new tenant
        claims — and thereby resets — the slot."""
        slot = self._node_slot.pop(node, None)
        if slot is None:
            raise ValueError(f"node {node!r} holds no slot")
        self._slot_node[slot] = -1
        self.releases += 1
        return slot

    def step(self, feeds: dict):
        """Advance the pool one telemetry tick; returns ``TickAttribution``.

        ``feeds`` maps ``node -> (c, w, a, lat_sum, lat_sumsq)`` per-tick
        rows ((M,), scalar, (M,), (M,), (M,)) for the nodes that produced
        this window.  A live node absent from ``feeds`` dropped the window
        (``valid = 0`` for this tick only); free slots are always invalid.
        The returned attribution arrays are slot-major (capacity rows) —
        map them back with ``slot_of``.  Raises ``ValueError`` on a feed
        for a node holding no slot."""
        cap, m = self.capacity, self.num_fns
        c = np.zeros((cap, m), np.float32)
        w = np.zeros((cap,), np.float32)
        a = np.zeros((cap, m), np.float32)
        ls = np.zeros((cap, m), np.float32)
        lq = np.zeros((cap, m), np.float32)
        valid = np.zeros((cap,), np.float32)
        for node, (c_i, w_i, a_i, ls_i, lq_i) in feeds.items():
            slot = self._node_slot.get(node)
            if slot is None:
                raise ValueError(f"feed for node {node!r} which holds no slot")
            c[slot] = np.asarray(c_i, np.float32)
            w[slot] = np.float32(w_i)
            a[slot] = np.asarray(a_i, np.float32)
            ls[slot] = np.asarray(ls_i, np.float32)
            lq[slot] = np.asarray(lq_i, np.float32)
            valid[slot] = 1.0
        step = eng.FleetStep(
            c=jnp.asarray(c), w=jnp.asarray(w), a=jnp.asarray(a),
            lat_sum=jnp.asarray(ls), lat_sumsq=jnp.asarray(lq),
            valid=jnp.asarray(valid),
        )
        self._state, att = eng.fleet_step(
            self._state, step, config=self.config, mesh=self.mesh
        )
        self.ticks += 1
        return att

    def reshard(self, mesh) -> None:
        """Move the live pool onto a different device mesh mid-stream.

        Checkpoint-to-host + ``sharding.put`` re-placement
        (``distributed.sharding.reshard``); values are bit-identical across
        the move, and subsequent steps compile once against the new mesh
        (the one deliberate compile of mesh elasticity).  ``mesh=None``
        scales down to the default device."""
        from repro.distributed.sharding import reshard as _reshard

        if mesh is not None:
            mesh.validate(self.capacity)
        self._state = _reshard(self._state, mesh)
        self.mesh = mesh
