"""Slot-based fleet serving (``SlotFleetSession``) vs the fixed-fleet paths.

The slot pool turns the streaming engine into a server: nodes claim and
release a fixed pool of ``capacity`` engine slots while the stream keeps
ticking, occupancy rides ``FleetStep.valid``, and admission init solves are
length-bucketed so every serving code path is pre-warmable.  Pinned here:

- a static fleet served through the pool (with spare slots) matches all
  three segment engines at 1e-5, sharded and unsharded;
- churn (joins/leaves/dropped windows) causes **zero retraces** after
  ``warmup()``;
- per-node math is node-independent: a node that joins mid-stream ends
  with the same estimate as a pool of one fed only its own ticks;
- the rejoin regression: admitting into a slot whose previous tenant wrote
  ticks earlier in the current partial step equals admitting into a slot
  that was never occupied (``fleet_stream_reset_slots`` scrubs the rows);
- bucketed packing reclaims ``pad_waste_frac`` on extreme rag while
  reproducing the monolithic pack per node;
- mid-stream ``reshard`` is pinned at 1e-5 against an uninterrupted run;
- ``profile_fleet(slots=...)`` matches the plain fixed-fleet session, and
  a ``ControlLoop`` survives nodes joining/leaving through the pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched_engine import (
    DEFAULT_BUCKETS,
    EngineConfig,
    bucket_for,
    bucketed_initial_estimate,
    bucketed_pad_waste,
    fleet_initial_estimate,
    fleet_ticks,
    pack_fleet_buckets,
    pack_fleet_inputs,
    pad_waste_frac,
    run_fleet,
    run_fleet_bucketed,
    run_fleet_gram,
    run_fleet_stream,
    synthetic_fleet,
    synthetic_ragged_windows,
)
from repro.core.profiler import SlotFleetSession
from repro.distributed.sharding import fleet_mesh
from repro.serving.scheduler import SlotAdmissionQueue
from repro.telemetry.simulator import churn_schedule

CFG = EngineConfig()
ENGINES = [run_fleet, run_fleet_gram, run_fleet_stream]
#: Mesh sizes above one device: the ``multidevice`` params (tests/conftest.py).
MULTI = [pytest.param(k, marks=pytest.mark.multidevice) for k in (2, 8)]


def _tick_rows(ticks, t):
    """numpy (B, ...) rows of tick ``t`` from a ``fleet_ticks`` stream."""
    row = jax.tree.map(lambda l: np.asarray(l[t]), ticks)
    return row


def _feed_all(pool, ticks, t, nodes):
    row = _tick_rows(ticks, t)
    feeds = {
        n: (row.c[n], row.w[n], row.a[n], row.lat_sum[n], row.lat_sumsq[n])
        for n in nodes
    }
    return pool.step(feeds)


def _rand_feed(rng, m):
    return (
        rng.random(m).astype(np.float32),
        np.float32(40.0 + 10.0 * rng.random()),
        rng.integers(0, 2, m).astype(np.float32),
        rng.random(m).astype(np.float32),
        rng.random(m).astype(np.float32),
    )


# ---------------------------------------------------------------------------
# Static fleet: pool == segment engines (spare slots included).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_static_pool_matches_engines(engine):
    """A static fleet driven through the pool (2 spare slots) reproduces
    every segment engine's x_final at 1e-5."""
    b, s, n_w, m = 3, 4, 6, 8
    inputs = synthetic_fleet(b, s, n_w, m, seed=0)
    ref = engine(inputs, CFG)
    pool = SlotFleetSession(b + 2, m, step_windows=n_w, config=CFG)
    pool.warmup()
    for i in range(b):
        pool.admit(i, x0=np.asarray(ref.x0)[i])
    ticks = fleet_ticks(inputs)
    for t in range(s * n_w):
        _feed_all(pool, ticks, t, range(b))
    est = pool.estimates()
    np.testing.assert_allclose(
        np.stack([est[i] for i in range(b)]), np.asarray(ref.x_final),
        rtol=1e-5, atol=1e-5,
    )
    assert pool.free_slots == 2  # spares stayed free and inert


@pytest.mark.parametrize("k", [1, *MULTI])
def test_static_pool_sharded(k, request):
    """Same pin with the pool state sharded over 1/2/8 fake devices."""
    if k > 1 and len(jax.devices()) < k:
        pytest.skip(
            "needs >1 JAX device; run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    b, s, n_w, m = 6, 3, 5, 4
    cap = 8  # divides 1, 2 and 8 devices
    inputs = synthetic_fleet(b, s, n_w, m, seed=1)
    ref = run_fleet(inputs, CFG)
    mesh = fleet_mesh(devices=jax.devices()[:k])
    pool = SlotFleetSession(cap, m, step_windows=n_w, config=CFG, mesh=mesh)
    pool.warmup()
    for i in range(b):
        pool.admit(i, x0=np.asarray(ref.x0)[i])
    ticks = fleet_ticks(inputs)
    for t in range(s * n_w):
        _feed_all(pool, ticks, t, range(b))
    est = pool.estimates()
    np.testing.assert_allclose(
        np.stack([est[i] for i in range(b)]), np.asarray(ref.x_final),
        rtol=1e-5, atol=1e-5,
    )


# ---------------------------------------------------------------------------
# Churn: zero retraces after warmup; node independence.
# ---------------------------------------------------------------------------


def test_churn_zero_retraces():
    """A churn trace — joins, leaves, dropped windows, bucketed init
    solves of assorted lengths — runs with zero retraces after warmup."""
    cap, m, n_w, horizon = 6, 4, 5, 80
    spans = churn_schedule(
        16, horizon, capacity=cap, seed=3, mean_lifetime=22.0, mean_gap=3.0
    )
    assert spans, "schedule generated no tenancies"
    joins: dict[int, list] = {}
    leaves: dict[int, list] = {}
    for sp in spans:
        joins.setdefault(sp.join, []).append(sp.node)
        leaves.setdefault(sp.leave, []).append(sp.node)

    pool = SlotFleetSession(cap, m, step_windows=n_w, config=CFG)
    base = pool.warmup()
    rng = np.random.default_rng(0)
    for t in range(horizon):
        for node in leaves.get(t, ()):
            pool.release(node)
        for node in joins.get(t, ()):
            # Ragged init blocks: every admit exercises a bucketed solve.
            n_init = int(rng.integers(3, 20))
            pool.admit(
                node,
                rng.random((n_init, m)).astype(np.float32),
                rng.random(n_init).astype(np.float32) * 30.0,
            )
        feeds = {
            n: _rand_feed(rng, m)
            for n in pool.live_nodes
            if rng.random() > 0.1  # occasional dropped window
        }
        pool.step(feeds)
    assert pool.admits == len(spans)
    assert pool.ticks == horizon
    after = pool.compile_counts()
    assert after == base, f"retraced under churn: {base} -> {after}"


def test_join_mid_stream_is_node_independent():
    """A node joining a busy pool at a step boundary ends with exactly the
    estimate a 1-slot pool fed only its own ticks produces."""
    m, n_w = 4, 5
    rng = np.random.default_rng(7)
    x0 = rng.random(m).astype(np.float32) * 5.0
    late_feeds = [_rand_feed(rng, m) for _ in range(3 * n_w)]

    pool = SlotFleetSession(3, m, step_windows=n_w, config=CFG)
    pool.warmup()
    pool.admit(0, x0=rng.random(m).astype(np.float32))
    pool.admit(1, x0=rng.random(m).astype(np.float32))
    bg = np.random.default_rng(11)
    for t in range(2 * n_w):  # two full steps before the join
        pool.step({n: _rand_feed(bg, m) for n in (0, 1)})
    pool.admit(9, x0=x0)
    for t in range(3 * n_w):
        feeds = {n: _rand_feed(bg, m) for n in (0, 1)}
        feeds[9] = late_feeds[t]
        pool.step(feeds)

    solo = SlotFleetSession(1, m, step_windows=n_w, config=CFG)
    solo.warmup()
    solo.admit(9, x0=x0)
    for t in range(3 * n_w):
        solo.step({9: late_feeds[t]})
    np.testing.assert_allclose(
        pool.estimates()[9], solo.estimates()[9], rtol=1e-5, atol=1e-5
    )


def test_rejoin_resets_partial_step_rows():
    """Satellite regression: a tenant admitted into a slot whose previous
    occupant wrote ticks earlier in the *current partial step* must see a
    clean ring buffer — identical to joining a never-occupied slot."""
    m, n_w = 3, 5
    rng = np.random.default_rng(5)
    x0_b = rng.random(m).astype(np.float32)
    b_feeds = [_rand_feed(rng, m) for _ in range(2 * n_w)]

    def run(with_previous_tenant):
        pool = SlotFleetSession(1, m, step_windows=n_w, config=CFG)
        pool.warmup()
        junk = np.random.default_rng(1)
        if with_previous_tenant:
            pool.admit(0, x0=junk.random(m).astype(np.float32) * 9.0)
        for _ in range(2):  # two ticks into a 5-tick step
            feeds = {0: _rand_feed(junk, m)} if with_previous_tenant else {}
            pool.step(feeds)
        if with_previous_tenant:
            pool.release(0)
        pool.admit(7, x0=x0_b)
        # B's first Kalman boundary closes this partial step: without the
        # admit-time reset, A's two ring rows would leak into B's gram.
        for t in range(2 * n_w):
            pool.step({7: b_feeds[t]})
        return pool.estimates()[7]

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Length buckets: init solves and packing.
# ---------------------------------------------------------------------------


def test_bucket_for_table():
    assert bucket_for(1) == 8 and bucket_for(8) == 8 and bucket_for(9) == 16
    assert bucket_for(512) == 512
    assert bucket_for(513) == 1024  # past the table: next power of two
    with pytest.raises(ValueError):
        bucket_for(0)


def test_bucketed_init_matches_exact():
    """Zero-padding an init block to its bucket is exact in the gram
    domain: the bucketed solve equals the unpadded solve at 1e-5."""
    rng = np.random.default_rng(2)
    for n in (5, 13, 64):
        c = jnp.asarray(rng.random((n, 6)), jnp.float32)
        w = jnp.asarray(rng.random(n) * 40.0, jnp.float32)
        exact = fleet_initial_estimate(c[None], w[None], CFG)[0]
        bucketed = bucketed_initial_estimate(c, w, CFG)
        np.testing.assert_allclose(
            np.asarray(bucketed), np.asarray(exact), rtol=1e-5, atol=1e-5
        )


def test_pack_fleet_buckets_matches_monolithic():
    """Extreme rag: bucketed groups reproduce the monolithic pack per node
    while wasting far fewer padded ticks."""
    n_w = 4
    lengths = [5, 9, 96, 8, 13, 17]
    b, n, m = len(lengths), max(lengths), 5
    arrs = synthetic_ragged_windows(b, n, m, lengths=lengths, seed=4)
    mono = pack_fleet_inputs(*arrs, step_windows=n_w, lengths=lengths)
    ref = run_fleet(mono, CFG)
    buckets = pack_fleet_buckets(
        *arrs, step_windows=n_w, lengths=lengths, buckets=(2, 4, 8, 16, 32)
    )
    assert len(buckets) > 1  # the rag actually split into groups
    x_final, x0, _ = run_fleet_bucketed(buckets, CFG)
    np.testing.assert_allclose(
        np.asarray(x_final), np.asarray(ref.x_final), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(x0), np.asarray(ref.x0), rtol=1e-5, atol=1e-5
    )
    waste_mono = pad_waste_frac(lengths, n_w)
    waste_bkt = bucketed_pad_waste(buckets, n_w)
    assert waste_bkt < waste_mono
    assert waste_mono > 0.5  # the monolithic pack really is mostly padding


# ---------------------------------------------------------------------------
# Mesh elasticity: mid-stream reshard.
# ---------------------------------------------------------------------------


def test_reshard_mid_stream_pinned():
    """checkpoint -> put -> resume equals the uninterrupted run at 1e-5."""
    cap, m, n_w = 4, 3, 5

    def build():
        pool = SlotFleetSession(cap, m, step_windows=n_w, config=CFG)
        pool.warmup()
        for i in range(cap):
            pool.admit(i, x0=np.full(m, 0.5 * (i + 1), np.float32))
        return pool

    def drive(pool, ticks, rng):
        for _ in range(ticks):
            pool.step({n: _rand_feed(rng, m) for n in range(cap)})

    a = build()
    drive(a, 23, np.random.default_rng(1))
    b = build()
    rng = np.random.default_rng(1)
    drive(b, 11, rng)
    b.reshard(fleet_mesh(cap))  # sharded when devices allow; 1-device mesh else
    drive(b, 12, rng)
    b.reshard(None)  # and back down to the default device
    ea, eb = a.estimates(), b.estimates()
    np.testing.assert_allclose(
        np.stack([ea[i] for i in range(cap)]),
        np.stack([eb[i] for i in range(cap)]),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.multidevice
def test_reshard_across_device_counts():
    """Elastic device set: 1 -> 2 -> 8 -> 1 devices mid-stream, pinned."""
    cap, m, n_w = 8, 3, 4
    meshes = [
        None,
        fleet_mesh(devices=jax.devices()[:2]),
        fleet_mesh(devices=jax.devices()[:8]),
        None,
    ]

    def build():
        pool = SlotFleetSession(cap, m, step_windows=n_w, config=CFG)
        pool.warmup()
        for i in range(cap):
            pool.admit(i, x0=np.full(m, 0.3 * (i + 1), np.float32))
        return pool

    a = build()
    rng = np.random.default_rng(9)
    for _ in range(4 * n_w):
        a.step({n: _rand_feed(rng, m) for n in range(cap)})

    b = build()
    rng = np.random.default_rng(9)
    for mesh in meshes:
        b.reshard(mesh)
        for _ in range(n_w):
            b.step({n: _rand_feed(rng, m) for n in range(cap)})
    ea, eb = a.estimates(), b.estimates()
    np.testing.assert_allclose(
        np.stack([ea[i] for i in range(cap)]),
        np.stack([eb[i] for i in range(cap)]),
        rtol=1e-5, atol=1e-5,
    )


# ---------------------------------------------------------------------------
# Admission queue.
# ---------------------------------------------------------------------------


def test_admission_queue_fifo_and_gate():
    m, n_w = 3, 4
    pool = SlotFleetSession(2, m, step_windows=n_w, config=CFG)
    pool.warmup()
    q = SlotAdmissionQueue(pool)
    assert q.submit(0, x0=np.zeros(m, np.float32)) == 0
    assert q.submit(1, x0=np.zeros(m, np.float32)) == 1
    # Pool full: 2 and 3 queue in arrival order.
    assert q.submit(2, x0=np.zeros(m, np.float32)) is None
    assert q.submit(3, x0=np.zeros(m, np.float32)) is None
    assert q.pending == 2
    pool.release(0)
    placed = q.drain()
    assert placed == [(2, 0)] and q.pending == 1  # FIFO: 2 before 3
    pool.release(1)
    assert q.drain() == [(3, 1)] and q.pending == 0

    # A gated head request parks the whole queue (head-of-line, like the
    # invocation scheduler), and clears once the gate opens.
    open_gate = [False]
    gated = SlotAdmissionQueue(pool, gate=lambda req: open_gate[0])
    pool.release(2)
    assert gated.submit(9, x0=np.zeros(m, np.float32)) is None
    assert gated.pending == 1 and gated.deferred == 1
    open_gate[0] = True
    assert gated.drain() == [(9, 0)]


# ---------------------------------------------------------------------------
# Control plane: profile_fleet(slots=...) and ControlLoop under churn.
# ---------------------------------------------------------------------------


def _fast_control_plane():
    from repro.core.profiler import ProfilerConfig
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.telemetry.simulator import SimulatorConfig
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    return reg, EnergyFirstControlPlane(
        reg, SimulatorConfig(platform="edge"),
        ProfilerConfig(init_windows=40, step_windows=20),
    )


def test_profile_fleet_slots_matches_plain():
    """Ragged fleet through a 6-slot pool == the plain fixed session."""
    from repro.workload.azure import WorkloadConfig, generate_trace

    reg, cp = _fast_control_plane()
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=i))
        for i, d in enumerate((160.0, 240.0, 200.0))
    ]
    plain = cp.profile_fleet(traces, mesh=None)
    slot = cp.profile_fleet(traces, mesh=None, slots=6)
    for a, b in zip(plain, slot):
        np.testing.assert_allclose(
            np.asarray(a.report.x_power), np.asarray(b.report.x_power),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(a.report.x_trajectory), np.asarray(b.report.x_trajectory),
            rtol=1e-5, atol=1e-5,
        )


def test_profile_fleet_slots_too_small_raises():
    from repro.workload.azure import WorkloadConfig, generate_trace

    reg, cp = _fast_control_plane()
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=160.0, load=1.0, seed=i))
        for i in range(3)
    ]
    with pytest.raises(ValueError, match="slots"):
        cp.profile_fleet(traces, mesh=None, slots=2)


def test_control_loop_survives_churn():
    """A ControlLoop bound to a slot-pool replay of a ragged fleet (nodes
    leaving mid-segment) finishes and reshapes every node's trace."""
    from repro.serving.control_plane import ControlConfig, ControlLoop
    from repro.workload.azure import WorkloadConfig, generate_trace

    reg, cp = _fast_control_plane()
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=2.0, seed=i))
        for i, d in enumerate((180.0, 260.0, 220.0))
    ]
    loop = ControlLoop(ControlConfig(cap_watts=250.0))
    out = cp.profile_fleet(traces, mesh=None, slots=5, control=loop)
    assert len(out) == 3
    controlled = loop.controlled_traces()
    assert len(controlled) == 3
