"""Telemetry front-end: edge cases + fleet-batched chain pins.

Two families:

- front-end edge cases the per-node chain must survive (segments shorter
  than one sensor period, lag longer than the segment, sensors slower than
  the delta window, zero-length pushes, samples exactly on window edges);
- bitwise pins of the fleet-batched chain (``sense_fleet`` /
  ``resample_fleet`` / ``FleetStreamingSensor`` / ``FleetWindowResampler``)
  against the per-node loop it replaces — exact equality, noise included,
  on full and ragged fleets under arbitrary chunking.
"""

import numpy as np
import pytest

import repro.telemetry.sources as src

DT = 0.02
DELTA = 1.0


def _true_power(b: int, t_len: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    base = 90.0 + 25.0 * np.abs(np.sin(np.arange(t_len) * DT))
    return base[None, :] + 2.0 * rng.standard_normal((b, t_len))


# ---------------------------------------------------------------------------
# Edge cases in the per-node chain.
# ---------------------------------------------------------------------------


def test_sense_short_segment_returns_empty_signal():
    # battery preset: 0.5 Hz -> one sample per 2 s; a sub-2 s segment
    # decimates to zero samples.  With lag_s > 0 this used to crash on
    # samples[0]; it must return an empty signal instead.
    t = _true_power(1, int(1.5 / DT))[0]
    sig = src.sense(t, DT, src.BATTERY_LIKE, np.random.default_rng(0))
    assert sig.times.shape == (0,) and sig.watts.shape == (0,)


def test_sense_short_segment_matches_streaming_push():
    t = _true_power(1, int(1.5 / DT))[0]
    batch = src.sense(t, DT, src.BATTERY_LIKE, np.random.default_rng(3))
    stream = src.StreamingSensor(src.BATTERY_LIKE, DT, np.random.default_rng(3))
    out = stream.push(t)
    np.testing.assert_array_equal(out.watts, batch.watts)
    np.testing.assert_array_equal(out.times, batch.times)


def test_sense_lag_longer_than_segment():
    # 10 s segment, 5 Hz sensor, 20 s lag: every report predates the first
    # measurement, so the whole stream repeats the first sample (pre-noise).
    cfg = src.SensorConfig(rate_hz=5.0, tau_s=0.0, lag_s=20.0)
    t = _true_power(1, int(10.0 / DT))[0]
    sig = src.sense(t, DT, cfg, np.random.default_rng(0))
    assert sig.watts.shape == (50,)
    np.testing.assert_array_equal(sig.watts, np.full(50, sig.watts[0]))
    stream = src.StreamingSensor(cfg, DT, np.random.default_rng(0))
    np.testing.assert_array_equal(stream.push(t).watts, sig.watts)
    # and the fleet-batched chain under the same over-long lag
    true = _true_power(3, t.size)
    fs = src.sense_fleet(true, DT, cfg)
    assert fs.watts.shape == (3, 50)
    for i in range(3):
        ref = src.sense(true[i], DT, cfg, np.random.default_rng(0))
        np.testing.assert_array_equal(fs.node(i).watts, ref.watts)


def test_resample_forward_fills_slow_sensor():
    # battery at 0.5 Hz against 1 s windows: every other window has no
    # sample and must hold the previous mean (seeded at the first sample).
    t = _true_power(1, int(10.0 / DT))[0]
    sig = src.sense(t, DT, src.BATTERY_LIKE, np.random.default_rng(1))
    w = src.resample_to_windows(sig, 10, DELTA)
    assert w.shape == (10,)
    # windows [0,1) and [1,2) precede the first sample (t=2.0): seeded
    assert w[0] == w[1]
    rs = src.StreamingWindowResampler(DELTA)
    got = np.concatenate([rs.push(sig.times, sig.watts), rs.flush(10)])
    np.testing.assert_allclose(got, w, rtol=0, atol=1e-9)


def test_zero_length_pushes_are_noops():
    cfg = src.IPMI_LIKE
    t = _true_power(1, int(20.0 / DT))[0]
    ref = src.sense(t, DT, cfg, np.random.default_rng(2))
    stream = src.StreamingSensor(cfg, DT, np.random.default_rng(2))
    rs = src.StreamingWindowResampler(DELTA)
    pos, out_w = 0, []
    for k in (0, 300, 0, 0, 700, 0):
        sig = stream.push(t[pos:pos + k])
        pos += k
        out_w.append(rs.push(sig.times, sig.watts))
    sig = stream.push(t[pos:])
    out_w.append(rs.push(sig.times, sig.watts))
    out_w.append(rs.flush(20))
    got = np.concatenate(out_w)
    np.testing.assert_allclose(
        got, src.resample_to_windows(ref, 20, DELTA), rtol=0, atol=1e-9
    )


def test_window_edge_sample_goes_to_next_window():
    # A sample timestamped exactly on a window edge belongs to the *next*
    # window in both the batch path (searchsorted side='left') and the
    # streaming path (`t >= edge` closes the window first).
    times = np.array([0.5, 1.0, 1.5])   # 1.0 sits exactly on the 1st edge
    watts = np.array([10.0, 20.0, 30.0])
    sig = src.PowerSignal(times=times, watts=watts, rate_hz=2.0)
    w = src.resample_to_windows(sig, 2, DELTA)
    np.testing.assert_array_equal(w, [10.0, 25.0])
    rs = src.StreamingWindowResampler(DELTA)
    got = np.concatenate([rs.push(times, watts), rs.flush(2)])
    np.testing.assert_array_equal(got, w)


# ---------------------------------------------------------------------------
# Fleet-batched chain: bitwise pins against the per-node loop.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preset", sorted(src.PRESETS))
def test_sense_fleet_matches_per_node_bitwise(preset):
    cfg = src.PRESETS[preset]
    b, t_len = 5, 3000
    true = _true_power(b, t_len)
    lens = np.array([t_len, 2400, t_len, 900, 1775])
    fs = src.sense_fleet(
        true, DT, cfg,
        rngs=[np.random.default_rng(100 + i) for i in range(b)],
        lengths=lens,
    )
    for i in range(b):
        ref = src.sense(true[i, : lens[i]], DT, cfg, np.random.default_rng(100 + i))
        node = fs.node(i)
        np.testing.assert_array_equal(node.watts, ref.watts)
        np.testing.assert_array_equal(node.times, ref.times)
        assert node.energy_j() == ref.energy_j()


@pytest.mark.parametrize("preset", sorted(src.PRESETS))
def test_resample_fleet_matches_per_node_bitwise(preset):
    cfg = src.PRESETS[preset]
    b, t_len = 4, 3000
    true = _true_power(b, t_len)
    lens = np.array([t_len, 2000, 1500, t_len])
    fs = src.sense_fleet(
        true, DT, cfg,
        rngs=[np.random.default_rng(7 + i) for i in range(b)],
        lengths=lens,
    )
    n_wins = (lens * DT / DELTA).astype(int)
    w = src.resample_fleet(fs, int(n_wins.max()), DELTA)
    for i in range(b):
        ref = src.resample_to_windows(fs.node(i), int(n_wins[i]), DELTA)
        np.testing.assert_array_equal(w[i, : n_wins[i]], ref)


def test_sense_fleet_short_segment_is_empty():
    fs = src.sense_fleet(
        _true_power(3, int(1.5 / DT)), DT, src.BATTERY_LIKE,
        rngs=[np.random.default_rng(i) for i in range(3)],
    )
    assert fs.watts.shape == (3, 0) and np.all(fs.n_samples == 0)
    np.testing.assert_array_equal(fs.energy_j(), np.zeros(3))


def test_fleet_streaming_sensor_matches_per_node_bitwise():
    b, t_len = 4, 2500
    true = _true_power(b, t_len, seed=5)
    for preset in ("ipmi", "battery"):
        cfg = src.PRESETS[preset]
        fleet = src.FleetStreamingSensor(
            cfg, DT, [np.random.default_rng(40 + i) for i in range(b)]
        )
        nodes = [
            src.StreamingSensor(cfg, DT, np.random.default_rng(40 + i))
            for i in range(b)
        ]
        rng = np.random.default_rng(9)
        pos = 0
        while pos < t_len:
            k = min(int(rng.integers(0, 130)), t_len - pos)
            out = fleet.push(true[:, pos:pos + k])
            for i in range(b):
                ref = nodes[i].push(true[i, pos:pos + k])
                np.testing.assert_array_equal(out.watts[i], ref.watts)
                np.testing.assert_array_equal(out.times, ref.times)
            pos += k


def test_fleet_window_resampler_matches_batch_bitwise():
    # The fleet resampler must reproduce the *batch* cumulative-sum floats
    # exactly — this is the property that makes stream_fleet telemetry
    # bitwise equal to simulate_fleet telemetry.
    b, t_len = 4, 3000
    true = _true_power(b, t_len, seed=6)
    n_w = int(t_len * DT / DELTA)
    for preset in sorted(src.PRESETS):
        cfg = src.PRESETS[preset]
        rngs = lambda: [np.random.default_rng(60 + i) for i in range(b)]  # noqa: E731
        fs = src.sense_fleet(true, DT, cfg, rngs=rngs())
        want = src.resample_fleet(fs, n_w, DELTA)
        sensor = src.FleetStreamingSensor(cfg, DT, rngs())
        rs = src.FleetWindowResampler(DELTA, b)
        got = []
        rng = np.random.default_rng(11)
        pos = 0
        while pos < t_len:
            k = min(int(rng.integers(0, 200)), t_len - pos)
            sig = sensor.push(true[:, pos:pos + k])
            got.append(rs.push(sig.times, sig.watts))
            pos += k
        got.append(rs.flush(n_w))
        np.testing.assert_array_equal(np.concatenate(got, axis=1), want)


def test_fleet_window_resampler_flush_row_matches_batch_tail():
    # flush_row closes one node's remaining windows without touching fleet
    # state — the values must equal the batch resampler's forward-fill tail.
    b = 3
    cfg = src.RAPL_LIKE
    true = _true_power(b, 2000, seed=8)
    fs = src.sense_fleet(true, DT, cfg, rngs=[np.random.default_rng(i) for i in range(b)])
    n_w = 40
    want = src.resample_fleet(fs, n_w, DELTA)
    rs = src.FleetWindowResampler(DELTA, b)
    closed = rs.push(fs.times, fs.watts)
    n_closed = closed.shape[1]
    for i in range(b):
        tail = rs.flush_row(i, n_w)
        np.testing.assert_array_equal(tail, want[i, n_closed:])
    # fleet state untouched: a full flush still closes the same windows
    np.testing.assert_array_equal(rs.flush(n_w), want[:, n_closed:])


def test_fleet_zero_and_empty_pushes():
    b = 3
    cfg = src.PLUG_LIKE
    true = _true_power(b, 1000, seed=12)
    ref = src.sense_fleet(true, DT, cfg, rngs=[np.random.default_rng(i) for i in range(b)])
    sensor = src.FleetStreamingSensor(cfg, DT, [np.random.default_rng(i) for i in range(b)])
    rs = src.FleetWindowResampler(DELTA, b)
    got_w, got_s = [], []
    for a, e in ((0, 0), (0, 400), (400, 400), (400, 1000), (1000, 1000)):
        sig = sensor.push(true[:, a:e])
        got_s.append(sig.watts)
        got_w.append(rs.push(sig.times, sig.watts))
    n_w = int(1000 * DT / DELTA)
    got_w.append(rs.flush(n_w))
    np.testing.assert_array_equal(np.concatenate(got_s, axis=1), ref.watts)
    np.testing.assert_array_equal(
        np.concatenate(got_w, axis=1), src.resample_fleet(ref, n_w, DELTA)
    )


# ---------------------------------------------------------------------------
# Simulator + ingest integration.
# ---------------------------------------------------------------------------


def _fleet(durations, platform="server"):
    from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform=platform))
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, seed=30 + i))
        for i, d in enumerate(durations)
    ]
    return sim, traces


def test_simulate_equals_simulate_fleet_bitwise():
    sim, traces = _fleet([50.0, 30.0, 40.0])
    seeds = [11, 12, 13]
    fleet = sim.simulate_fleet(traces, seeds=seeds)
    for i, t in enumerate(traces):
        solo = sim.simulate(t, seed=seeds[i])
        np.testing.assert_array_equal(
            np.asarray(solo.telemetry.system_power),
            np.asarray(fleet[i].telemetry.system_power),
        )
        np.testing.assert_array_equal(
            np.asarray(solo.telemetry.chip_power),
            np.asarray(fleet[i].telemetry.chip_power),
        )
        assert solo.measured_energy_j == fleet[i].measured_energy_j


def test_stream_fleet_equals_simulate_fleet_ragged_bitwise():
    sim, traces = _fleet([50.0, 30.0, 40.0])
    seeds = [11, 12, 13]
    fleet = sim.simulate_fleet(traces, seeds=seeds)
    n_list = [f.num_windows for f in fleet]
    ticks = list(sim.stream_fleet(traces, seeds=seeds))
    assert [tk.t for tk in ticks] == list(range(max(n_list)))
    for tk in ticks:
        for i in range(len(traces)):
            if tk.t < n_list[i]:
                assert tk.valid[i]
                assert np.float32(tk.w_sys[i]) == np.asarray(
                    fleet[i].telemetry.system_power
                )[tk.t]
                assert np.float32(tk.w_chip[i]) == np.asarray(
                    fleet[i].telemetry.chip_power
                )[tk.t]
            else:
                assert not tk.valid[i]
                assert tk.w_sys[i] == 0.0


def test_prefetch_iterator_order_transfer_and_errors():
    from repro.core.sessions.prefetch import prefetch_iterator

    assert list(prefetch_iterator(iter(range(50)), size=3)) == list(range(50))
    assert list(prefetch_iterator(iter([1, 2, 3]), size=2, transfer=lambda x: x * 10)) \
        == [10, 20, 30]

    def boom():
        yield 1
        raise RuntimeError("producer died")

    it = prefetch_iterator(boom(), size=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="producer died"):
        next(it)
    with pytest.raises(ValueError):
        next(prefetch_iterator(iter([1]), size=0))


def test_session_ingest_matches_push_loop():
    # Overlapped ingest is a scheduling change, not a numerical one: reports
    # must be identical with prefetch on and off.
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.workload.azure import WorkloadConfig, generate_trace
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    cp = EnergyFirstControlPlane(reg)
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=120.0, seed=s)) for s in (3, 4)
    ]
    a = cp.profile_fleet(traces, seeds=[1, 2], prefetch=0)
    b = cp.profile_fleet(traces, seeds=[1, 2], prefetch=3)
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(
            np.asarray(ra.report.spectrum.j_indiv),
            np.asarray(rb.report.spectrum.j_indiv),
        )
        np.testing.assert_array_equal(
            np.asarray(ra.report.spectrum.j_total),
            np.asarray(rb.report.spectrum.j_total),
        )
