"""The host-span reduction (``host_spans.py``) against hand-built and chip traces.

``testdata/table2_server_paced_spans.xplane.pb`` is a traced window of the
``table2_server`` configuration with the program's ``faasmeter.*`` spans,
paced at half its capacity for 0.5 s (``sweep.py --trace-dir`` on one TPU
v5e).  ``testdata/table2_server_paced.xplane.pb`` was recorded the same
way before the program had spans.  Run with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import collections
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import host_spans  # noqa: E402

NO_SPANS = HERE / "testdata" / "table2_server_paced.xplane.pb"
SPANS = HERE / "testdata" / "table2_server_paced_spans.xplane.pb"
LAYERS = ("faasmeter.ingest.push", "faasmeter.session.dispatch", "faasmeter.engine.fleet_step",
          "faasmeter.session.emit", "faasmeter.pull", "faasmeter.control.trackers")


def _ev(name, s, e, **meta):
    return (name, float(s), float(e), meta)


# Window [100, 1000) ns.  The ingesting thread: a wait cut by the window's
# opening, a tick nested push > dispatch > fleet_step and push > emit >
# {pull, trackers, bench.on_tick}, JAX spans inside, 10 ns under no span,
# then a push cut by the window's closing.  A second thread emits one tick.
INGEST = [
    _ev("bench.window_open", 0, 100),
    _ev("faasmeter.ingest.wait", 50, 200, depth=0),
    _ev("faasmeter.ingest.push", 200, 700, window=3),
    _ev("faasmeter.session.dispatch", 210, 400, tick=2),
    _ev("PjitFunction(_fleet_step_impl)", 220, 260),
    _ev("faasmeter.engine.fleet_step", 300, 350),
    _ev("faasmeter.session.emit", 400, 650, tick=2),
    _ev("faasmeter.pull", 410, 450, site="emit.x", tick=2),
    _ev("np.asarray(jax.Array)", 415, 445),
    _ev("faasmeter.control.trackers", 460, 600, tick=2),
    _ev("bench.on_tick", 610, 640),
    _ev("faasmeter.ingest.wait", 700, 940, depth=1),
    _ev("faasmeter.ingest.push", 950, 1100, window=4),
    _ev("faasmeter.session.dispatch", 960, 1050, tick=3),
]
OTHER = [
    _ev("faasmeter.session.emit", 300, 500, tick=1),
    _ev("faasmeter.pull", 310, 330, site="emit.target", tick=1),
    _ev("bench.window_close", 1000, 1001),
]


def test_self_time_and_window_cut_on_two_threads():
    red = host_spans.reduce([OTHER, INGEST])
    got = {k: (v["count"], round(v["seconds"] * 1e9, 6), round(v["self_seconds"] * 1e9, 6))
           for k, v in red["host_spans"].items()}
    assert got == {
        "faasmeter.ingest.wait": (1, 340, 340),
        "faasmeter.ingest.push": (2, 550, 70),
        "faasmeter.session.dispatch": (2, 230, 180),
        "faasmeter.engine.fleet_step": (1, 50, 50),
        "faasmeter.session.emit": (2, 450, 220),
        "faasmeter.pull": (2, 60, 60),
        "faasmeter.control.trackers": (1, 140, 140),
        "bench.on_tick": (1, 30, 30),
    }
    assert red["main_s"] == pytest.approx(900e-9)
    assert red["unspanned_s"] == pytest.approx(10e-9)
    per = host_spans.per_tick(red, 2)
    assert per == pytest.approx({
        "ingest_host_us": 0.035, "session_host_us": 0.2, "fleet_step_host_us": 0.025,
        "device_pull_us": 0.03, "tracker_host_us": 0.07, "device_pulls_per_tick": 1.0,
        "host_unspanned_us": 0.005,
    })
    assert host_spans.longest([OTHER, INGEST], n=1) == [
        {"site": "emit.x", "tick": 2, "ms": pytest.approx(40e-6)}
    ]
    assert host_spans.longest([OTHER, INGEST], None, n=2) == [
        {"span": "faasmeter.session.emit", "tick": 1, "ms": pytest.approx(180e-6)},
        {"span": "faasmeter.session.dispatch", "tick": 2, "ms": pytest.approx(140e-6)},
    ]


def test_a_trace_without_program_spans_reads_nothing():
    """The trace of a program without spans: no reading, no error."""
    red = host_spans.reduce(host_spans.load(str(NO_SPANS)))
    assert set(red["host_spans"]) == {"bench.on_tick"}
    assert red["main_s"] is None and red["unspanned_s"] is None
    assert host_spans.per_tick(red, red["host_spans"]["bench.on_tick"]["count"]) == {}


@pytest.fixture(scope="module")
def chip():
    threads = host_spans.load(str(SPANS))
    return threads, host_spans.reduce(threads)


def test_readings_on_the_chip_trace(chip):
    _, red = chip
    ticks = red["host_spans"]["bench.on_tick"]["count"]
    assert ticks == 24
    assert host_spans.per_tick(red, ticks) == pytest.approx({
        "ingest_host_us": 1162.3145416666669,
        "session_host_us": 4488.215041666667,
        "fleet_step_host_us": 547.7724583333335,
        "device_pull_us": 3271.787625000001,
        "tracker_host_us": 1789.807,
        "device_pulls_per_tick": 145 / 24,
        "host_unspanned_us": 126.37566666666434,
    }, rel=1e-9)


def test_pulls_on_the_chip_trace_by_site(chip):
    """Five pulls per emitted tick and one per pushed window: 25, though 23
    push spans start in the window, as the pushes open when the trace
    started and when it stopped are not recorded.  No step boundary falls
    in the window."""
    threads, _ = chip
    lo, hi = host_spans.window(threads)
    sites = collections.Counter(
        ev[3]["site"] for line in threads for ev in line
        if ev[0] == "faasmeter.pull" and lo <= ev[1] < hi
    )
    assert sites == {
        "emit.x": 24, "emit.tick_power": 24, "emit.unattributed": 24,
        "emit.busy_seconds": 24, "emit.target": 24, "push.principal": 25,
    }
    assert host_spans.longest(threads)[0] == {"site": "emit.x", "tick": 180,
                                              "ms": pytest.approx(1.02577)}


def test_spans_account_for_the_ingesting_thread(chip):
    """Ingest waits, the six host layers, ``bench.on_tick`` and the time
    under no span add up to the window on the ingesting thread."""
    _, red = chip
    spans = red["host_spans"]
    layers = sum(spans[n]["self_seconds"] for n in LAYERS)
    total = (spans["faasmeter.ingest.wait"]["self_seconds"] + layers
             + spans["bench.on_tick"]["self_seconds"] + red["unspanned_s"])
    assert red["main_s"] == pytest.approx(0.500510193, abs=1e-9)
    assert total == pytest.approx(red["main_s"], rel=0.01)
    work = red["main_s"] - spans["faasmeter.ingest.wait"]["self_seconds"]
    assert red["unspanned_s"] < 0.1 * work
