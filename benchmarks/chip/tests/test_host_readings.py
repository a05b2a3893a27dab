"""The host layers' per-layer metrics: one reader each over ``host_spans.per_tick``.

Every ``per_layer`` entry of ``BENCHMARK.json`` whose source is the
program's spans is read by ``metrics/<reading>.py`` from ``ctx["host"]``,
which ``harness.run_cell``'s traced run fills with ``host_spans.per_tick``
of its own trace.  Pinned here: each reader gives exactly ``per_tick``'s
reading on the recorded chip trace and nothing where the trace lacks it;
the put reading sums ``faasmeter.put`` time per tick; a traced run through
the harness reports every host reading of its cell.  Run with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import host_spans  # noqa: E402

SPANS = HERE / "testdata" / "table2_server_paced_spans.xplane.pb"
READINGS = ("tracker_host_us", "device_pull_us", "device_pulls_per_tick", "device_put_us",
            "session_host_us", "ingest_host_us", "fleet_step_host_us")


def _host_metrics() -> list[dict]:
    with open(harness.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    return [m for m in bench["per_layer"] if m["source"] == "program_span"]


def test_every_reading_has_an_entry_in_each_cell():
    """One entry per reading and cell, each listing its one cell."""
    got = {(m["name"].split(".")[0], tuple(m["workloads"])) for m in _host_metrics()}
    cells = {"table2_server.stream_paced", "table2_server.stream_overload",
             "table2_server_1024.stream_paced_4chip"}
    assert got == {(r, (c,)) for r in READINGS for c in cells}
    for m in _host_metrics():
        cell = m["workloads"][0]
        assert m["moves"] == ("node_ticks_per_s" if cell.endswith("overload")
                              else "tick_latency_p50_ms")
        assert m["better"] == "lower"


@pytest.fixture(scope="module")
def chip_host():
    red = host_spans.reduce(host_spans.load(str(SPANS)))
    return host_spans.per_tick(red, red["host_spans"]["bench.on_tick"]["count"])


@pytest.mark.parametrize("metric", [m["name"] for m in _host_metrics()])
def test_reader_gives_per_tick_reading(metric, chip_host):
    got = harness.metric_reader(metric)({"host": chip_host})
    assert got == chip_host.get(metric.split(".")[0])
    if metric.startswith("device_put_us"):
        # The fixture was recorded before the program spanned its puts.
        assert got is None
    else:
        assert got is not None and got > 0


def _ev(name, s, e, **meta):
    return (name, float(s), float(e), meta)


def test_put_reading_sums_put_time_per_tick():
    """Window [100, 1000) ns: puts inside dispatch and push count, cut to the
    window; a put after it does not.  The puts are carved out of the self
    time of the spans they sit in."""
    thread = [
        _ev("bench.window_open", 0, 100),
        _ev("faasmeter.ingest.push", 150, 400, window=3),
        _ev("faasmeter.put", 160, 200, site="push.principal", tick=3),
        _ev("faasmeter.session.dispatch", 250, 380, tick=2),
        _ev("faasmeter.put", 260, 300, site="dispatch.a", tick=2),
        _ev("faasmeter.put", 300, 320, site="dispatch.ls", tick=2),
        _ev("faasmeter.put", 950, 1050, site="dispatch.a", tick=3),
        _ev("bench.window_close", 1000, 1001),
        _ev("faasmeter.put", 1100, 1200, site="dispatch.a", tick=4),
    ]
    per = host_spans.per_tick(host_spans.reduce([thread]), 2)
    assert per["device_put_us"] == pytest.approx((40 + 40 + 20 + 50) * 1e-3 / 2)
    assert per["session_host_us"] == pytest.approx((130 - 60) * 1e-3 / 2)
    assert per["ingest_host_us"] == pytest.approx((250 - 40 - 130) * 1e-3 / 2)
    assert harness.metric_reader("device_put_us.paced")({"host": per}) == per["device_put_us"]


def test_traced_run_reports_the_host_readings(tmp_path):
    """A traced paced run through the harness, on the CPU at 4 nodes: each
    host reading of the cell is in the result line, as ``per_tick`` reads it
    from the run's own trace."""
    cell = copy.deepcopy(harness.load_cell("table2_server.stream_paced"))
    cell.config["nodes"] = 4
    cell.traffic.update(rate_windows_per_s=60.0, segment_windows_per_s=60.0)
    res = harness.run_cell(cell, 2**31 + 17, 1.0, True, time.perf_counter(),
                           trace_dir=str(tmp_path))
    assert res["correct"], res["checks"]
    path = str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    red = host_spans.reduce(host_spans.load(path))
    # The harness divides by the ticks it stamped in the window: one count
    # for every reading.
    whole = host_spans.per_tick(red, 1)
    ticks = round(whole["tracker_host_us"] / res["metrics"]["tracker_host_us.paced"]["value"])
    assert ticks == pytest.approx(red["host_spans"]["bench.on_tick"]["count"], abs=2)
    host = host_spans.per_tick(red, ticks)
    for r in READINGS:
        unit = "pulls" if r == "device_pulls_per_tick" else "us"
        assert res["metrics"][f"{r}.paced"] == {"value": pytest.approx(host[r], rel=1e-12),
                                                "unit": unit}
