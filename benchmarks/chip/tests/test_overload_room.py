"""The one-chip overload cell measures the controller up to three times its capacity.

A stopped open-loop run ends in error where the controller overtakes the
segment ("sweep again") or emits 95% of what is offered ("kept up").  The
cell's traffic is sized so that neither fires below 3 x C, C = 108 ticks/s
(the one-chip capacity it was sized from: ``sweep.py``'s untraced probe on
one TPU v5e), and the segment runs out first.  Run with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

CELL = "table2_server.stream_overload"
CAPACITY = 108.0   # ticks/s


def _cell_and_seconds():
    with open(harness.ROOT / "BENCHMARK.json") as f:
        seconds = json.load(f)["run_seconds"]
    return harness.load_cell(CELL), seconds


def test_segment_holds_three_times_capacity_after_warm_up():
    cell, seconds = _cell_and_seconds()
    p, traffic = cell.config["profiler"], cell.traffic
    n = cell.pacer.segment_windows(p, traffic, seconds)
    after = (n - p["init_windows"] - p["step_windows"] - p["sync_max_shift"]
             - traffic["tail_windows"])
    assert after >= 3 * CAPACITY * seconds


def test_segment_runs_out_before_the_kept_up_check_fires():
    cell, _ = _cell_and_seconds()
    assert 0.95 * cell.traffic["rate_windows_per_s"] > cell.traffic["segment_windows_per_s"]
