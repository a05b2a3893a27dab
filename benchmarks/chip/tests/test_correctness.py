"""The comparison that decides ``correct`` catches the control and the faults.

Runs on the CPU at a size a test can hold (a few nodes, a short segment):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

- The control, the reference computed one precision below the
  configuration's (``precision="high"``), reads above the limits.
- A whole run through the harness, the look for a chip left out, comes out
  correct, and comes out not correct with the timed path broken underneath:
  a Kalman step that returns its state unchanged; half of the fleet's feed
  left out of the step; an answer altered where it is produced.  (The
  exchange between chips is not in these one-chip cells: ``mesh=None``.)
"""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

CELLS = ["table2_server.stream_paced", "table2_server.stream_overload"]


def _small(name: str, nodes: int = 4) -> harness.Cell:
    cell = copy.deepcopy(harness.load_cell(name))
    cell.config["nodes"] = nodes
    # A paced rate the CPU sustains; an overload rate far above what it does.
    rate = 4000.0 if cell.traffic["at_close"] == "stop" else 60.0
    cell.traffic.update(rate_windows_per_s=rate, segment_windows_per_s=rate)
    return cell


def _run(cell: harness.Cell, capture=None) -> dict:
    seconds = 1.0 if cell.traffic["at_close"] == "stop" else 2.5
    return harness.run_cell(cell, 2**31 + 7, seconds, False, time.perf_counter(),
                            capture=capture)


@pytest.fixture
def fresh_jit():
    """Each run traces the engine anew, so a planted fault takes effect."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_control_is_not(name, fresh_jit):
    import calibrate

    # The worst node's gap grows with the fleet: at a few nodes the control
    # can read below limits set at the cells' own sizes.
    cell = _small(name, nodes=64)
    cap: dict = {}
    res = _run(cell, capture=cap)
    assert res["correct"], res["checks"]
    numbers = calibrate.control_numbers(
        cap["inputs"], cap["reference"], cell.config["profiler"]["step_windows"],
        cap["reports"] is not None,
    )
    import correctness

    ok, checks = correctness.judge(numbers, correctness.load_limits(cell.config))
    assert not ok, checks


def _state_unchanged(monkeypatch):
    from repro.core.engine import streaming

    monkeypatch.setattr(streaming, "kalman_step_gram", lambda st, inp, cfg: (st, st.x))


def _half_feed(monkeypatch):
    from repro.core.engine import streaming

    fold = streaming.fold_step_valid

    def left_out(step):
        step = fold(step)
        b = step.c.shape[0]
        keep = (np.arange(b) < b // 2).astype(np.float32)
        return step._replace(
            c=step.c * keep[:, None], w=step.w * keep, a=step.a * keep[:, None],
            lat_sum=step.lat_sum * keep[:, None], lat_sumsq=step.lat_sumsq * keep[:, None],
        )

    monkeypatch.setattr(streaming, "fold_step_valid", left_out)


def _answer_altered(monkeypatch):
    from repro.core.engine import streaming

    split = streaming._conserved_split

    def altered(raw, w, delta):
        tp, ua = split(raw, w, delta)
        return tp.at[0].multiply(1.01), ua

    monkeypatch.setattr(streaming, "_conserved_split", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_feed, _answer_altered],
                         ids=["state_unchanged", "half_feed", "answer_altered"])
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch, fresh_jit):
    fault(monkeypatch)
    res = _run(_small(CELLS[0]))
    assert not res["correct"], res["checks"]


def test_cell_the_harness_does_not_drive_fails_before_it_runs(tmp_path):
    import json

    with open(harness.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cfg_file = harness.ROOT / bench["configs"][0]["file"]
    with open(cfg_file) as f:
        cfg = json.load(f)
    bench["workloads"][0]["chips"] = 4
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    cell = harness.load_cell(bench["workloads"][0]["name"], bench_file)
    with pytest.raises(harness.BenchError, match="chips"):
        harness.fleet_mesh(cell.chips, cfg["nodes"])

    cfg["ring_buffer_windows"] = 64
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    bench["configs"][0]["file"] = str(tmp_path / "cfg.json")
    bench_file.write_text(json.dumps(bench))
    with pytest.raises(harness.BenchError, match="not driven"):
        harness.load_cell(bench["workloads"][0]["name"], bench_file)
