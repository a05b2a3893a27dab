"""The trace reduction against a trace recorded on a v5e chip.

``testdata/table2_server_paced.xplane.pb`` is a traced window of the
``table2_server`` configuration, paced at half its capacity for 0.5 s
(``sweep.py --trace-dir`` on one TPU v5e): 26 ticks, seven device
programs per tick.  Run with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

FIXTURE = HERE / "testdata" / "table2_server_paced.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load(str(FIXTURE)))


def test_union_merges_overlaps_and_keeps_gaps():
    iv = np.asarray([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0], [8.0, 9.0]])
    np.testing.assert_array_equal(
        trace_reduce.union(iv), [[0.0, 4.0], [5.0, 7.0], [8.0, 9.0]]
    )


def test_names_are_shortened():
    assert trace_reduce.program_name("jit__fleet_step_impl(6675227769985979974)") == (
        "jit__fleet_step_impl"
    )
    assert trace_reduce.op_name("%copy-done.3 = f32[256] copy-done(%x)") == "copy-done.3"


def test_window_busy_time_and_programs(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.500076264, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.000228776, abs=1e-9)
    progs = reduced["programs"]
    assert set(progs) == {
        "jit_shared_principal_contribution", "jit_combined_rest_target", "jit_dynamic_slice",
        "jit_squeeze", "jit_broadcast_in_dim", "jit_concatenate", "jit__fleet_step_impl",
    }
    assert all(v["count"] == 26 for v in progs.values())
    assert progs["jit__fleet_step_impl"]["seconds"] == pytest.approx(0.000158193, abs=1e-12)


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops[0][0] == "copy-done"
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = reduced["breakdown"]["idle_gaps"]
    assert len(gaps) == 10
    # Paced at half its capacity, the controller mostly waits for the next
    # window: the longest idle gaps are the pacer's waits.
    assert gaps[0][0] == "bench.pacer_wait"
    assert gaps[0][1] == pytest.approx(0.016909978, abs=1e-9)


def test_metric_readers(reduced):
    ctx = {"reduced": reduced, "ticks": 26, "nodes": 256, "cell": "fixture"}
    read = harness.metric_reader
    assert read("device_idle_share.paced")(ctx) == pytest.approx(99.95425177788483)
    assert read("fleet_step_device_us.paced")(ctx) == pytest.approx(6.0843461538461545)
    assert read("device_programs_per_tick.paced")(ctx) == 7.0
    empty = {"reduced": dict(reduced, devices=0, programs={}), "ticks": 0}
    assert all(
        read(m)(empty) is None
        for m in ("device_idle_share", "fleet_step_device_us", "device_programs_per_tick")
    )
