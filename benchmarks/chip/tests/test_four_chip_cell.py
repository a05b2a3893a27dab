"""The four-chip cell through the harness, on four host CPU devices.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

``table2_server_1024.stream_paced_4chip`` cut to 8 nodes (two per device)
runs through ``harness.run_cell`` with the ``FleetMesh`` the harness builds
from the cell's ``chips``, so the exchange between chips that the one-chip
cells of ``test_correctness.py`` leave out is on the timed path: each tick's
feed placed into four node shards, the sharded step, the attribution
gathered back.  The four devices exist only in a child process
(``--xla_force_host_platform_device_count=4`` must be set before JAX
starts).  Pinned: the run comes out correct, and comes out not correct
when one device's shard of the feed is zeroed inside the sharded step.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
CELL = "table2_server_1024.stream_paced_4chip"

CHILD = f"""
import copy, json, sys, time
sys.path.insert(0, {str(HERE.parents[1] / "src")!r})
sys.path.insert(0, {str(HERE)!r})

import jax
import jax.numpy as jnp

import harness
from repro.core.engine import streaming

assert len(jax.devices()) == 4, jax.devices()
cell = copy.deepcopy(harness.load_cell({CELL!r}))
cell.config["nodes"] = 8
cell.traffic.update(rate_windows_per_s=60.0, segment_windows_per_s=60.0)

if sys.argv[1] == "shard_zeroed":
    fold = streaming.fold_step_valid

    def zeroed(step):
        # Runs inside the shard_map, once per device on its own node block.
        step = fold(step)
        keep = (jax.lax.axis_index("node") != 1).astype(jnp.float32)
        return step._replace(c=step.c * keep, w=step.w * keep, a=step.a * keep,
                             lat_sum=step.lat_sum * keep, lat_sumsq=step.lat_sumsq * keep)

    streaming.fold_step_valid = zeroed

mesh = harness.fleet_mesh(cell.chips, cell.config["nodes"])
res = harness.run_cell(cell, 2**31 + 11, 2.5, False, time.perf_counter())
print(json.dumps({{"devices": mesh.num_devices, "correct": res["correct"],
                  "checks": res["checks"]}}))
"""


def _run(variant: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(CHILD), variant],
                         capture_output=True, text=True, timeout=900, env=env,
                         cwd=HERE.parents[1])
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("variant,correct", [("sound", True), ("shard_zeroed", False)])
def test_four_chip_cell_is_judged_across_the_shards(variant, correct):
    out = _run(variant)
    assert out["devices"] == 4
    assert out["correct"] is correct, out["checks"]
