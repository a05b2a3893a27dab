"""Each tick's feed is placed in its node shards under a ``FleetMesh``.

A combined-mode ``profile_fleet`` stream over 16 server nodes runs on a
4-way mesh of host CPU devices and, for comparison, with ``mesh=None``.
The four devices exist only in a child process
(``--xla_force_host_platform_device_count=4`` must be set before JAX
starts), so this test runs everywhere, the one-device tier-1 run included,
and is not a ``multidevice`` test.  Pinned:

- the sharded stream emits the unsharded stream's ticks (``x``,
  ``tick_power``, ``unattributed``, ``target``), and so does a pure-mode
  ragged fleet of 8 edge nodes, half of which end a Kalman step early;
- every per-tick input of ``fleet_step`` arrives in ``mesh.node_sharding()``
  (the combined rest target too, since its own inputs arrive so);
- under a profiler trace, each tick's feed goes through one
  ``faasmeter.put`` span with ``shards=4``, and its attribution comes back
  through ``faasmeter.pull`` spans with ``shards=4``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
NODES, DURATION, INIT, STEP = 16, 150.0, 60, 30
TICKS = list(range(INIT, int(DURATION)))

CHILD = f"""
import json, os, sys, tempfile
from pathlib import Path

import jax
import numpy as np

from repro.core import engine
from repro.core.profiler import ProfilerConfig
from repro.distributed.sharding import fleet_mesh
from repro.serving.control_plane import EnergyFirstControlPlane
from repro.telemetry.simulator import SimulatorConfig
from repro.workload.azure import WorkloadConfig, generate_trace
from repro.workload.functions import paper_functions

assert len(jax.devices()) == 4, jax.devices()
reg = paper_functions()
traces = [generate_trace(reg, WorkloadConfig(duration_s={DURATION}, load=1.0, seed=s))
          for s in range({NODES})]
mesh = fleet_mesh({NODES})
node = mesh.node_sharding()

step_fn = engine.fleet_step
placed = []


def spy(state, step, **kw):
    placed.append(all(getattr(leaf, "sharding", None) == node
                      for leaf in step if leaf is not None))
    return step_fn(state, step, **kw)


engine.fleet_step = spy


def run(m, platform="server", mode="combined", fleet=traces):
    cp = EnergyFirstControlPlane(
        reg, SimulatorConfig(platform=platform, seed=0),
        ProfilerConfig(init_windows={INIT}, step_windows={STEP}, mode=mode),
    )
    ticks = []
    cp.profile_fleet(fleet, seeds=list(range(100, 100 + len(fleet))), mode=mode, mesh=m,
                     on_tick=lambda tk, trackers: ticks.append(tk))
    return {{f: np.stack([getattr(tk, f) for tk in ticks]).tolist()
             for f in ("x", "tick_power", "unattributed", "target")}} | {{
        "t": [tk.t for tk in ticks]}}


# A pure-mode ragged fleet: the feed carries a zero chip column, the idle
# watts and the liveness flag.
ragged = [generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=s))
          for s, d in enumerate([{DURATION}] * 4 + [{DURATION} - {STEP}] * 4)]
out = {{"ragged_none": run(None, "edge", "pure", ragged),
        "ragged_mesh": run(fleet_mesh(8), "edge", "pure", ragged)}}
placed.clear()
out |= {{"none": run(None), "placed_none": placed[:]}}
placed.clear()
with tempfile.TemporaryDirectory() as tmp:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(tmp, profiler_options=opts):
        out["mesh"] = run(mesh)
    (path,) = Path(tmp).rglob("*.xplane.pb")
    spans = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, dict(e.stats)] for e in line.events
                          if e.name in ("faasmeter.put", "faasmeter.pull")]
out["placed_mesh"] = placed
out["spans"] = spans
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def streams():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(CHILD)], capture_output=True,
                         text=True, timeout=600, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sharded_stream_emits_the_unsharded_ticks(streams):
    mesh, none = streams["mesh"], streams["none"]
    assert mesh["t"] == none["t"] == TICKS
    for f in ("x", "tick_power", "unattributed", "target"):
        # Each node's math is the same on its shard (the CPU gives equal
        # bits), but XLA compiles the 4-node shard's program apart from the
        # 16-node one and may order a float32 reduction differently: a few
        # roundings of 6e-8 each, relative to the node's watts, well
        # inside 1e-6.
        got, want = np.asarray(mesh[f]), np.asarray(none[f])
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * np.abs(want).max(),
                                   err_msg=f)


def test_ragged_pure_sharded_stream_emits_the_unsharded_ticks(streams):
    mesh, none = streams["ragged_mesh"], streams["ragged_none"]
    assert mesh["t"] == none["t"] == TICKS
    for f in ("x", "tick_power", "unattributed", "target"):
        # As above; the ended nodes' rows are exactly zero on both paths.
        got, want = np.asarray(mesh[f]), np.asarray(none[f])
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * np.abs(want).max(),
                                   err_msg=f)
    assert not np.asarray(mesh["tick_power"])[-1, 4:].any()


def test_every_fleet_step_input_arrives_in_its_node_shards(streams):
    assert len(streams["placed_mesh"]) == len(TICKS)
    assert all(streams["placed_mesh"])
    # Without a mesh nothing is placed in node shards.
    assert len(streams["placed_none"]) == len(TICKS) and not any(streams["placed_none"])


def test_each_tick_puts_its_feed_into_four_shards(streams):
    puts = [meta for name, meta in streams["spans"] if name == "faasmeter.put"]
    fed = [meta for meta in puts if meta["site"].startswith("dispatch.")]
    # The whole feed in one put per tick, split over the four devices.
    assert sorted(m["tick"] for m in fed) == TICKS
    assert all(m["site"] == "dispatch.feed" and m["shards"] == 4 for m in fed)
    # The principal column is computed from its two CPU fractions on the
    # default device and pulled straight back: never part of the step's feed.
    pushed = [meta for meta in puts if meta["site"] in ("push.cp_frac", "push.sys_frac")]
    assert len(pushed) == 2 * int(DURATION) and all(m["shards"] == 1 for m in pushed)
    # The attribution comes back from the four shards it was computed on.
    emitted = [meta for name, meta in streams["spans"]
               if name == "faasmeter.pull" and meta["site"].startswith("emit.")]
    assert len(emitted) == 4 * len(TICKS) and all(m["shards"] == 4 for m in emitted)
