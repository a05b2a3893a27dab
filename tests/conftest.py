"""Shared fixtures, and the runner of the ``multidevice`` tests.

The suite runs on whatever devices the process sees; no fixture sets an
XLA device flag, so the one-device tests test ``mesh="auto"`` on one
device.  A ``multidevice`` test needs a mesh of more than one device.  In
a process that sees one device, the ``multidevice`` items of a test file
run together in one child ``pytest`` process that sees eight host CPU
devices (``--xla_force_host_platform_device_count`` must be set before JAX
starts), and each item reports the outcome it had there.  A process that
already sees more than one device runs them in place.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from repro.workload.azure import WorkloadConfig, generate_trace
from repro.workload.functions import paper_functions

_DEVICE_FLAG = "--xla_force_host_platform_device_count"
_CHILD_DEVICES = 8
_CHILD_TIMEOUT_S = 900

# Runs pytest on the node ids in argv[2:] and writes each one's outcome
# to argv[1]: {nodeid: [outcome, text]}, the worst of its setup, call and
# teardown (failed > skipped > passed), a failure with its report text and
# a skip with its reason.
_CHILD = """
import json, sys
import pytest

RANK = {"passed": 0, "skipped": 1, "failed": 2}

class Record:
    def __init__(self):
        self.out = {}

    def pytest_runtest_logreport(self, report):
        if report.failed:
            got = ("failed", f"{report.when}:\\n{report.longreprtext}")
        elif report.skipped:
            lr = report.longrepr
            got = ("skipped", lr[2] if isinstance(lr, tuple) else str(lr))
        else:
            got = ("passed", "")
        prev = self.out.get(report.nodeid)
        if prev is None or RANK[got[0]] > RANK[prev[0]]:
            self.out[report.nodeid] = got

rec = Record()
pytest.main(sys.argv[2:], plugins=[rec])
with open(sys.argv[1], "w") as f:
    json.dump(rec.out, f)
"""


class _ChildFailed(Exception):
    """A ``multidevice`` test failed in the child process."""


class ChildRunItem(pytest.Item):
    """A ``multidevice`` test that runs in its file's child process."""

    def __init__(self, *, origin, batch, **kw):
        super().__init__(**kw)
        self._origin = origin
        self._batch = batch

    def runtest(self):
        outcome, text = self._batch.outcome(self.nodeid)
        if outcome == "skipped":
            pytest.skip(text)
        if outcome == "failed":
            raise _ChildFailed(text)

    def repr_failure(self, excinfo):
        if isinstance(excinfo.value, _ChildFailed):
            return f"[multidevice child, {_CHILD_DEVICES} host devices] {excinfo.value}"
        return super().repr_failure(excinfo)

    def reportinfo(self):
        return self._origin.reportinfo()


class _ChildBatch:
    """The ``multidevice`` items of one test file and their one child run."""

    def __init__(self, rootdir):
        self.rootdir = rootdir
        self.nodeids = []
        self._outcomes = None
        self._missing = ""

    def outcome(self, nodeid):
        if self._outcomes is None:
            self._outcomes = self._run()
        return self._outcomes.get(nodeid) or ("failed", self._missing)

    def _run(self):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PYTEST_XDIST", "PYTEST_CURRENT_TEST"))}
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = " ".join(
            filter(None, [env.get("XLA_FLAGS"), f"{_DEVICE_FLAG}={_CHILD_DEVICES}"])
        )
        fd, out = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        try:
            res = subprocess.run(
                [sys.executable, "-c", _CHILD, out, "-q", "-p", "no:xdist",
                 "-p", "no:cacheprovider", *self.nodeids],
                cwd=self.rootdir, env=env, capture_output=True, text=True,
                timeout=_CHILD_TIMEOUT_S,
            )
            self._missing = (
                f"no report from the child process (exit {res.returncode}):\n"
                f"{res.stdout[-3000:]}\n{res.stderr[-3000:]}"
            )
            with open(out) as f:
                return json.load(f)
        except (subprocess.SubprocessError, OSError, ValueError) as e:
            # A crashed or hung child: each of its items fails with this.
            self._missing = f"{self._missing}\n{e!r}"
            return {}
        finally:
            os.unlink(out)


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(config, items):
    """Hand each selected ``multidevice`` item to its file's child run when
    this process sees a single device (and is not such a child itself)."""
    import jax

    if len(jax.devices()) > 1 or _DEVICE_FLAG in os.environ.get("XLA_FLAGS", ""):
        return
    batches = {}
    for i, item in enumerate(items):
        if item.get_closest_marker("multidevice") is None:
            continue
        batch = batches.setdefault(item.path, _ChildBatch(config.rootpath))
        batch.nodeids.append(item.nodeid)
        items[i] = ChildRunItem.from_parent(
            item.parent, name=item.name, origin=item, batch=batch
        )


@pytest.fixture(scope="session")
def registry():
    return paper_functions()


@pytest.fixture(scope="session")
def short_trace(registry):
    """~3 minute, 4-function Poisson trace (fast profiler tests)."""
    sub = registry
    return generate_trace(sub, WorkloadConfig(duration_s=180.0, load=1.0, seed=7))


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
