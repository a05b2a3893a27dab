"""From a JAX profiler trace (``.xplane.pb``) to the benchmark's readings.

The device planes (``/device:TPU:<n>``) carry one line of program
executions (``XLA Modules``) and one of the operations inside them
(``XLA Ops``); the host plane (``/host:CPU``) carries one line per thread
with its annotated spans (``jax.profiler.TraceAnnotation`` and JAX's own).
All times are nanoseconds on the trace's one clock.

``reduce`` cuts everything to the benchmark's window, which the pacer marks
with two host spans (``WINDOW_OPEN`` ending where the window opens,
``WINDOW_CLOSE`` starting where it closes), and returns:

- ``busy_s``: the union of operation intervals on the device, averaged over
  the devices that ran anything;
- ``window_s``: the window's length;
- ``programs``: per program name, executions and device seconds;
- ``ops``: per operation name, device seconds;
- ``gaps``: the idle intervals of the first device, each with the host span
  that overlapped it most (what the host was doing while the chip idled).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import numpy as np

WINDOW_OPEN = "bench.window_open"
WINDOW_CLOSE = "bench.window_close"
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


@dataclasses.dataclass
class Trace:
    """Events of one trace: (name, start_ns, end_ns) per device and host."""

    modules: dict[str, list[tuple[str, float, float]]]  # device -> program runs
    ops: dict[str, list[tuple[str, float, float]]]      # device -> operations
    host: list[tuple[str, float, float]]                # every host thread


def program_name(event: str) -> str:
    """``jit__fleet_step_impl(6675...)`` -> ``jit__fleet_step_impl``."""
    return re.sub(r"\(\d+\)$", "", event)


def op_name(event: str) -> str:
    """``%copy-done.3 = f32[...] copy-done(...)`` -> ``copy-done.3``."""
    return event.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules: dict = defaultdict(list)
    ops: dict = defaultdict(list)
    host = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    dest, name = modules[plane.name], program_name
                elif line.name in OP_LINES:
                    dest, name = ops[plane.name], op_name
                else:
                    continue
                dest.extend(
                    (name(e.name), e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
    return Trace(modules=dict(modules), ops=dict(ops), host=host)


def window(trace: Trace) -> tuple[float, float]:
    """(open_ns, close_ns) from the pacer's two marker spans."""
    opens = [end for name, _, end in trace.host if name == WINDOW_OPEN]
    closes = [start for name, start, _ in trace.host if name == WINDOW_CLOSE]
    if not opens or not closes:
        raise ValueError("trace lacks the window's marker spans")
    return max(opens), min(closes)


def _clip(events, lo: float, hi: float) -> np.ndarray:
    """(n, 2) intervals of ``events`` cut to [lo, hi], empty ones dropped."""
    if not events:
        return np.zeros((0, 2))
    iv = np.asarray([(s, e) for _, s, e in events], np.float64)
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted intervals covering the same time as ``iv``."""
    if iv.shape[0] == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _per_name(events, lo: float, hi: float) -> dict[str, list[float]]:
    """name -> [count, seconds] of the events that start inside the window."""
    acc: dict = defaultdict(lambda: [0, 0.0])
    for name, s, e in events:
        if lo <= s < hi:
            acc[name][0] += 1
            acc[name][1] += (min(e, hi) - s) * 1e-9
    return dict(acc)


def _label_gaps(gaps: np.ndarray, host, limit: int) -> list[tuple[str, float]]:
    """The ``limit`` longest gaps, each named by the host span overlapping it most."""
    order = np.argsort(gaps[:, 0] - gaps[:, 1])[:limit] if gaps.shape[0] else []
    spans = [(n, s, e) for n, s, e in host if n not in (WINDOW_OPEN, WINDOW_CLOSE)]
    starts = np.asarray([s for _, s, _ in spans]) if spans else np.zeros(0)
    ends = np.asarray([e for _, _, e in spans]) if spans else np.zeros(0)
    out = []
    for i in order:
        lo, hi = gaps[i]
        ov = np.minimum(ends, hi) - np.maximum(starts, lo)
        # A span enclosing the whole gap names it only if no shorter one does.
        inside = (ov > 0) & (ends - starts < (hi - lo) * 50)
        pick = np.flatnonzero(inside if inside.any() else ov > 0)
        if pick.size:
            best = pick[np.argmax(ov[pick])]
            name = spans[best][0]
        else:
            name = "host: no span"
        out.append((name, float(hi - lo) * 1e-9))
    return out


def reduce(trace: Trace, top: int = 10) -> dict:
    """The window's device busy time, programs, operations and idle gaps."""
    lo, hi = window(trace)
    devices = sorted(set(trace.ops) | set(trace.modules))
    busy = {}
    for dev in devices:
        events = trace.ops.get(dev) or trace.modules.get(dev, [])
        busy[dev] = union(_clip(events, lo, hi))
    active = [d for d in devices if busy[d].shape[0]]
    busy_s = (
        float(np.mean([np.sum(busy[d][:, 1] - busy[d][:, 0]) for d in active])) * 1e-9
        if active else 0.0
    )
    programs: dict = defaultdict(lambda: [0, 0.0])
    ops: dict = defaultdict(float)
    for dev in devices:
        for name, (n, s) in _per_name(trace.modules.get(dev, []), lo, hi).items():
            programs[name][0] += n
            programs[name][1] += s
        for name, (_, s) in _per_name(trace.ops.get(dev, []), lo, hi).items():
            ops[name] += s
    gaps = np.zeros((0, 2))
    if active:
        b = busy[active[0]]
        edges = np.concatenate([[lo], b.ravel(), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) * 1e-9,
        "devices": len(active),
        "programs": {k: {"count": v[0], "seconds": v[1]} for k, v in programs.items()},
        "ops": dict(ops),
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in _label_gaps(gaps, trace.host, top)],
        },
    }
