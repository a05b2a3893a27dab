#!/usr/bin/env python3
"""Host self time per layer of the tick path, from the program's own spans.

The program marks the layers of its streaming tick path with ``faasmeter.*``
host spans (``repro.tracing``; docs/streaming.md lists them).  They land on
the profiler trace's host plane, one line per thread, on the clock of the
device planes that ``trace_reduce`` reads.  ``reduce`` cuts them to the
benchmark's window (``trace_reduce.window``) and returns:

- ``host_spans``: per span name (every ``faasmeter.*`` name, and the
  benchmark's ``bench.on_tick``), ``count`` (spans that start in the window),
  ``seconds`` (their time inside it) and ``self_seconds`` (that time less
  what the spans nested directly in them on the same thread cover).
  ``bench.on_tick`` is the benchmark's consumer, not the program's: it is
  carved out of the emit span that encloses it and reported on its own.
  JAX's own spans count inside whichever of these encloses them;
- ``main_s`` and ``unspanned_s``: on the ingesting thread (the one that
  carries ``faasmeter.session.dispatch``), the window and the part of it
  under none of those spans; None where no thread carries one.

So on that thread the self times of the spans plus ``unspanned_s`` add up
to ``main_s``.  ``per_tick`` turns the reduction into the per-tick readings
of each layer.  Run as a script, it runs one cell traced and prints them:

    python benchmarks/chip/host_spans.py --workload <cell> --seed <n> --seconds <s>

One JSON line goes to standard output: the run's end-to-end diagnostics,
its ``correct``, the per-tick readings, the five longest device->host
pulls in the window, each with its call site and tick, and the five spans
with the most self time (where a host stall sits).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

PREFIX = "faasmeter."
DISPATCH = "faasmeter.session.dispatch"
WAIT = "faasmeter.ingest.wait"
PULL = "faasmeter.pull"
PUT = "faasmeter.put"
ON_TICK = "bench.on_tick"

# reading -> (spans it sums, which of their times), in microseconds per tick
LAYERS = {
    "ingest_host_us": (("faasmeter.ingest.push",), "self_seconds"),
    "session_host_us": ((DISPATCH, "faasmeter.session.emit"), "self_seconds"),
    "fleet_step_host_us": (("faasmeter.engine.fleet_step",), "seconds"),
    "device_pull_us": ((PULL,), "seconds"),
    "device_put_us": ((PUT,), "seconds"),
    "tracker_host_us": (("faasmeter.control.trackers",), "self_seconds"),
}


def _carved(name: str) -> bool:
    return name.startswith(PREFIX) or name == ON_TICK


def load(path: str) -> list[list[tuple[str, float, float, dict]]]:
    """Every host event, per thread line: (name, start_ns, end_ns, stats);
    stats are read for the spans this module reduces only."""
    from jax.profiler import ProfileData

    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            threads.extend(
                [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  dict(e.stats) if _carved(e.name) else {}) for e in line.events]
                for line in plane.lines
            )
    return threads


def window(threads) -> tuple[float, float]:
    """The benchmark's window, from the pacer's marker spans."""
    flat = [ev[:3] for line in threads for ev in line]
    return trace_reduce.window(trace_reduce.Trace(modules={}, ops={}, host=flat))


def _nest(line, lo: float, hi: float):
    """[name, start_ns, seconds, self seconds, top level, stats] of each
    carved span of one thread, cut to [lo, hi]."""
    out, stack = [], []
    for name, s, e, meta in sorted((ev for ev in line if _carved(ev[0])),
                                   key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        sec = max(min(e, hi) - max(s, lo), 0.0) * 1e-9
        if stack:
            out[stack[-1][0]][3] -= sec
        out.append([name, s, sec, sec, not stack, meta])
        stack.append((len(out) - 1, e))
    return out


def reduce(threads) -> dict:
    """Span counts, times and self times in the window; the ingesting
    thread's window and unspanned time."""
    lo, hi = window(threads)
    acc: dict = defaultdict(lambda: {"count": 0, "seconds": 0.0, "self_seconds": 0.0})
    ingest, most = None, 0
    for line in threads:
        spans = _nest(line, lo, hi)
        for name, s, sec, self_sec, _, _ in spans:
            acc[name]["count"] += int(lo <= s < hi)
            acc[name]["seconds"] += sec
            acc[name]["self_seconds"] += self_sec
        n = sum(1 for sp in spans if sp[0] == DISPATCH and lo <= sp[1] < hi)
        if n > most:
            ingest, most = spans, n
    main_s = unspanned_s = None
    if ingest is not None:
        main_s = (hi - lo) * 1e-9
        unspanned_s = main_s - sum(sp[2] for sp in ingest if sp[4])
    return {"host_spans": dict(acc), "main_s": main_s, "unspanned_s": unspanned_s}


def per_tick(red: dict, ticks: int) -> dict:
    """Each layer's reading per tick (us; pulls as a count); a reading whose
    spans the trace lacks is left out."""
    spans = red["host_spans"]
    out = {}
    if not ticks:
        return out
    for reading, (names, key) in LAYERS.items():
        if any(n in spans for n in names):
            out[reading] = 1e6 * sum(spans[n][key] for n in names if n in spans) / ticks
    if PULL in spans:
        out["device_pulls_per_tick"] = spans[PULL]["count"] / ticks
    if red["unspanned_s"] is not None:
        out["host_unspanned_us"] = 1e6 * red["unspanned_s"] / ticks
    return out


def longest(threads, name: str | None = PULL, n: int = 5) -> list[dict]:
    """The ``n`` spans named ``name`` that start in the window with the most
    self time, with their stats and self ms.  Where ``name`` is None: of
    every carved span but the ingest waits (time waited is no stall)."""
    lo, hi = window(threads)
    spans = [sp for line in threads for sp in _nest(line, lo, hi)
             if (sp[0] == name if name else sp[0] != WAIT) and lo <= sp[1] < hi]
    spans.sort(key=lambda sp: -sp[3])
    return [dict(sp[5], **({} if name else {"span": sp[0]}), ms=sp[3] * 1e3)
            for sp in spans[:n]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", default=None, help="keep the trace here")
    args = ap.parse_args(argv)

    import tempfile

    import harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("host_spans.py: needs a TPU", file=sys.stderr)
        return 1
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = args.trace_dir or tmp
        res = harness.run_cell(cell, args.seed, args.seconds, True, T0, trace_dir=trace_dir)
        threads = load(str(sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]))
    red = reduce(threads)
    ticks = red["host_spans"].get(ON_TICK, {}).get("count", 0)
    row = {
        "workload": cell.name, "seed": args.seed, "correct": res["correct"],
        "diagnostics": res["diagnostics"], "device": res["device"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()}, "ticks": ticks,
        "per_tick": per_tick(red, ticks), "main_s": red["main_s"],
        "unspanned_s": red["unspanned_s"], "host_spans": red["host_spans"],
        "longest_pulls": longest(threads), "longest_self": longest(threads, None),
    }
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
