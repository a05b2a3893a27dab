#!/usr/bin/env python3
"""Find a stream cell's highest sustained rate on the chip, in one process.

    python benchmarks/chip/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --probe-rate <windows/s> --fractions 0.7 0.8 0.9 1.0

First the cell's configuration is driven in overload at ``--probe-rate``
(well above capacity): the ticks it completes per second are its capacity
C.  Then the paced mix runs at each fraction of C; a rate is sustained when
all its due ticks are emitted within the window and later ticks wait no
longer than earlier ones (``trend_ms`` near 0).  One JSON line per run goes
to standard output.  The rates a cell's traffic file fixes come from such
a sweep (PERF.md).
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--probe-rate", type=float, required=True)
    ap.add_argument("--fractions", type=float, nargs="*", default=[])
    ap.add_argument("--trace-dir", default=None,
                    help="also run one traced paced window and keep its profile here")
    args = ap.parse_args(argv)

    import harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 1

    def one(mode: str, rate: float, trace_dir=None) -> dict:
        c = copy.deepcopy(cell)
        # "overload" stops the controller when the window closes; "paced"
        # streams the segment to its end.
        c.traffic.update(at_close="stop" if mode == "overload" else "drain",
                         rate_windows_per_s=rate, segment_windows_per_s=rate)
        # Either mode's end-to-end reading is reported, whichever the cell's.
        c.end_to_end = [m for m in c.end_to_end if m["name"] == "setup_s"]
        t = time.perf_counter()
        try:
            res = harness.run_cell(c, args.seed, args.seconds, trace_dir is not None, t,
                                   trace_dir=trace_dir)
            row = {"mode": mode, "rate": rate, "correct": res["correct"],
                   "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                   "diagnostics": res["diagnostics"], "device": res["device"]}
        except harness.BenchError as e:
            row = {"mode": mode, "rate": rate, "error": str(e)}
        print(json.dumps(row), flush=True)
        return row

    probe = one("overload", args.probe_rate)
    cap = probe.get("diagnostics", {}).get("ticks_per_s")
    if cap is None:
        return 1
    for f in args.fractions:
        one("paced", round(f * cap, 1))
    if args.trace_dir:
        # A short traced window: small enough to keep as a test fixture.
        args.seconds = 0.5
        one("paced", round(0.5 * cap, 1), trace_dir=args.trace_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
