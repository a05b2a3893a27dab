#!/usr/bin/env python3
"""Readings that a configuration's correctness limits are set from.

    python benchmarks/chip/calibrate.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed, in one process: one run of the cell (a short window at the
cell's own size and load), the compared numbers of the program, and the
same numbers for the control, which is the reference computed one precision
below the configuration's (``reference.py``, ``precision="high"``) over the
same inputs.  One JSON line per seed goes to standard output.  A limit lies
above every program reading and below every control reading (PERF.md).
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))


def control_numbers(inputs, ref, step_windows: int, finalized: bool) -> dict:
    """The compared numbers of the control against the reference, over
    every tick the program's run received (and its reports, where it
    finalized)."""
    import correctness
    import numpy as np
    import reference

    prof, traces, num_fns, duration, idle_w, raw, last = inputs
    ctl = reference.reference_ticks(prof, traces, num_fns, duration, idle_w, raw, last,
                                    precision="high")
    received = {"t": np.arange(ctl.t0, ctl.t0 + ctl.x.shape[0]), "x": ctl.x,
                "tick_power": ctl.tick_power, "unattributed": ctl.unattributed}
    if not finalized:
        return correctness.gaps(received, ref, step_windows)
    return correctness.gaps(
        received, ref, step_windows,
        reference.report(ctl, traces, num_fns, step_windows),
        reference.report(ref, traces, num_fns, step_windows),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 1
    n_w = cell.config["profiler"]["step_windows"]
    for seed in args.seeds:
        cap: dict = {}
        res = harness.run_cell(cell, seed, args.seconds, False, time.perf_counter(), capture=cap)
        row = {
            "seed": seed,
            "program": cap["numbers"],
            "control": control_numbers(cap["inputs"], cap["reference"], n_w,
                                       cap["reports"] is not None),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
