#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are looked up by name (``BENCHMARK.json``,
``benchmarks/chip/configs``, ``traffic`` and ``metrics``; ``harness.py``
says how).  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.  Diagnostics, the set-up split and the compared numbers beside
their limits go to standard error; the last line of standard output is the
result as one JSON object.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(
            f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
            f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr,
        )
        return 1
    harness.log(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache {cache}")
    # run_cell logs the compared numbers beside their limits last.
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
