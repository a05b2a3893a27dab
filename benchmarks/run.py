"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full | --smoke] [--only fig6,...]

``--smoke`` runs every module at tiny B/M/T shapes (seconds, not minutes) —
the CI rot gate: each module must still import, execute, and emit
well-formed scalar metrics.  Prints one line per metric and writes
experiments/bench_results.json.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import math
import os
import time
import traceback

MODULES = [
    ("fig2_signal_quality", "Fig 2a: sensor pathology"),
    ("fig3_isolated_energy", "Fig 3: isolation invalid as ground truth"),
    ("fig5_sync", "Fig 5: skew correction"),
    ("fig6_marginal_validation", "Fig 6 + Table 3: marginal-energy validation"),
    ("fig7_symmetry", "Fig 7: symmetry + latency-variance"),
    ("fig8_total_error", "Fig 8: total-error"),
    ("fig9_pricing_variance", "Fig 9: pricing stability"),
    ("fig10_capping", "Fig 10: software power capping"),
    ("fig11_neighbors", "Fig 11: noisy neighbors"),
    ("profiler_overhead", "Perf: fleet profiler throughput"),
    ("streaming_overhead", "Perf: streaming engine per-tick overhead"),
    ("sharded_fleet", "Perf: mesh-sharded fleet scaling"),
    ("ragged_fleet", "Perf: ragged-fleet padding overhead vs rag ratio"),
    ("combined_fleet", "Perf: combined-mode (§4.3) chip/rest split overhead"),
    ("ingest_pipeline", "Perf: telemetry ingest — batched front-end + prefetch overlap"),
    ("control_loop", "Closed-loop control: cap overshoot, deferral cost, retrain recovery"),
    ("hetero_fleet", "Serving: mixed-platform fleet — one batch, 1e-5 pin + zero-retrace gate"),
    ("slot_serving", "Serving: slot-pool churn — ticks/sec + zero-retrace gate"),
    ("kernel_bench", "Perf: kernel path"),
]

# Engine hot paths whose jit caches are snapshotted around every module:
# each smoke result carries a ``_jit_traces`` count (compiles the module
# triggered on the serving/streaming paths), and the gate below turns the
# tests' ad-hoc retrace guards into a fleet-wide CI invariant.
_TRACKED_JITS = (
    ("repro.core.batched_engine", "fleet_step"),
    ("repro.core.batched_engine", "fleet_stream_reset_slots"),
    ("repro.core.batched_engine", "_bucket_init_solve"),
)


def _jit_cache_total() -> int | None:
    """Summed jit-cache size of the tracked engine entry points (None when
    the private counter is unavailable — the gate then rides only the
    modules' own ``retraces_after_warmup`` metrics)."""
    total = 0
    try:
        for mod_name, fn_name in _TRACKED_JITS:
            fn = getattr(importlib.import_module(mod_name), fn_name)
            total += int(fn._cache_size())
    except Exception:
        return None
    return total


def _well_formed(metrics: dict) -> bool:
    """A benchmark result is well-formed when it is a dict of scalar
    metrics that survives a *strict* JSON round-trip: NaN and Inf are
    rejected outright (a metric that went 0/0 is exactly the silent rot
    the smoke gate exists to catch; deliberately-absent measurements like
    fig6's edge RAPL only appear outside smoke mode)."""
    if not isinstance(metrics, dict) or not metrics:
        return False
    for k, v in metrics.items():
        if not isinstance(k, str):
            return False
        if isinstance(v, bool) or v is None:
            continue
        if isinstance(v, (int, float)):
            if isinstance(v, float) and not math.isfinite(v):
                return False
            continue
        if not isinstance(v, str):
            return False
    return True


def main() -> None:
    """CLI: run registered benchmarks and write the strict-JSON artifact."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale durations")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny shapes, seconds not minutes (CI rot gate); validates "
        "that every module emits well-formed JSON metrics",
    )
    ap.add_argument("--only", default="", help="comma-separated module prefixes")
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    only = [s for s in args.only.split(",") if s]
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    results, failures = {}, 0
    for mod_name, title in MODULES:
        if only and not any(mod_name.startswith(o) for o in only):
            continue
        print(f"\n=== {mod_name}: {title} ===", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            kwargs = {"quick": not args.full}
            if args.smoke:
                # Every module must opt in to smoke shapes; a silent
                # quick-scale fallback would erode the seconds-not-minutes
                # contract the CI gate depends on.
                if "smoke" not in inspect.signature(mod.run).parameters:
                    raise TypeError(
                        f"benchmarks.{mod_name}.run lacks the smoke= "
                        "parameter; every registered module must support "
                        "--smoke (tiny shapes)"
                    )
                kwargs["smoke"] = True
            jit_before = _jit_cache_total()
            metrics = mod.run(**kwargs)
            if args.smoke and not _well_formed(metrics):
                raise ValueError(f"{mod_name}.run returned malformed metrics: {metrics!r}")
            metrics["_seconds"] = round(time.time() - t0, 1)
            jit_after = _jit_cache_total()
            metrics["_jit_traces"] = (
                jit_after - jit_before
                if jit_before is not None and jit_after is not None else -1
            )
            # The fleet-wide retrace gate: any module that declares a
            # post-warmup retrace count must report zero — an engine path
            # that recompiles after its per-bucket warmup is a serving
            # regression, not a slow benchmark.
            retraces = metrics.get("retraces_after_warmup")
            if args.smoke and retraces is not None and int(retraces) > 0:
                raise ValueError(
                    f"{mod_name} retraced after warmup "
                    f"({retraces} extra jit traces) — the zero-retrace "
                    "serving invariant is broken"
                )
            results[mod_name] = metrics
            for k, v in metrics.items():
                print(f"  {k:36s} {v:.6g}" if isinstance(v, float) else f"  {k:36s} {v}")
        except Exception:
            failures += 1
            traceback.print_exc()
            results[mod_name] = {"error": True}
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/bench_results.json", "w") as f:
        json.dump(results, f, indent=1)
    print(f"\nwrote experiments/bench_results.json ({len(results)} modules, {failures} failures)")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
