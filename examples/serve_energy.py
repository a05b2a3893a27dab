"""End-to-end driver: energy-first SERVING of real models (the paper's kind).

Three reduced architectures run as FaaS function classes on this host —
real jitted prefill+decode compute, wall-clock metered — then the measured
invocation trace flows through telemetry simulation -> FaasMeter profiling
-> energy footprints -> pricing, the paper's Fig. 1 pipeline::

    PYTHONPATH=src python examples/serve_energy.py [--archs internlm2-1.8b,xlstm-350m] \
        [--requests 24 --batch 2 --seq 64 --gen-steps 4]
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.registry import get_config
from repro.configs.shapes import ShapeConfig
from repro.core.profiler import FaasMeterProfiler, ProfilerConfig
from repro.core.pricing import PricingConfig, price_report
from repro.models import build
from repro.models.common import materialize
from repro.serving.control_plane import MeteredServer
from repro.serving.engine import ServeEngine
from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
from repro.workload.functions import FunctionRegistry, FunctionSpec

import jax.numpy as jnp


def main() -> None:
    """CLI: continuous-batching serving smoke across model-zoo architectures."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default="internlm2-1.8b,xlstm-350m,olmoe-1b-7b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--gen-steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    archs = args.archs.split(",")
    shape = ShapeConfig("serve", args.seq, args.batch, "prefill")
    server = MeteredServer()
    rng = np.random.default_rng(args.seed)

    print("== registering function classes (reduced configs, real compute) ==")
    for name in archs:
        cfg = get_config(name, reduced=True)
        api = build(cfg)
        params = materialize(api.params_def, jax.random.PRNGKey(args.seed))
        engine = ServeEngine(api, shape, params)
        batch = {}
        for k, sp in api.prefill_inputs(shape).items():
            if np.issubdtype(np.dtype(sp.dtype), np.integer):
                batch[k] = jnp.asarray(
                    rng.integers(0, cfg.vocab_size, size=sp.shape), jnp.int32
                )
            else:
                batch[k] = jnp.asarray(rng.standard_normal(sp.shape) * 0.1, sp.dtype)
        server.register(f"{name}/generate", engine, batch, steps=args.gen_steps)
        print(f"  {name}/generate registered")

    schedule = [
        (f"{archs[i % len(archs)]}/generate", 0.0) for i in range(args.requests)
    ]
    print(f"== serving {len(schedule)} requests ==")
    trace = server.serve(schedule, duration=60.0)
    lat = trace.end - trace.start
    print(f"   measured warm latencies: mean={lat.mean():.3f}s p95={np.quantile(lat, 0.95):.3f}s")

    # Meter the measured trace through the telemetry substrate + profiler.
    specs = []
    for i, name in enumerate(server.order):
        mask = trace.fn_id == i
        mean_lat = float(lat[mask].mean()) if mask.any() else 0.1
        specs.append(
            FunctionSpec(name, mean_lat, 0.2, dyn_power_w=25.0 + 5.0 * i, cpu_frac=0.9)
        )
    registry = FunctionRegistry(specs)
    sim = NodeSimulator(registry, SimulatorConfig(platform="desktop")).simulate(trace)
    report = FaasMeterProfiler(ProfilerConfig(init_windows=20, step_windows=10)).profile(
        jnp.asarray(trace.fn_id), jnp.asarray(trace.start), jnp.asarray(trace.end),
        num_fns=trace.num_fns, duration=trace.duration, telemetry=sim.telemetry,
    )
    prices = price_report(
        report.spectrum.j_indiv, report.spectrum.j_total, report.invocations,
        report.mean_latency, jnp.ones(trace.num_fns), PricingConfig(),
    )
    print("== FaasMeter footprints ==")
    for i, name in enumerate(server.order):
        print(
            f"  {name:32s} J/inv={float(report.spectrum.per_invocation[i]):8.2f} "
            f"(indiv {float(report.spectrum.per_invocation_indiv[i]):7.2f}) "
            f"usd/inv={float(prices['total_usd_per_inv'][i]):.2e} "
            f"carbon g/inv={float(prices['carbon_g_per_inv'][i]):.3f}"
        )
    print(f"  total-error={report.total_error:.3f} skew={report.skew_windows:+.1f}w")


if __name__ == "__main__":
    main()
