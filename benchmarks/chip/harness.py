"""One run of one benchmark cell: set-up, measured window, check, readings.

Everything is found by name.  A cell of ``BENCHMARK.json`` names a
configuration (the file its ``configs`` entry gives: the deployment) and a
traffic mix (``traffic/<traffic>.json``: how the fleet's telemetry windows
arrive at the controller).  The traffic file names its pacer
(``pacers/<pacer>.py``), the code that holds the windows back until they
are due.  Every metric is read by ``metrics/<name>.py``, or, where no such
file is there, by ``metrics/<name up to its first dot>.py``
(``device_idle_share.paced`` -> ``metrics/device_idle_share.py``).  The
check's limits are ``limits/<config>.json``.

The system under test is ``EnergyFirstControlPlane.profile_fleet`` in the
mode the configuration states (combined), on as many chips as the cell
asks for: one device with ``mesh=None``, more with a ``FleetMesh`` over
that many.  The benchmark hands it:

- the fleet's invocation traces (``invocations.py``, from ``--seed``);
- a ``tick_transform`` (the pacer), which sees every telemetry window on
  its way into the controller, keeps a copy for the reference, and holds it
  back until it is due;
- an ``on_tick`` hook (the recorder), which stamps and keeps every tick's
  attribution as the consumer receives it.

A cell that asks for what the harness does not drive (an unknown key in
its configuration or traffic file, a mode the reference does not model, a
chip count the mesh cannot span) fails before anything runs.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import ModuleType

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CONFIG_KEYS = {
    "name", "source", "deployment", "nodes", "platform", "idle_w", "mode", "config_seed",
    "profiler", "workload", "functions", "precision", "guarantees",
    "conservation_tolerance_w", "assumed", "reduced", "reference",
}


class StopWindow(Exception):
    """Raised by a pacer when a window that stops the controller closes."""


class BenchError(RuntimeError):
    """The run cannot produce a sound measurement."""


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with its configuration, traffic and metrics."""

    name: str
    chips: int
    config: dict
    traffic: dict
    pacer: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]

    def __deepcopy__(self, memo):
        import copy

        fields = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        return Cell(**{k: v if k == "pacer" else copy.deepcopy(v, memo)
                       for k, v in fields.items()})


def _reports(metric: dict, cell: str, e2e_names: set[str] | None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads`` key
    lists, else every cell (end-to-end) or every cell that reports the
    metric it ``moves`` (per-layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def _module(path: Path) -> ModuleType:
    if not path.is_file():
        raise BenchError(f"no {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    """Resolve a cell and everything it names; refuse what is not driven."""
    with open(bench_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in {bench_file.name}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    unknown = set(config) - CONFIG_KEYS
    if unknown:
        raise BenchError(f"configuration {w['config']}: keys not driven {sorted(unknown)}")
    if config["mode"] != "combined":
        raise BenchError(f"configuration {w['config']}: mode {config['mode']!r}; the "
                         "reference models combined mode only")
    with open(HERE / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    pacer = _module(HERE / "pacers" / f"{traffic['pacer']}.py")
    pacer.check(traffic)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, None)]
    if not any(m["name"] == "setup_s" for m in e2e):
        raise BenchError("every cell reports setup_s")
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    for m in e2e + per_layer:
        if m["name"] != "setup_s":
            metric_reader(m["name"])
    return Cell(name, int(w["chips"]), config, traffic, pacer, e2e, per_layer)


def metric_reader(name: str):
    """``read(ctx) -> float | None`` of ``metrics/<name>.py``, else of
    ``metrics/<name up to its first dot>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    return _module(path).read


def fleet_platforms(cfg: dict) -> tuple[str, list[str] | None, np.ndarray]:
    """(simulator platform, per-node platforms or None, (B,) idle watts).

    ``platform`` is one name for the whole fleet or one per node; ``idle_w``
    one number or one per platform name.
    """
    b = cfg["nodes"]
    plat = cfg["platform"]
    per_node = [plat] * b if isinstance(plat, str) else list(plat)
    if len(per_node) != b:
        raise BenchError(f"{len(per_node)} platforms for {b} nodes")
    idle = cfg["idle_w"]
    idle_w = np.asarray([idle[p] if isinstance(idle, dict) else idle for p in per_node], float)
    mixed = len(set(per_node)) > 1
    return per_node[0], (per_node if mixed else None), idle_w


def fleet_mesh(chips: int, nodes: int):
    """``mesh=`` for ``profile_fleet``: None on one chip, else a mesh over
    exactly ``chips`` devices."""
    if chips == 1:
        return None
    import jax

    from repro.distributed.sharding import fleet_mesh as make

    mesh = make(nodes, devices=jax.devices()[:chips])
    if mesh.num_devices != chips:
        raise BenchError(f"{nodes} nodes do not tile onto {chips} chips")
    return mesh


class CompileClock:
    """Backend compiles (cache loads included), stamped on the host clock."""

    def __init__(self):
        import jax

        self.events: list[tuple[float, float]] = []   # (end time, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), secs))

    def between(self, lo: float, hi: float) -> tuple[int, float]:
        ev = [s for t, s in self.events if lo <= t < hi]
        return len(ev), float(sum(ev))


class Recorder:
    """The ``on_tick`` consumer: stamps and keeps each tick's attribution."""

    def __init__(self):
        self.t: list[int] = []
        self.at: list[float] = []
        self.x: list[np.ndarray] = []
        self.tick_power: list[np.ndarray] = []
        self.unattributed: list[np.ndarray] = []
        self.last_t = -1

    def __call__(self, tk, trackers) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench.on_tick"):
            self.at.append(time.perf_counter())
            self.t.append(tk.t)
            self.x.append(tk.x)
            self.tick_power.append(tk.tick_power)
            self.unattributed.append(tk.unattributed)
            self.last_t = tk.t

    def arrays(self) -> dict:
        return {
            "t": np.asarray(self.t),
            "x": np.stack(self.x),
            "tick_power": np.stack(self.tick_power),
            "unattributed": np.stack(self.unattributed),
        }


class Tracer:
    """JAX profiler over the window, into a temporary directory."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self) -> None:
        import jax

        # Host spans (TraceMe, TraceAnnotation) but no Python call tracing:
        # that would hook every Python call of the controller's host path.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def path(self) -> str:
        found = sorted(Path(self.directory).rglob("*.xplane.pb"))
        if not found:
            raise BenchError("the profiler wrote no trace")
        return str(found[-1])


def _timed(fn, log: dict, key: str):
    def wrapper(*a, **kw):
        t = time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            log[key] = log.get(key, 0.0) + time.perf_counter() - t
    return wrapper


def build_system(cfg: dict, seed: int):
    """The control plane the cell drives, as its configuration states it."""
    from repro.core.disaggregation import DisaggregationConfig
    from repro.core.kalman import KalmanConfig
    from repro.core.profiler import ProfilerConfig
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.telemetry.simulator import SimulatorConfig
    from repro.workload.functions import FunctionRegistry, FunctionSpec

    p = cfg["profiler"]
    prof = ProfilerConfig(
        delta=p["delta"], init_windows=p["init_windows"], step_windows=p["step_windows"],
        mode=cfg["mode"], sync_max_shift=p["sync_max_shift"],
        kalman=KalmanConfig(
            alpha=p["alpha"], beta=p["beta"], gamma=p["gamma"], delta=p["delta"],
            ridge_lambda=p["ridge_lambda"], nnls_iters=p["nnls_iters"], r_scale=p["r_scale"],
        ),
        disagg=DisaggregationConfig(ridge_lambda=p["ridge_lambda"], nnls_iters=p["init_iters"]),
    )
    reg = FunctionRegistry([FunctionSpec(**f) for f in cfg["functions"]])
    platform, _, _ = fleet_platforms(cfg)
    sim = SimulatorConfig(platform=platform, delta=p["delta"], seed=seed)
    return EnergyFirstControlPlane(reg, sim, prof), reg


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             trace_dir: str | None = None, capture: dict | None = None) -> dict:
    """Run one cell once; returns the result line's object.

    ``t0`` is the host-clock time the process started at; set-up runs from
    there to the window's opening.  ``trace_dir`` keeps the traced run's
    profile there instead of in a temporary directory.  ``capture``, when
    given, receives what the check compared (``received``, ``reference``,
    ``reports``), its inputs (``inputs``: the arguments of
    ``reference.reference_ticks`` but the precision) and its ``numbers``.
    """
    import tempfile

    from repro.workload.trace import InvocationTrace

    import correctness
    import invocations
    import reference

    cfg, traffic = cell.config, cell.traffic
    p = cfg["profiler"]
    stop = traffic["at_close"] == "stop"
    n_windows = cell.pacer.segment_windows(p, traffic, seconds)
    duration = n_windows * p["delta"]
    _, platforms, idle_w = fleet_platforms(cfg)
    mesh = fleet_mesh(cell.chips, cfg["nodes"])
    split: dict[str, float] = {}

    t = time.perf_counter()
    arrays, k = invocations.generate(cfg, duration, seed)
    split["trace generation"] = time.perf_counter() - t
    cp, reg = build_system(cfg, seed)
    traces = [
        InvocationTrace(fn_id=a, start=s, end=e, num_fns=len(reg), duration=duration,
                        fn_names=reg.names)
        for a, s, e in arrays
    ]
    cp.simulator.simulate_fleet = _timed(cp.simulator.simulate_fleet, split, "simulate_fleet")
    cp.combined_counter_inputs = _timed(cp.combined_counter_inputs, split, "counter-model fit")
    cp.profiler.start_fleet_stream = _timed(
        cp.profiler.start_fleet_stream, split, "session construction"
    )
    log(f"cell {cell.name}: {len(traces)} nodes x {len(reg)} functions on {cell.chips} chip(s), "
        f"{n_windows} windows ({k} invocation slots per node), traffic {traffic}, "
        f"seconds {seconds}, seed {seed}")

    clock = CompileClock()
    recorder = Recorder()
    tmp = tempfile.TemporaryDirectory() if trace and trace_dir is None else None
    tracer = Tracer(trace_dir or tmp.name) if trace else None
    pacer = cell.pacer.Pacer(
        traffic, seconds=seconds, boundary_tick=p["init_windows"] + p["step_windows"] - 1,
        recorder=recorder, tracer=tracer,
    )
    t_call = time.perf_counter()
    reports = None
    try:
        reports = cp.profile_fleet(
            traces, platforms=platforms, mode=cfg["mode"], mesh=mesh, on_tick=recorder,
            tick_transform=pacer,
        )
    except StopWindow:
        pass
    t_done = time.perf_counter()
    if pacer.t_open is None or pacer.t_close is None:
        raise BenchError(
            "the segment ended before the window closed: the controller overtook "
            "the segment the cell's traffic sizes, sweep again"
        )
    if not stop and reports is None:
        raise BenchError("profile_fleet returned no reports")
    t_open, t_end, t_close = pacer.t_open, pacer.t_end, pacer.t_close
    dev = device_info(cell.chips)

    # -- set-up split and compilations --------------------------------------
    first_tick = recorder.at[0] if recorder.at else t_open
    split["bootstrap (to the first tick)"] = first_tick - (pacer.t_first_yield or t_call)
    split["warm-up ticks"] = t_open - first_tick
    n_c, s_c = clock.between(-math.inf, t_open)
    n_w, s_w = clock.between(t_open, t_close)
    for key, val in split.items():
        log(f"setup: {key}: {val:.4f} s")
    log(f"setup: compiles: {n_c} programs, {s_c:.4f} s")
    log(f"window: compiles: {n_w} programs, {s_w:.4f} s")
    if n_w:
        raise BenchError(f"{n_w} programs compiled inside the measured window")
    at = np.asarray(recorder.at)
    ts = np.asarray(recorder.t)
    in_window = (at >= t_open) & (at < t_end)
    inside = at[in_window]
    gap_at = np.flatnonzero(np.diff(inside) > 0.05)
    stalls = int(gap_at.size)
    log(f"window: {stalls} emission gaps over 50 ms at "
        f"{[round(float(inside[i] - t_open), 3) for i in gap_at]} s, lasting "
        f"{[round(float(inside[i + 1] - inside[i]) * 1e3, 3) for i in gap_at]} ms")

    # -- end-to-end metrics -------------------------------------------------
    b = len(traces)
    late = np.asarray(pacer.late) if pacer.late else np.zeros(1)
    log(f"pacer: {len(pacer.late)} windows on schedule, late p50 {np.median(late) * 1e3:.4f} ms, "
        f"max {late.max() * 1e3:.4f} ms")
    emitted = int(in_window.sum())
    offered = pacer.offered()
    diag: dict[str, float] = {"late_p50_ms": float(np.median(late) * 1e3),
                              "late_max_ms": float(late.max() * 1e3),
                              "stalls_over_50ms": stalls, "ticks_per_s": emitted / seconds}
    latency_ms = None
    if stop:
        log(f"stop: {emitted} ticks emitted of {offered} offered in the window")
        if emitted >= 0.95 * offered:
            raise BenchError(
                f"the controller kept up with {pacer.rate} windows/s: the rate is no longer "
                "above capacity, sweep again"
            )
        attempted, failed = emitted, 0
    else:
        # Tick t needs raw window t + lookahead (the sync skew) and is due
        # when that window is.
        skews = np.asarray([r.report.skew_windows for r in reports])
        look = int(math.ceil(max(float(skews.max()), 0.0)))
        due = pacer.due(ts + look)
        mask = (ts + look >= pacer.k0) & (due < t_end)
        latency_ms = (at[mask] - due[mask]) * 1e3
        attempted = offered
        failed = attempted - int(mask.sum())
        if latency_ms.size == 0:
            raise BenchError("no tick was due in the window")
        quarter = max(latency_ms.size // 4, 1)
        diag.update(
            p50_ms=float(np.median(latency_ms)), max_ms=float(latency_ms.max()),
            # A backlog that grows over the window shows as later ticks
            # waiting longer than earlier ones.
            trend_ms=float(np.median(latency_ms[-quarter:]) - np.median(latency_ms[:quarter])),
            **{f"p{q}_ms": float(np.percentile(latency_ms, q)) for q in (90, 95, 98, 99)},
        )
        log(f"drain: {latency_ms.size} ticks due in the window (lookahead {look}), latency ms "
            f"p50 {np.median(latency_ms):.4f} p99 {np.percentile(latency_ms, 99):.4f} "
            f"max {latency_ms.max():.4f}")
    values = {"setup_s": t_open - t0}
    e2e_ctx = {"latency_ms": latency_ms, "emitted": emitted, "nodes": b, "seconds": seconds}
    for m in cell.end_to_end:
        if m["name"] not in values:
            values[m["name"]] = metric_reader(m["name"])(e2e_ctx)

    # -- per-layer metrics from the trace -------------------------------------
    metrics = {}
    breakdown = None
    if trace:
        import host_spans
        import trace_reduce

        path = tracer.path()
        red = trace_reduce.reduce(trace_reduce.load(path))
        ticks_traced = int(((at >= t_open) & (at < t_close)).sum())
        host = host_spans.per_tick(host_spans.reduce(host_spans.load(path)), ticks_traced)
        if tmp is not None:
            tmp.cleanup()
        ctx = {"reduced": red, "host": host, "ticks": ticks_traced, "nodes": b,
               "cell": cell.name}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        breakdown = red["breakdown"]
        log(f"trace: busy {red['busy_s']:.6f} s of {red['window_s']:.6f} s, "
            f"{ticks_traced} ticks, programs {json.dumps(red['programs'])}")
        log(f"trace: host per tick {json.dumps(host)}")
    else:
        for m in cell.end_to_end:
            if values[m["name"]] is None:
                raise BenchError(f"end-to-end metric {m['name']} was not measured")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    # -- correctness ------------------------------------------------------------
    got_reports = None
    if reports is not None:
        got_reports = {
            "x_trajectory": np.stack([np.asarray(r.report.x_trajectory) for r in reports]),
            "invocations": np.stack([np.asarray(r.report.invocations) for r in reports]),
        }
    del reports
    received = recorder.arrays()
    raw = pacer.raw_arrays()
    t_ref = time.perf_counter()
    inputs = (reference.Profiler.from_config(cfg), arrays, len(reg), duration, idle_w,
              raw, int(received["t"].max()))
    ref = reference.reference_ticks(*inputs)
    ref_reports = (reference.report(ref, arrays, len(reg), p["step_windows"])
                   if got_reports is not None else None)
    numbers = correctness.gaps(received, ref, p["step_windows"], got_reports, ref_reports)
    if capture is not None:
        capture.update(received=received, reference=ref, inputs=inputs, numbers=numbers,
                       reports=got_reports)
    ok, checks = correctness.judge(numbers, correctness.load_limits(cfg))
    log(f"reference: {time.perf_counter() - t_ref:.2f} s over {ref.x.shape[0]} ticks x {b} nodes; "
        f"profile_fleet returned after {t_done - t_call:.2f} s")
    for name, val in numbers.items():
        if name not in checks:
            log(f"diagnostic (not judged): {name} {val}")
    for name, c in checks.items():
        log(f"check: {name} {c['value']} limit {c['limit']}")
    out = {
        "correct": bool(ok),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["diagnostics"] = diag
    out["checks"] = checks
    return out
