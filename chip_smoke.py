#!/usr/bin/env python3
"""Chip smoke test: the fleet energy controller's main path on a TPU.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: sharded phase A only

Phase A drives ``EnergyFirstControlPlane.profile_fleet`` in combined mode
(§4.3) over 64 server nodes x 600 s of Azure-style traffic for the paper's
seven Table 2 functions: 60 init windows, then 18 Kalman steps of 30
one-second windows, i.e. 64 nodes x 540 ticks streamed through the jitted
``fleet_step``.  It checks that every report is finite, that each tick's
measured power is conserved (attributed + control plane + unattributed =
target, and target + idle side = measured), and prints the per-function
footprint error against the simulator's marginal ground truth.  It then
reruns the same call on the host CPU device and reports the chip-vs-CPU gap.

Phase B packs the same fleet's step blocks (the ticks phase A streamed) and
runs the gram-hoisted segment engine ``run_fleet_gram`` with
``backend="auto"``, which on a TPU is the compiled Pallas ``disagg_gram``
kernel: the compiled program must contain a ``tpu_custom_call``.  Its result
is checked against the XLA gram path on the chip and the sequential oracle.

``--four-chips`` runs phase A's fleet once sharded over a 4-device
``FleetMesh`` and once unsharded, in the same process, pinned at 1e-5, plus
the fleet totals through the mesh ``psum``.

Everything runs in this one process: a chip belongs to one process at a
time.  Timings, compile seconds and diffs go to stdout; the last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any check fails,
the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

NODES = 64
DURATION_S = 600.0
# benchmarks/sharded_fleet.py: the relative bound at benchmark scale.
REL_BOUND = 1e-4
# tests/test_sharded_fleet.py: sharded vs unsharded.
SHARD_BOUND = 1e-5
# tests/test_combined_fleet.py: per-tick conservation (W).
CONSERVE_ATOL = 1e-3


class Checks:
    """Named pass/fail checks, all printed; the run fails if any failed."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)


class CompileClock:
    """Sums JAX backend-compile seconds (cache loads included) per phase."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def lap(self) -> tuple[float, int]:
        """(seconds, programs) compiled since the previous lap."""
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def _rel(a, b) -> float:
    """Largest |a - b| / max(|b|, 1): relative above 1, absolute below."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


class TickLog:
    """``on_tick`` hook: per-tick conservation and the engine's feed."""

    def __init__(self):
        self.c, self.w, self.a = [], [], []
        self.tick_power, self.unattributed = [], []
        self.max_resid_w = 0.0
        self.resid_j = 0.0
        self.measured_j = 0.0
        self.first_tick_hook = None

    def __call__(self, tk, trackers) -> None:
        if self.first_tick_hook is not None:
            self.first_tick_hook()
            self.first_tick_hook = None
        # attributed + control plane (the last column) + unattributed must
        # rebuild the engine's target; target + idle side = measured by
        # definition of the idle side, so the per-node energy residual is
        # the summed per-tick residual.
        resid = tk.tick_power.sum(-1) + tk.unattributed - tk.target
        self.max_resid_w = max(self.max_resid_w, float(np.max(np.abs(resid))))
        self.resid_j = self.resid_j + resid
        self.measured_j = self.measured_j + np.asarray(tk.w_sys, np.float64)
        self.c.append(tk.busy_seconds)
        self.w.append(tk.target)
        self.a.append(tk.a)
        self.tick_power.append(tk.tick_power)
        self.unattributed.append(tk.unattributed)


def _fleet():
    """The phase A fleet: 64 server nodes, 600 s, Table 2 functions."""
    from benchmarks.common import PROFILER_CONFIG
    from repro.serving.control_plane import EnergyFirstControlPlane
    from repro.telemetry.simulator import SimulatorConfig
    from repro.workload.azure import WorkloadConfig, fleet_traces
    from repro.workload.functions import paper_functions

    reg = paper_functions()
    traces = fleet_traces(reg, WorkloadConfig(duration_s=DURATION_S, load=1.0), NODES)
    cp = EnergyFirstControlPlane(reg, SimulatorConfig(platform="server"), PROFILER_CONFIG)
    return traces, cp


def _profile(cp, traces, log: TickLog, **kw):
    t0 = time.perf_counter()
    out = cp.profile_fleet(traces, mode="combined", on_tick=log, **kw)
    return out, time.perf_counter() - t0


def _report_arrays(out):
    x = np.stack([np.asarray(p.report.x_power) for p in out])
    j = np.stack([np.asarray(p.report.spectrum.j_indiv) for p in out])
    jt = np.stack([np.asarray(p.report.spectrum.j_total) for p in out])
    terr = np.asarray([p.report.total_error for p in out])
    return x, j, jt, terr


def _footprint_error(out) -> np.ndarray:
    """Per-function fleet footprint error vs the simulator's marginal truth."""
    est = np.stack([np.asarray(p.report.spectrum.j_indiv) for p in out]).sum(0)
    true = np.stack([p.sim.true_fn_energy_j for p in out]).sum(0)
    return np.abs(est - true) / np.maximum(true, 1e-9)


def _check_phase_a(check: Checks, tag: str, out, log: TickLog) -> None:
    x, j, jt, terr = _report_arrays(out)
    check(
        f"{tag} reports finite",
        all(np.isfinite(v).all() for v in (x, j, jt, terr)) and len(out) == NODES,
        f"{len(out)} reports, x {x.shape}, total_error max {terr.max():.4g}",
    )
    energy = float(np.max(np.abs(log.resid_j) / log.measured_j))
    check(
        f"{tag} energy conserved",
        log.max_resid_w <= CONSERVE_ATOL and len(log.c) == 540,
        f"{len(log.c)} ticks, max per-tick residual {log.max_resid_w:.3g} W "
        f"(atol {CONSERVE_ATOL}), worst node energy residual {energy:.3g} of measured",
    )


def phase_a(check: Checks, clock: CompileClock):
    """Streaming controller on the chip, then on the host CPU."""
    import jax

    print("phase A: profile_fleet(mode='combined'), 64 nodes x 540 ticks", flush=True)
    traces, cp = _fleet()
    log = TickLog()
    out, secs = _profile(cp, traces, log)
    comp_s, comp_n = clock.lap()
    print(f"  chip: {secs:.2f} s wall, {comp_s:.2f} s compiling {comp_n} programs")
    _check_phase_a(check, "chip", out, log)
    err = _footprint_error(out)
    print(f"  footprint error vs ground truth per function: {np.array2string(err, precision=5)}")
    print(f"  footprint error: max {err.max():.5f}, mean {err.mean():.5f}")

    cpu_log = TickLog()
    with jax.default_device(jax.devices("cpu")[0]):
        cpu_out, cpu_secs = _profile(cp, traces, cpu_log)
    comp_s, comp_n = clock.lap()
    print(f"  host CPU rerun: {cpu_secs:.2f} s wall, {comp_s:.2f} s compiling {comp_n} programs")
    _check_phase_a(check, "cpu", cpu_out, cpu_log)
    cpu_err = _footprint_error(cpu_out)
    x, j, _, _ = _report_arrays(out)
    cx, cj, _, _ = _report_arrays(cpu_out)
    check("chip vs CPU final x", _rel(x, cx) <= REL_BOUND, f"max rel diff {_rel(x, cx):.3g}")
    check("chip vs CPU footprints", _rel(j, cj) <= REL_BOUND, f"max rel diff {_rel(j, cj):.3g}")
    d_err = float(np.max(np.abs(err - cpu_err)))
    check(
        "footprint error matches CPU", d_err <= REL_BOUND,
        f"CPU max {cpu_err.max():.5f}, largest per-function difference {d_err:.3g}",
    )
    return traces, log


def _segment_inputs(traces, log: TickLog):
    """(B, S, n_w, M) step blocks from the ticks phase A streamed."""
    from benchmarks.common import PROFILER_CONFIG
    from repro.core.engine import FleetInputs
    import jax.numpy as jnp

    n_w, init_n = PROFILER_CONFIG.step_windows, PROFILER_CONFIG.init_windows
    c = np.stack(log.c, axis=1)                     # (B, T, M_aug)
    w = np.stack(log.w, axis=1)                     # (B, T)
    a = np.stack(log.a, axis=1)
    b, t, m = c.shape
    s = t // n_w
    a_steps = a.reshape(b, s, n_w, m).sum(2)
    # Latency moments per step by invocation start time, as the session
    # derives them; the control-plane column has none.
    ls = np.zeros((b, s, m))
    lq = np.zeros((b, s, m))
    counts = np.zeros((b, s, m))
    for i, tr in enumerate(traces):
        k = np.floor((tr.start - init_n * PROFILER_CONFIG.delta) / (n_w * PROFILER_CONFIG.delta))
        ok = (tr.fn_id >= 0) & (k >= 0) & (k < s)
        k, fn = k[ok].astype(int), tr.fn_id[ok]
        dur = np.maximum(tr.end - tr.start, 0.0)[ok]
        np.add.at(ls[i], (k, fn), dur)
        np.add.at(lq[i], (k, fn), dur * dur)
        np.add.at(counts[i], (k, fn), 1.0)
    nf = traces[0].num_fns
    if not np.array_equal(counts[..., :nf], a_steps[..., :nf]):
        raise AssertionError("per-step invocation counts disagree with the stream's")
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    inputs = FleetInputs(
        c=f32(c.reshape(b, s, n_w, m)), w=f32(w.reshape(b, s, n_w)),
        a=f32(a_steps), lat_sum=f32(ls), lat_sumsq=f32(lq),
    )
    return inputs, f32(c[:, : 2 * n_w]), f32(w[:, : 2 * n_w])


def phase_b(check: Checks, clock: CompileClock, traces, log: TickLog) -> None:
    """Gram-hoisted segment engine with the compiled Pallas kernel."""
    import jax
    from repro.core.engine import EngineConfig, run_fleet_gram, run_fleet_sequential

    inputs, init_c, init_w = _segment_inputs(traces, log)
    print(
        f"phase B: run_fleet_gram(backend='auto'), step blocks {tuple(inputs.c.shape)}, "
        f"init block {tuple(init_c.shape)}", flush=True,
    )
    cfg = EngineConfig(backend="auto")
    seg = jax.jit(lambda inp, ic, iw: run_fleet_gram(inp, cfg, init_c=ic, init_w=iw))
    t0 = time.perf_counter()
    compiled = seg.lower(inputs, init_c, init_w).compile()
    t_compile = time.perf_counter() - t0
    check(
        "Pallas kernel compiled in", "tpu_custom_call" in compiled.as_text(),
        f"segment program compiled in {t_compile:.2f} s",
    )
    out = jax.block_until_ready(compiled(inputs, init_c, init_w))
    check(
        "segment result finite",
        all(np.isfinite(np.asarray(v)).all() for v in (out.x_final, out.tick_power)),
        f"x_final {tuple(out.x_final.shape)}, tick_power {tuple(out.tick_power.shape)}",
    )
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(inputs, init_c, init_w))
    print(f"  pallas segment run: {time.perf_counter() - t0:.4f} s (warm)")

    t0 = time.perf_counter()
    xla = jax.block_until_ready(
        run_fleet_gram(inputs, EngineConfig(backend="xla"), init_c=init_c, init_w=init_w)
    )
    print(f"  xla gram path: {time.perf_counter() - t0:.2f} s (with compile)")
    t0 = time.perf_counter()
    seq = jax.block_until_ready(
        run_fleet_sequential(inputs, cfg, init_c=init_c, init_w=init_w)
    )
    print(f"  sequential oracle: {time.perf_counter() - t0:.2f} s (with compile)")
    comp_s, comp_n = clock.lap()
    print(f"  phase B compiled {comp_n} programs in {comp_s:.2f} s")
    resid = out.tick_power.sum(-1) + out.unattributed - inputs.w.reshape(out.unattributed.shape)
    resid = float(np.max(np.abs(np.asarray(resid))))
    check(
        "segment energy conserved", resid <= CONSERVE_ATOL,
        f"max per-tick residual {resid:.3g} W (atol {CONSERVE_ATOL})",
    )
    # The bound holds the segment's result, the final Kalman estimate.
    # Ticks are printed, not held to it: the early steps are attributed
    # from X_0, the init block's fixed-iteration FISTA iterate, which is
    # not converged, so the few 1e-7 by which two gram paths differ move
    # it by ~1e-4 on any backend (on the CPU, Pallas interpret vs XLA), and
    # the Kalman memory carries that into the first steps' ticks.
    for name, ref in (("xla gram path", xla), ("sequential oracle", seq)):
        d = _rel(out.x_final, ref.x_final)
        check(f"pallas vs {name}", d <= REL_BOUND, f"final x max rel diff {d:.3g} (bound {REL_BOUND})")
        print(
            f"    ticks max rel diff {_rel(out.tick_power, ref.tick_power):.3g}, "
            f"X_0 {_rel(out.x0, ref.x0):.3g}"
        )


def four_chips(check: Checks, clock: CompileClock) -> None:
    """Phase A's fleet sharded over four chips vs the unsharded run."""
    import jax
    from repro.distributed.sharding import fleet_attribution_totals, fleet_mesh

    mesh = fleet_mesh(NODES)
    check("mesh spans four devices", mesh.num_devices == 4, f"{mesh.num_devices} devices")
    print("four chips: profile_fleet(mode='combined', mesh=fleet_mesh(64))", flush=True)
    traces, cp = _fleet()
    placement = {}

    def _placement():
        live = [a for a in jax.live_arrays() if len(a.sharding.device_set) == 4]
        placement["sharded_arrays"] = len(live)
        placement["devices"] = len(set().union(*(a.sharding.device_set for a in live)))

    log = TickLog()
    log.first_tick_hook = _placement
    sharded, secs = _profile(cp, traces, log, mesh=mesh)
    comp_s, comp_n = clock.lap()
    print(f"  sharded: {secs:.2f} s wall, {comp_s:.2f} s compiling {comp_n} programs")
    check(
        "stream state spread over the mesh",
        placement.get("devices") == 4 and placement.get("sharded_arrays", 0) > 0,
        f"{placement}",
    )
    _check_phase_a(check, "sharded", sharded, log)

    plain_log = TickLog()
    plain, secs = _profile(cp, traces, plain_log, mesh=None)
    comp_s, comp_n = clock.lap()
    print(f"  unsharded: {secs:.2f} s wall, {comp_s:.2f} s compiling {comp_n} programs")
    x, j, _, _ = _report_arrays(sharded)
    px, pj, _, _ = _report_arrays(plain)
    check("sharded vs unsharded x", _rel(x, px) <= SHARD_BOUND, f"max rel diff {_rel(x, px):.3g}")
    check("sharded vs unsharded footprints", _rel(j, pj) <= SHARD_BOUND, f"max rel diff {_rel(j, pj):.3g}")

    tp = np.stack(log.tick_power, axis=1)
    ua = np.stack(log.unattributed, axis=1)
    tot = fleet_attribution_totals(mesh.put(tp), mesh.put(ua), mesh=mesh)
    ref = fleet_attribution_totals(tp, ua)
    d = max(_rel(tot.per_fn, ref.per_fn), _rel(tot.attributed, ref.attributed))
    check("psum fleet totals", d <= SHARD_BOUND, f"max rel diff {d:.3g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded phase A fleet on a 4-device mesh",
    )
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX's default backend is "
            f"{jax.default_backend()!r}", file=sys.stderr,
        )
        return 1
    devs = jax.devices()
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} TPU devices, found {len(devs)}", file=sys.stderr)
        return 1
    print(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache {cache}", flush=True)

    check = Checks()
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(check, clock)
    else:
        t1 = time.perf_counter()
        traces, log = phase_a(check, clock)
        print(f"phase A: {time.perf_counter() - t1:.2f} s", flush=True)
        t1 = time.perf_counter()
        phase_b(check, clock, traces, log)
        print(f"phase B: {time.perf_counter() - t1:.2f} s", flush=True)
    print(f"total: {time.perf_counter() - t0:.2f} s", flush=True)
    if check.failed:
        print(f"chip_smoke: failed checks: {', '.join(check.failed)}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
