"""Each tick's contribution row is host data (``StreamTick.busy_seconds``).

The contribution array is static per segment (the invocation traces are
known when the session opens; only telemetry streams), so the session
serves tick ``t``'s row from a host copy: the same float32 values reach the
jitted ``fleet_step`` and the ``on_tick`` consumer, with no device slice and
no pull back.  Pinned, in pure mode without a principal column, in combined
mode with one, on a ragged fleet and through the slot pool:

- every ``busy_seconds`` is a float32 ``np.ndarray`` bitwise equal to the
  session's device contribution row ``_c_fns[:, t]``, with the principal
  column appended where the session has one;
- a consumer writing into one tick's ``busy_seconds`` changes neither the
  later ticks' arrays nor the session's contribution data, engine state,
  attribution or reports.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.profiler import FaasMeterProfiler, ProfilerConfig, prepare_combined_fleet
from repro.telemetry.simulator import NodeSimulator, SimulatorConfig
from repro.workload.azure import WorkloadConfig, generate_trace
from repro.workload.functions import paper_functions

INIT, STEP = 60, 30

#: case -> (platform, mode, per-node durations, has_cp, slots)
CASES = {
    "pure": ("edge", "pure", [150.0, 150.0], False, None),
    "combined": ("desktop", "combined", [150.0, 150.0], True, None),
    "ragged": ("server", "pure", [150.0, 105.0], True, None),
    "slots": ("edge", "pure", [150.0, 150.0], True, 3),
}


def _fixture(case):
    platform, mode, durs, has_cp, slots = CASES[case]
    reg = paper_functions()
    sim = NodeSimulator(reg, SimulatorConfig(platform=platform))
    profiler = FaasMeterProfiler(ProfilerConfig(
        init_windows=INIT, step_windows=STEP, mode=mode, sync_max_shift=0,
    ))
    traces = [
        generate_trace(reg, WorkloadConfig(duration_s=d, load=1.0, seed=s))
        for s, d in enumerate(durs, start=1)
    ]
    tels = [s.telemetry for s in sim.simulate_fleet(traces, seeds=[11, 12])]
    arrays = [(jnp.asarray(t.fn_id), jnp.asarray(t.start), jnp.asarray(t.end))
              for t in traces]
    kw = {}
    if mode == "combined":
        specs = reg.specs
        fnc, wf, models = prepare_combined_fleet(
            profiler.config, arrays, tels, num_fns=traces[0].num_fns, duration=durs[0],
            gflops=np.asarray([s.gflops for s in specs]),
            hbm_gb=np.asarray([s.hbm_gb for s in specs]),
            mean_latency=np.asarray([max(s.mean_latency_s, 1e-3) for s in specs]),
        )
        kw = dict(fn_counters=fnc, counter_model=models, window_features=wf)
    duration = durs if len(set(durs)) > 1 else durs[0]
    has_chip = tels[0].chip_power is not None

    def run(on_tick):
        sess = profiler.start_fleet_stream(
            arrays, num_fns=traces[0].num_fns, duration=duration,
            idle_watts=[t.idle_watts for t in tels], has_chip=has_chip,
            has_cp=has_cp, on_tick=on_tick, slots=slots, **kw,
        )

        def col(tel, name, t):
            arr = getattr(tel, name)
            if arr is None:
                return 0.0
            arr = np.asarray(arr)
            return arr[t] if t < arr.shape[0] else 0.0

        for t in range(int(max(durs))):
            w = {k: np.asarray([col(tel, n, t) for tel in tels])
                 for k, n in (("w_sys", "system_power"), ("w_chip", "chip_power"),
                              ("cp_frac", "cp_cpu_frac"), ("sys_frac", "sys_cpu_frac"))}
            sess.push_window(
                w["w_sys"], w["w_chip"] if has_chip else None,
                w["cp_frac"] if has_cp else None, w["sys_frac"] if has_cp else None,
            )
        return sess, sess.finalize()

    return run


def _expected_row(sess, t):
    row = np.asarray(sess._c_fns)[:, t]
    if sess.has_cp:
        row = np.concatenate([row, sess._cp_col[t][:, None]], axis=1)
    return row


@pytest.mark.parametrize("case", list(CASES))
def test_busy_seconds_is_the_host_contribution_row(case):
    ticks = []
    sess, _ = _fixture(case)(ticks.append)
    assert [tk.t for tk in ticks] == list(range(INIT, sess.n_used))
    assert sess.m_aug == sess.num_fns + int(CASES[case][3])
    for tk in ticks:
        got = tk.busy_seconds
        assert type(got) is np.ndarray and got.dtype == np.float32
        assert got.shape == (sess.b, sess.m_aug)
        np.testing.assert_array_equal(got, _expected_row(sess, tk.t))


@pytest.mark.parametrize("case", list(CASES))
def test_writing_into_busy_seconds_changes_nothing_else(case):
    run = _fixture(case)
    clean = []
    clean_sess, clean_reports = run(clean.append)

    seen = []

    def scribble(tk):
        seen.append((tk, tk.busy_seconds.copy()))
        tk.busy_seconds[...] = np.nan

    sess, reports = run(scribble)
    assert [tk.t for tk, _ in seen] == [tk.t for tk in clean]
    # Every tick got its own row, untouched by the writes into earlier ones.
    for a, (b, row) in zip(clean, seen):
        np.testing.assert_array_equal(row, a.busy_seconds)
        assert np.isnan(b.busy_seconds).all()
    np.testing.assert_array_equal(sess._c_host, clean_sess._c_host)
    np.testing.assert_array_equal(np.asarray(sess._c_fns), np.asarray(clean_sess._c_fns))
    np.testing.assert_array_equal(sess._c_host, np.asarray(sess._c_fns))
    for a, (b, _) in zip(clean, seen):
        for f in ("x", "tick_power", "unattributed", "target"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)
    for la, lb in zip(jax.tree.leaves(clean_sess.state), jax.tree.leaves(sess.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for ra, rb in zip(clean_reports, reports):
        np.testing.assert_array_equal(np.asarray(ra.x_power), np.asarray(rb.x_power))
