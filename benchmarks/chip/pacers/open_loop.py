"""Open-loop pacer: fleet telemetry windows due on a fixed schedule.

A traffic file names this pacer with ``"pacer": "open_loop"`` and gives:

- ``rate_windows_per_s``: windows due per second once the window opens;
- ``segment_windows_per_s``: windows per second of measured window that the
  segment holds (the rate itself where the segment is to be streamed to its
  end; above the controller's capacity and below the offered rate where the
  run stops when the window closes, so the segment is not simulated for
  windows no tick will use);
- ``at_close``: ``"drain"`` (stream the rest of the segment and finalize,
  neither counted) or ``"stop"`` (stop the controller: ``StopWindow``);
- ``tail_windows``: windows past the window that the segment holds.

The pacer is the controller's ``tick_transform`` and runs on the ingest
stage's prefetch thread.  It yields the init block, the sync lookahead and
the first full Kalman step as fast as they are taken, and opens the window
once the controller has emitted that step's boundary tick.  From then on
window ``k`` is due at ``open + (k - k0) / rate``, a schedule that does not
slow when the controller does.
"""

from __future__ import annotations

import math
import time

import numpy as np

from harness import BenchError, StopWindow
from trace_reduce import WINDOW_CLOSE, WINDOW_OPEN

KEYS = {"pacer", "rate_windows_per_s", "segment_windows_per_s", "at_close", "tail_windows",
        "about"}


def check(traffic: dict) -> None:
    """Refuse a traffic file this pacer does not drive."""
    unknown = set(traffic) - KEYS
    missing = KEYS - set(traffic) - {"about"}
    if unknown or missing:
        raise BenchError(f"open_loop traffic: unknown keys {sorted(unknown)}, "
                         f"missing {sorted(missing)}")
    if traffic["at_close"] not in ("drain", "stop"):
        raise BenchError(f"open_loop traffic: at_close {traffic['at_close']!r}")
    if traffic["segment_windows_per_s"] > traffic["rate_windows_per_s"]:
        raise BenchError("open_loop traffic: the segment holds more windows than are offered")


def segment_windows(profiler: dict, traffic: dict, seconds: float) -> int:
    """Windows in the segment: the init block, then whole Kalman steps that
    hold the warm-up step, the sync lookahead, the window and a tail.  The
    controller emits ticks of whole steps only."""
    post = (
        profiler["step_windows"] + profiler["sync_max_shift"] + traffic["tail_windows"]
        + int(math.ceil(traffic["segment_windows_per_s"] * seconds))
    )
    n_w = profiler["step_windows"]
    return profiler["init_windows"] + n_w * int(math.ceil(post / n_w))


class Pacer:
    """``tick_transform``: warm-up as fast as taken, then a fixed schedule."""

    def __init__(self, traffic: dict, *, seconds: float, boundary_tick: int, recorder,
                 tracer=None):
        self.rate = float(traffic["rate_windows_per_s"])
        self.stop = traffic["at_close"] == "stop"
        self.seconds = seconds
        self.boundary_tick = boundary_tick
        self.recorder = recorder
        self.tracer = tracer
        self.raw: dict[str, list] = {"w_sys": [], "w_chip": [], "cp_frac": [], "sys_frac": []}
        self.k0: int | None = None
        self.t_open: float | None = None
        self.t_end: float | None = None
        self.t_close: float | None = None
        self.t_first_yield: float | None = None
        self.late: list[float] = []   # seconds each window in the window went out late

    def _open(self, k: int) -> None:
        import jax

        if self.tracer is not None:
            self.tracer.start()
        with jax.profiler.TraceAnnotation(WINDOW_OPEN):
            self.k0 = k
            self.t_open = time.perf_counter()
            self.t_end = self.t_open + self.seconds

    def _close(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(WINDOW_CLOSE):
            self.t_close = time.perf_counter()
        if self.tracer is not None:
            self.tracer.stop()

    def _sleep_until(self, when: float) -> None:
        import jax

        wait = when - time.perf_counter()
        if wait > 0:
            with jax.profiler.TraceAnnotation("bench.pacer_wait"):
                time.sleep(wait)

    def __call__(self, ticks):
        for tk in ticks:
            if self.t_first_yield is None:
                self.t_first_yield = time.perf_counter()
            for key, rows in self.raw.items():
                rows.append(getattr(tk, key))
            # The window opens once the warm-up step's boundary tick is out;
            # until then windows go as fast as they are taken (the prefetch
            # depth bounds how far ahead that runs).
            if self.k0 is None and self.recorder.last_t >= self.boundary_tick:
                self._open(tk.t)
            if self.k0 is not None and self.t_close is None:
                due = self.t_open + (tk.t - self.k0) / self.rate
                self._sleep_until(min(due, self.t_end))
                now = time.perf_counter()
                if now >= self.t_end:
                    self._close()
                    if self.stop:
                        raise StopWindow
                else:
                    self.late.append(now - due)
            yield tk

    def raw_arrays(self) -> dict:
        return {k: np.stack(v) for k, v in self.raw.items()}

    def due(self, windows: np.ndarray) -> np.ndarray:
        """Host-clock time each raw window was due (the window's schedule)."""
        return self.t_open + (windows - self.k0) / self.rate

    def offered(self) -> int:
        """Windows due inside the measured window."""
        return int(math.ceil(self.rate * self.seconds))
