"""Front-end error paths: strict packing and prefetch failure semantics.

Small contracts that only show up when things go wrong: the strict packing
mode refusing ragged input loudly, and the prefetch thread (a) re-raising a
producer exception at the consumer with the *producer's* traceback attached
and (b) shutting its thread down promptly when the consumer abandons the
iterator mid-stream instead of blocking forever on the full queue.
"""

import threading
import time
import traceback

import numpy as np
import pytest

from repro.core.batched_engine import pack_fleet_inputs, synthetic_ragged_windows
from repro.core.sessions.prefetch import prefetch_iterator


# ---------------------------------------------------------------------------
# pack_fleet_inputs(strict=True)
# ---------------------------------------------------------------------------


def test_strict_pack_rejects_ragged_lengths():
    lengths = [8, 12, 12]
    arrs = synthetic_ragged_windows(3, 12, 4, lengths=lengths, seed=0)
    # Permissive mode pads + masks...
    packed = pack_fleet_inputs(*arrs, step_windows=4, lengths=lengths)
    assert packed.mask is not None
    # ...strict mode refuses the same input.
    with pytest.raises(ValueError, match="strict"):
        pack_fleet_inputs(*arrs, step_windows=4, lengths=lengths, strict=True)


def test_strict_pack_rejects_indivisible_windows():
    arrs = synthetic_ragged_windows(2, 10, 4, lengths=[10, 10], seed=1)
    with pytest.raises(ValueError, match="divisible"):
        pack_fleet_inputs(*arrs, step_windows=4, lengths=[10, 10], strict=True)


def test_strict_pack_accepts_uniform_divisible():
    arrs = synthetic_ragged_windows(2, 12, 4, lengths=[12, 12], seed=2)
    packed = pack_fleet_inputs(*arrs, step_windows=4, lengths=[12, 12], strict=True)
    assert packed.c.shape[:2] == (2, 3)


# ---------------------------------------------------------------------------
# prefetch_iterator failure semantics
# ---------------------------------------------------------------------------


def _producer_that_blows_up():
    yield 1
    yield 2
    raise RuntimeError("sensor went away")


def test_prefetch_reraises_with_producer_traceback():
    it = prefetch_iterator(_producer_that_blows_up(), size=2)
    assert next(it) == 1
    assert next(it) == 2
    with pytest.raises(RuntimeError, match="sensor went away") as exc_info:
        next(it)
    # The traceback must reach back into the producer generator's frame —
    # the consumer sees *where* the stream died, not just that it died.
    frames = [f.name for f in traceback.extract_tb(exc_info.value.__traceback__)]
    assert "_producer_that_blows_up" in frames, frames


def test_prefetch_transfer_error_reraises():
    def bad_transfer(x):
        raise ValueError(f"cannot place {x}")

    it = prefetch_iterator(iter([1]), size=1, transfer=bad_transfer)
    with pytest.raises(ValueError, match="cannot place 1"):
        next(it)


def _live_producer_threads():
    return [
        t for t in threading.enumerate()
        if t.name == "prefetch-producer" and t.is_alive()
    ]


def test_prefetch_abandoned_consumer_shuts_down_producer():
    """Closing the consumer generator early must stop the producer thread
    even though the bounded queue is full (no daemon-thread leak)."""
    before = len(_live_producer_threads())

    def endless():
        i = 0
        while True:
            yield np.full(4, i)
            i += 1

    it = prefetch_iterator(endless(), size=2)
    assert int(next(it)[0]) == 0
    it.close()  # consumer abandons mid-stream; queue is full at this point
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if len(_live_producer_threads()) <= before:
            break
        time.sleep(0.02)
    assert len(_live_producer_threads()) <= before, "producer thread leaked"


def test_prefetch_close_joins_producer_before_returning():
    """``close()`` must *join* the producer, not merely signal it: callers
    stacking more background stages on top (the streaming session's drain
    thread) rely on the producer being gone — not still touching the source
    iterator — the moment control returns.  No wait loop here on purpose."""
    before = len(_live_producer_threads())

    def endless():
        while True:
            yield np.zeros(4)

    it = prefetch_iterator(endless(), size=2)
    next(it)
    it.close()
    assert len(_live_producer_threads()) <= before, (
        "close() returned with the producer thread still alive"
    )


def test_prefetch_consumer_exception_shuts_down_producer():
    """An exception thrown in the consuming loop (generator GC'd via the
    exception path) also signals the producer to stop."""
    before = len(_live_producer_threads())

    def endless():
        while True:
            yield 1

    with pytest.raises(KeyError):
        for item in prefetch_iterator(endless(), size=2):
            raise KeyError("consumer bug")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if len(_live_producer_threads()) <= before:
            break
        time.sleep(0.02)
    assert len(_live_producer_threads()) <= before, "producer thread leaked"
