"""Prefetched ingest: run a tick source on a background thread.

``StreamingFleetSession.ingest`` pulls its telemetry stream through
``prefetch_iterator`` so the host-side sensing that produces tick ``t + 1``
overlaps the jitted step dispatched for tick ``t``.
"""

from __future__ import annotations

from typing import Any, Iterator

from repro.tracing import span


def prefetch_iterator(
    it: Iterator[Any], size: int = 2, *, transfer: Any = None
) -> Iterator[Any]:
    """Run ``it`` on a background thread, ``size`` elements ahead.

    The producer thread fills a bounded queue while the consumer (usually a
    jitted device loop) drains it, so host-side work — telemetry sensing,
    batch synthesis, host->device transfer — overlaps device compute.  The
    host stages release the GIL in their numpy/scipy kernels and in device
    transfers, which is where the overlap comes from; ``transfer`` (e.g. a
    ``jax.device_put`` wrapper) runs on the producer thread so the consumer
    only ever sees device-resident elements.  Each wait of the consumer is
    a ``faasmeter.ingest.wait`` span (``depth``: elements queued when it
    began), which a profiler trace records.

    Exceptions raised by ``it`` or ``transfer`` re-raise at the consuming
    ``next()`` call with the producer's original traceback attached.  When
    the consumer abandons the iterator early (``close()``/GC of the
    generator, or an exception in the consuming loop), the producer thread
    is signalled to stop and *joined* (bounded wait) before control returns
    — callers layering more background stages on top (the drain thread in
    ``StreamingFleetSession.ingest``) rely on ``close()`` not leaking a
    producer that is still touching the source iterator.  The producer is
    also a daemon, so one blocked inside the source iterator itself can
    never hang the join (it is abandoned after the timeout) or interpreter
    exit.
    """
    import queue
    import threading

    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    q: "queue.Queue[tuple[Any, Any]]" = queue.Queue(maxsize=size)
    done = object()
    stop = threading.Event()

    def _put(entry: tuple[Any, Any]) -> bool:
        # Bounded-blocking put: wake up periodically to notice an abandoned
        # consumer (the queue is full and nobody will ever drain it).
        while not stop.is_set():
            try:
                q.put(entry, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce() -> None:
        try:
            for item in it:
                if not _put((item if transfer is None else transfer(item), None)):
                    return
        except BaseException as e:  # noqa: BLE001 - re-raised on the consumer
            _put((done, e))
        else:
            _put((done, None))

    producer = threading.Thread(
        target=_produce, daemon=True, name="prefetch-producer"
    )
    producer.start()
    try:
        while True:
            with span("faasmeter.ingest.wait", depth=q.qsize()):
                item, err = q.get()
            if item is done:
                if err is not None:
                    raise err
                return
            yield item
    finally:
        stop.set()
        producer.join(timeout=5.0)
