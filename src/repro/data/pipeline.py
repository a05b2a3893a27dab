"""Host-side data pipeline: deterministic synthetic batches per ModelApi spec.

Production stance: the pipeline is *spec-driven* — it reads the ModelApi's
TensorSpec tree and synthesizes matching host batches, so the same iterator
serves every family (LM tokens, VLM patch embeddings, enc-dec frame
embeddings) and every (arch x shape) cell.  Determinism: batch ``i`` is a
pure function of (seed, i), so a restarted trainer resumes mid-epoch with
bit-identical data (checkpoint stores the step; the iterator is seekable).

At fleet scale each host synthesizes only its addressable shard (the
``host_slice`` hook maps global batch -> per-host slice); on this single-
host container the full global batch is produced and ``device_put`` against
the batch shardings does the (trivial) placement.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeConfig
from repro.models.model_zoo import ModelApi, TensorSpec, is_spec
from repro.tracing import span


@dataclasses.dataclass(frozen=True)
class DataConfig:
    seed: int = 0
    # Synthetic LM stream: tokens follow a Zipf-ish distribution so the loss
    # has signal (uniform tokens make CE flat at ln V).
    zipf_a: float = 1.2


def _leaf_batch(spec: TensorSpec, rng: np.random.Generator, cfg: ArchConfig, zipf_a: float):
    if np.issubdtype(np.dtype(spec.dtype), np.integer):
        # Token-like: Zipf over the true vocab (clipped).
        z = rng.zipf(zipf_a, size=spec.shape).astype(np.int64)
        return np.minimum(z - 1, cfg.vocab_size - 1).astype(np.int32)
    return (rng.standard_normal(spec.shape) * 0.1).astype(spec.dtype)


def synthetic_batch(
    api: ModelApi, shape: ShapeConfig, step: int, config: DataConfig = DataConfig()
) -> dict[str, np.ndarray]:
    """Batch ``step`` of the deterministic synthetic stream (host numpy)."""
    rng = np.random.default_rng(np.random.SeedSequence([config.seed, step]))
    specs = api.train_inputs(shape)
    batch: dict[str, Any] = {}
    for name, spec in specs.items():
        assert is_spec(spec)
        batch[name] = _leaf_batch(spec, rng, api.cfg, config.zipf_a)
    # labels = next-token shift of tokens (real LM objective on the stream).
    if "labels" in batch and "tokens" in batch:
        toks = batch["tokens"]
        batch["labels"] = np.concatenate(
            [toks[:, 1:], np.full((toks.shape[0], 1), -1, np.int32)], axis=1
        )
    return batch


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


def batch_iterator(
    api: ModelApi,
    shape: ShapeConfig,
    config: DataConfig = DataConfig(),
    *,
    start_step: int = 0,
    shardings: Any = None,
) -> Iterator[dict]:
    """Seekable infinite iterator; ``device_put``s when shardings given."""
    import jax

    step = start_step
    while True:
        host = synthetic_batch(api, shape, step, config)
        if shardings is not None:
            yield {
                k: jax.device_put(v, shardings[k]) for k, v in host.items()
            }
        else:
            yield {k: jnp.asarray(v) for k, v in host.items()}
        step += 1
