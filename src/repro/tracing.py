"""Named host spans on the streaming tick path (docs/streaming.md).

A span records only while a JAX profiler trace is active
(``jax.profiler.trace`` / ``start_trace``).  It then lands on the trace's
host plane, on the same clock as the device planes, so each device idle
interval lines up with what the host was doing.  Outside a trace a span is
a no-op context manager.  The span name is the stable contract; keyword
metadata rides along as the event's stats (spans of one tick share
``tick=t``).  Spans go at layer boundaries on the host only: never inside a
jitted function, never one per node.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.profiler import TraceAnnotation

PULL = "faasmeter.pull"
PUT = "faasmeter.put"


def span(name: str, **meta) -> TraceAnnotation:
    """A host span ``name`` over a ``with`` block, carrying ``meta``."""
    return TraceAnnotation(name, **meta)


def pull(x, site: str, **meta) -> np.ndarray:
    """``np.asarray(x)`` of a device array inside a ``faasmeter.pull`` span.

    Every blocking device->host transfer on the tick path goes through here,
    one call per array, so the trace counts and times them by ``site``;
    ``shards`` is how many devices the array is gathered from.
    """
    with TraceAnnotation(PULL, site=site, shards=len(x.sharding.device_set), **meta):
        return np.asarray(x)


def put(x, site: str, sharding=None, **meta) -> jax.Array:
    """``jax.device_put(x, sharding)`` of host data inside a ``faasmeter.put`` span.

    The mirror of ``pull``: every host->device transfer on the tick path
    goes through here, one call per array.  ``sharding=None`` places ``x``
    on the default device; a ``NamedSharding`` splits it on the host and
    sends each device its own block, with no stop on another device.
    ``shards`` is how many devices the placed array spans.
    """
    shards = 1 if sharding is None else len(sharding.device_set)
    with TraceAnnotation(PUT, site=site, shards=shards, **meta):
        return jax.device_put(x, sharding)
