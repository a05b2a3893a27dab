"""Host self time per emitted tick of pushing each telemetry window into the
session (us): the ``faasmeter.ingest.push`` spans less the spans nested in
them (``host_spans.per_tick``).
"""


def read(ctx):
    return ctx["host"].get("ingest_host_us")
