"""Host self time per emitted tick of the control plane's per-node tracker loop (us):
the ``faasmeter.control.trackers`` spans in the traced window (``host_spans.per_tick``).
"""


def read(ctx):
    return ctx["host"].get("tracker_host_us")
