"""Device->host pulls per emitted tick: the ``faasmeter.pull`` spans that start in
the traced window (``host_spans.per_tick``).
"""


def read(ctx):
    return ctx["host"].get("device_pulls_per_tick")
