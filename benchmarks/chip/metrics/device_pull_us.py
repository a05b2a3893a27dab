"""Host time per emitted tick of the tick path's device->host pulls (us): the
``faasmeter.pull`` spans in the traced window (``host_spans.per_tick``).
"""


def read(ctx):
    return ctx["host"].get("device_pull_us")
