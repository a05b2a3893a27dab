"""Host time per emitted tick of the tick path's host->device puts (us): the
``faasmeter.put`` spans in the traced window (``host_spans.per_tick``); none
on a program without put spans.
"""


def read(ctx):
    return ctx["host"].get("device_put_us")
