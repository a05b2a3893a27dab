"""Host self time per emitted tick of the session's dispatch and emit (us): the
``faasmeter.session.dispatch`` and ``faasmeter.session.emit`` spans less the
spans nested in them (``host_spans.per_tick``).
"""


def read(ctx):
    return ctx["host"].get("session_host_us")
