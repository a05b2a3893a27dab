"""Host time per emitted tick of launching the engine's ``fleet_step`` (us): the
``faasmeter.engine.fleet_step`` spans in the traced window
(``host_spans.per_tick``).
"""


def read(ctx):
    return ctx["host"].get("fleet_step_host_us")
