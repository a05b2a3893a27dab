"""Device program executions in the traced window per emitted tick."""


def read(ctx):
    progs = ctx["reduced"]["programs"]
    if not progs or not ctx["ticks"]:
        return None
    return sum(v["count"] for v in progs.values()) / ctx["ticks"]
