"""Device time per emitted tick of the program compiled from ``fleet_step`` (us)."""


def read(ctx):
    runs = [v for k, v in ctx["reduced"]["programs"].items() if "fleet_step" in k]
    if not runs or not ctx["ticks"]:
        return None
    return 1e6 * sum(v["seconds"] for v in runs) / ctx["ticks"]
