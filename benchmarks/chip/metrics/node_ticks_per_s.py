"""Nodes x ticks the consumer received inside the window, per second."""


def read(ctx):
    return ctx["emitted"] * ctx["nodes"] / ctx["seconds"]
