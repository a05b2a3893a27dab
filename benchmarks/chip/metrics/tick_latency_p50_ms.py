"""Median (50th percentile) of the latency of every tick due in the window (ms).

A tick is due when the last raw window it needs (its own plus the sync
lookahead) was due on the open-loop schedule; its latency runs from then to
when the ``on_tick`` consumer received its attribution.
"""

import numpy as np


def read(ctx):
    lat = ctx["latency_ms"]
    if lat is None or lat.size == 0:
        return None
    return float(np.percentile(lat, 50))
