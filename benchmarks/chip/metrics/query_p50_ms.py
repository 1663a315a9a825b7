"""Median latency of the queries finished in the window, in ms: from the
query's start (its new rows still on the host) to its last candidate on
the host."""

import numpy as np


def read(ctx):
    lat = [(q.t1 - q.t0) * 1e3 for q in ctx.queries if q.complete]
    return float(np.percentile(lat, 50)) if lat else None
