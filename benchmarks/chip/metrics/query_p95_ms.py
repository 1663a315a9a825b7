"""95th percentile of the latency of the queries finished in the window, in
ms (numpy's linear interpolation between order statistics)."""

import numpy as np


def read(ctx):
    lat = [(q.t1 - q.t0) * 1e3 for q in ctx.queries if q.complete]
    return float(np.percentile(lat, 95)) if lat else None
