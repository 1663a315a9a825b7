"""Share of the traced window in which no operation ran on the device, in %
(1 - union of the device-op intervals over the window)."""

import reduce


def read(ctx):
    return reduce.idle_share(ctx.trace) if ctx.trace else None
