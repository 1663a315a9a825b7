"""Device time of the band-step program outside the fused CNF kernel, per
band step, in ms: on-device candidate extraction (engine/extract.py), the
mask transpose and the count offsets."""

import reduce


def read(ctx):
    if not ctx.trace:
        return None
    program, steps = reduce.op_time(ctx.trace, reduce.BAND_STEP,
                                    reduce.MODULES_LINE)
    kernel, _ = reduce.op_time(ctx.trace, reduce.KERNEL)
    if not steps:
        return None
    return (program - kernel) / steps * 1e3
