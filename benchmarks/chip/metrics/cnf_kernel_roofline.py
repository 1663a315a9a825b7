"""The fused CNF kernel's share of its roofline, in %: the least time of one
band step's work (kernel_work.py, against the peak of the planes' dtype)
over the kernel's mean device time per call in the trace."""

import kernel_work
import reduce


def read(ctx):
    if not ctx.trace:
        return None
    seconds, calls = reduce.op_time(ctx.trace, reduce.KERNEL)
    if not calls:
        return None
    least, _ = kernel_work.least_time(ctx.work, ctx.device_kind)
    return least / (seconds / calls) * 100.0
