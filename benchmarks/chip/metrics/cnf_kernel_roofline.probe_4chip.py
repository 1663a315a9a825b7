"""The fused CNF kernel's share of its roofline on one device, in %: the
least time of one device's band step (its L shard against the mean real
columns of the window's band steps, so the padded columns of a band do not
count as work; stage_work.py, kernel_work.py) over the kernel's mean
device time per call in the trace."""

import kernel_work
import reduce
import stage_work


def read(ctx):
    config = stage_work.deployment(ctx) if ctx.trace else None
    if config is None:
        return None
    seconds, calls = reduce.op_time(ctx.trace, reduce.KERNEL)
    if not calls:
        return None
    cols = sum(ctx.step_pairs) / len(ctx.step_pairs) / int(config["rows_l"])
    least, _ = kernel_work.least_time(
        stage_work.band_step_work(config, ctx.chips, cols), ctx.device_kind)
    return least / (seconds / calls) * 100.0
