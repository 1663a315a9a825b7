"""The staging program's share of its HBM roofline, in %: the least time of
one device's staging (stage_work.py: one read and one write of its
resident L rows at their real widths, against HBM bandwidth) over the mean
device time of the ``fdj_stage_shards`` module per call in the trace."""

import reduce
import stage_work


def read(ctx):
    config = stage_work.deployment(ctx) if ctx.trace else None
    if config is None:
        return None
    seconds, calls = reduce.op_time(ctx.trace, stage_work.STAGE,
                                    reduce.MODULES_LINE)
    if not calls:
        return None
    least = stage_work.stage_least_s(config, ctx.chips, ctx.device_kind)
    return least / (seconds / calls) * 100.0
