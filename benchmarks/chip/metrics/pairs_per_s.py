"""Step-2 throughput: cross-product pairs of every band step of the window's
queries, over the window (its start to the end of its last query)."""


def read(ctx):
    t0, t1 = ctx.window
    if not ctx.step_pairs or t1 <= t0:
        return None
    return sum(ctx.step_pairs) / (t1 - t0)
