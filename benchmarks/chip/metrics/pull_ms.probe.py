"""Host time in the step-2 ring's ``pull`` spans per finished query, in ms:
the wait for each band step's counts, the candidate pull and the host's
conversion to pairs (engine/sharded.py)."""

import reduce


def read(ctx):
    return reduce.span_ms_per_query(ctx.spans, "pull", ctx.queries) \
        if ctx.spans else None
