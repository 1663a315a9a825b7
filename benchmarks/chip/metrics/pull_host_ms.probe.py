"""Host time in the step-2 ring's own work after each band step's counts
are on the host, per finished query, in ms: the ``fetch`` spans (bases and
candidate buffers moved device-to-host) plus the ``to_pairs`` spans
(padding filter, tuple conversion) of engine/sharded.py."""

import marks


def read(ctx):
    parts = [marks.span_values_per_query(ctx.spans, name, ctx.queries,
                                         lambda sp: sp.t1 - sp.t0)
             for name in ("fetch", "to_pairs")]
    return None if None in parts else sum(parts) * 1e3
