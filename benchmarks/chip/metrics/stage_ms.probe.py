"""Host time in the ``stage_planes`` span per finished query, in ms: the
on-device assembly of the resident and new planes into the kernel's layout
(kernels/fused_cnf_join/ops.py)."""

import reduce


def read(ctx):
    return reduce.span_ms_per_query(ctx.spans, "stage_planes", ctx.queries) \
        if ctx.spans else None
