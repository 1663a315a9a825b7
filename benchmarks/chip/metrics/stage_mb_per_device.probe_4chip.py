"""Bytes of the kernel-layout planes that staging wrote on the fullest
device, per finished query, in MB (10^6 B): the ``bytes_staged_device``
counter of the ``stage_planes`` spans (kernels/fused_cnf_join/ops.py), one
L shard and the whole R band stack, 0 for a query whose plane set already
held its assembly."""

import marks


def read(ctx):
    if not any("bytes_staged_device" in sp.attrs for sp in ctx.spans
               if sp.name == "stage_planes"):
        return None
    staged = marks.span_values_per_query(
        ctx.spans, "stage_planes", ctx.queries,
        lambda sp: sp.attrs["bytes_staged_device"])
    return None if staged is None else staged / 1e6
