"""Operations and bytes the fused CNF kernel needs, and its roofline.

For one band step of ``rows`` L rows against ``cols`` R columns, at the
real (unpadded) rows and widths: every embed feature the CNF names costs a
dot product of its width per pair (``2 * width`` operations); scalar
distances, the threshold compares and the mask packing are not counted.
The kernel has to read each feature's L and R planes once and write one bit
per pair.  The least time is the larger of operations over the peak rate of
the planes' dtype and bytes over HBM bandwidth.
"""

from __future__ import annotations

from peaks import peak

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def band_step_work(rows: int, cols: int, features: list, clauses: list,
                   dtype: str = "float32") -> dict:
    """``{"ops", "bytes", "dtype"}`` of one band step; ``features`` are the
    deployment's feature entries (kind, width), embeds widened by the two
    marker columns the planes carry."""
    used = sorted({f for c in clauses for f in c})
    size = DTYPE_BYTES[dtype]
    ops = 0
    nbytes = rows * cols // 8                                 # packed mask
    for f in used:
        feat = features[f]
        if feat["kind"] == "embed":
            width = int(feat["width"]) + 2
            ops += 2 * rows * cols * width
            nbytes += (rows + cols) * width * size
        else:
            nbytes += (rows + cols) * size
    return {"ops": ops, "bytes": nbytes, "dtype": dtype}


def least_time(work: dict, device_kind: str) -> tuple:
    """``(seconds, bound)``: the least time of ``work`` on the chip and
    which of ``"compute"`` or ``"memory"`` sets it."""
    ops_per_s, bytes_per_s = peak(device_kind, work["dtype"])
    t_ops, t_bytes = work["ops"] / ops_per_s, work["bytes"] / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
