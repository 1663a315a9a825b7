"""Peak rates of each chip the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
float32 and bfloat16 work are read against the bfloat16 peak, int8 work
against the int8 peak.  A device kind missing from the table is an error.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "ops_per_s": {"bfloat16": 197e12, "float32": 197e12, "int8": 393e12},
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, dtype: str) -> tuple:
    """``(ops_per_s, hbm_bytes_per_s)`` of ``device_kind`` for work in
    ``dtype``; raises ``KeyError`` for a chip or dtype not in the table."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to benchmarks/chip/peaks.py with its source")
    entry = PEAKS[device_kind]
    if dtype not in entry["ops_per_s"]:
        raise KeyError(f"no {dtype} peak for {device_kind!r}")
    return entry["ops_per_s"][dtype], entry["hbm_bytes_per_s"]
