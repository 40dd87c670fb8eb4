"""Published peaks of the chips the benchmark runs on, by `device_kind`.

TPU v5e: Google Cloud documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
A kind that is not listed is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
}


def for_kind(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]
