"""The least time the chip could take for the grouped top-k kernel's work.

The work is counted from the live rows the kernel scanned, never from the
padded shapes it ran at: the same rows cost the same whatever implements
them, and padding shows as a lower share.  Per live row the kernel reads
its ``dim`` int8 codes and does ``2 * dim`` operations (a multiply and an
add per coordinate); per group it reads the float32 query and the two
float32 dequantization parameters.  At 2 operations per byte the kernel
is bound by bytes on any chip in ``peaks.PEAKS``.
"""
from __future__ import annotations


def grouped_topk_work(rows: int, groups: int, dim: int) -> tuple[float, float]:
    """(bytes, operations) of scanning ``rows`` live int8 rows in ``groups``
    (query, leaf) units."""
    nbytes = rows * dim + groups * (4 * dim + 8)
    ops = 2.0 * rows * dim
    return float(nbytes), ops


def least_seconds(nbytes: float, ops: float, peak: dict) -> float:
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["int8_ops"])
