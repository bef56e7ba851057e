"""The work a k-column sparse multiply needs, whatever implements it.

Bytes: every stored value and its column index once (8 bytes per nonzero
in float32 with int32 indices), one row pointer per row plus one, X read
once and Y written once for the k columns served. Operations: one
multiply and one add per stored nonzero and column. The count does not
depend on the storage format or its padding, so a change of format moves
the share of the roofline, never the yardstick.
"""
from __future__ import annotations


def spmm_bytes(m: int, n: int, nnz: int, k: int, *, val_bytes: int = 4,
               idx_bytes: int = 4) -> int:
    return ((val_bytes + idx_bytes) * nnz + idx_bytes * (m + 1)
            + val_bytes * k * (n + m))


def spmm_flops(nnz: int, k: int) -> int:
    return 2 * nnz * k


def least_time_s(m: int, n: int, nnz: int, k: int, peaks: dict) -> float:
    """The least time the chip could take: the larger of bytes over peak
    HBM bandwidth and operations over peak FLOP/s."""
    return max(spmm_bytes(m, n, nnz, k) / peaks["hbm_bytes_per_s"],
               spmm_flops(nnz, k) / peaks["flops_per_s"])
