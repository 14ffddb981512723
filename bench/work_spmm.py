"""The work of a k-vector multiply Y = A X, counted from shapes.

`spmm_floor_bytes` is the least any layout can move with float32 values:
every value read once, X read once and Y written once, k floats a row.
Indices are left out, as `work.spmv_floor_bytes` leaves them out.
"""
from __future__ import annotations

from bench.work import VALUE_BYTES


def spmm_flops(nnz: int, k: int) -> int:
    return 2 * int(nnz) * int(k)


def spmm_floor_bytes(nnz: int, n_rows: int, n_cols: int, k: int) -> int:
    return VALUE_BYTES * (int(nnz) + (int(n_cols) + int(n_rows)) * int(k))


def spmm_floor_seconds(nnz: int, n_rows: int, n_cols: int, k: int,
                       peak: dict, chips: int = 1) -> float:
    """The least time a multiply can take on `chips` chips: the larger of
    the byte and the operation bounds (bytes, by an order at k = 256)."""
    return max(spmm_floor_bytes(nnz, n_rows, n_cols, k)
               / (peak["hbm_bytes_per_s"] * chips),
               spmm_flops(nnz, k) / (peak["bf16_flops_per_s"] * chips))
