"""The work of a multiply, counted from shapes, and the chip's peaks.

`spmv_floor_bytes` is the least any format can move for y = A x with
float32 values: every value read once, x read once, y written once.
Indices are left out, since a format may make them implicit (DIA).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import jax

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
VALUE_BYTES = 4


def peaks(device_kind: str) -> dict:
    """The peak table's row for a device kind; unknown kinds raise."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def spmv_flops(nnz: int) -> int:
    return 2 * int(nnz)


def spmv_floor_bytes(nnz: int, n_rows: int, n_cols: int) -> int:
    return VALUE_BYTES * (int(nnz) + int(n_cols) + int(n_rows))


def spmv_floor_seconds(nnz: int, n_rows: int, n_cols: int, peak: dict,
                       chips: int = 1) -> float:
    """The least time a multiply can take on `chips` chips: the larger of
    the byte and the operation bounds (bytes, by three orders)."""
    return max(spmv_floor_bytes(nnz, n_rows, n_cols)
               / (peak["hbm_bytes_per_s"] * chips),
               spmv_flops(nnz) / (peak["bf16_flops_per_s"] * chips))


def device_arrays(obj) -> list:
    """Every jax array held by a prepared layout, whether it is a
    registered pytree or a plain dataclass of arrays."""
    out = []
    for leaf in jax.tree.leaves(obj):
        if isinstance(leaf, jax.Array):
            out.append(leaf)
        elif dataclasses.is_dataclass(leaf):
            for f in dataclasses.fields(leaf):
                out.extend(device_arrays(getattr(leaf, f.name)))
    return out


def layout_bytes(prep) -> int:
    return sum(int(a.nbytes) for a in device_arrays(prep))
