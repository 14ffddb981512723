"""Sparse-matrix containers used throughout the framework.

The paper stores matrices in CSR (values / col-indices / row-pointers,
2m + n + 1 elements).  On TPU we additionally provide formats whose access
pattern is *structurally* friendly to the HBM->VMEM DMA engine:

  * CSR   -- the paper's format; row-pointer driven, good for scalar-prefetch
             Pallas grids.
  * ELL   -- fixed nnz/row, row-major padded; vectorizes on the VPU.
  * BELL  -- blocked-ELL: (bm x bn) dense blocks, fixed blocks per row-block.
             The TPU-native unstructured format (blocks are lane-aligned, so
             every gather moves a useful 2-D tile instead of 8 wasted lanes).
  * DIA   -- diagonal/banded storage; the FD fast path (x-windows contiguous).
  * HYB   -- hybrid row split for power-law matrices: rows above an nnz
             threshold move to a column-sorted COO heavy partition (hub
             rows stream x instead of thrashing it), the structured
             remainder stays ELL with a small width.

All containers are registered pytrees of jnp arrays so they pass through
jit/pjit unharmed; construction happens host-side in numpy.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def _register(cls):
    """Register a dataclass as a pytree (arrays = leaves, ints = static)."""
    fields = [f.name for f in dataclasses.fields(cls)]
    array_fields = [f for f in fields if f not in cls._static]
    static_fields = [f for f in fields if f in cls._static]

    def flatten(obj):
        return (
            tuple(getattr(obj, f) for f in array_fields),
            tuple(getattr(obj, f) for f in static_fields),
        )

    def unflatten(static, arrays):
        kwargs = dict(zip(array_fields, arrays))
        kwargs.update(dict(zip(static_fields, static)))
        return cls(**kwargs)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


@_register
@dataclasses.dataclass(frozen=True)
class CSR:
    """Compressed sparse row.  2m + n + 1 stored elements (paper §II-A)."""

    _static = ("n_rows", "n_cols")

    data: Array        # (nnz,) values
    indices: Array     # (nnz,) column index per nonzero
    indptr: Array      # (n_rows + 1,) offsets into data
    n_rows: int
    n_cols: int

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_cols)

    def storage_bytes(self) -> int:
        return (
            self.data.size * self.data.dtype.itemsize
            + self.indices.size * self.indices.dtype.itemsize
            + self.indptr.size * self.indptr.dtype.itemsize
        )

    @staticmethod
    def from_coo(rows, cols, vals, n_rows, n_cols, dtype=np.float32) -> "CSR":
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals, dtype=dtype)
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        indptr = np.zeros(n_rows + 1, dtype=np.int32)
        np.add.at(indptr, rows + 1, 1)
        indptr = np.cumsum(indptr, dtype=np.int64)
        if indptr[-1] < np.iinfo(np.int32).max:
            indptr = indptr.astype(np.int32)
        return CSR(
            data=jnp.asarray(vals),
            indices=jnp.asarray(cols.astype(np.int32)),
            indptr=jnp.asarray(indptr),
            n_rows=int(n_rows),
            n_cols=int(n_cols),
        )

    def to_dense(self) -> Array:
        out = np.zeros(self.shape, dtype=np.asarray(self.data).dtype)
        indptr = np.asarray(self.indptr)
        cols = np.asarray(self.indices)
        vals = np.asarray(self.data)
        for r in range(self.n_rows):
            lo, hi = int(indptr[r]), int(indptr[r + 1])
            np.add.at(out[r], cols[lo:hi], vals[lo:hi])
        return jnp.asarray(out)

    def row_lengths(self) -> np.ndarray:
        indptr = np.asarray(self.indptr)
        return np.diff(indptr)

    def apply_delta(self, delta) -> "CSR":
        """Materialize this matrix with an `repro.core.delta.EdgeDelta`
        applied: deleted coordinates removed structurally, inserts
        appended, result rebuilt canonically through `from_coo`.  The
        streaming plan lifecycle calls this when a delta outgrows its
        overlay budget and the plan re-compiles."""
        from .delta import apply_delta as _apply
        return _apply(self, delta)

    def permute(self, row_perm=None, col_perm=None) -> "CSR":
        """A' with A'[i, j] = A[row_perm[i], col_perm[j]].

        `row_perm[i]` names the OLD row placed at NEW position i (the
        convention of `repro.reorder.Reordering`); either perm may be None
        for identity.  Raises ValueError on a non-permutation (duplicate or
        out-of-range index), which would otherwise corrupt silently.
        Rebuilds through `from_coo`, so the result is canonically
        (row, col)-sorted.
        """
        def invert(perm, n, name):
            perm = np.asarray(perm, dtype=np.int64)
            if perm.shape != (n,) or \
                    not np.array_equal(np.bincount(perm, minlength=n),
                                       np.ones(n, dtype=np.int64)):
                raise ValueError(f"{name} is not a permutation of range({n})")
            inv = np.empty(n, dtype=np.int64)
            inv[perm] = np.arange(n, dtype=np.int64)
            return inv

        indptr = np.asarray(self.indptr, dtype=np.int64)
        cols = np.asarray(self.indices, dtype=np.int64)
        vals = np.asarray(self.data)
        rows = np.repeat(np.arange(self.n_rows, dtype=np.int64),
                         np.diff(indptr))
        if row_perm is not None:
            rows = invert(row_perm, self.n_rows, "row_perm")[rows]
        if col_perm is not None:
            cols = invert(col_perm, self.n_cols, "col_perm")[cols]
        return CSR.from_coo(rows, cols, vals, self.n_rows, self.n_cols,
                            dtype=vals.dtype)


@_register
@dataclasses.dataclass(frozen=True)
class ELL:
    """ELLPACK: every row padded to `max_nnz` entries (pad col = 0, val = 0)."""

    _static = ("n_rows", "n_cols", "max_nnz")

    data: Array        # (n_rows, max_nnz)
    indices: Array     # (n_rows, max_nnz) int32; padding points at col 0
    n_rows: int
    n_cols: int
    max_nnz: int

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_cols)

    @staticmethod
    def from_csr(csr: CSR, max_nnz: int | None = None,
                 fill: float = 0.0) -> "ELL":
        """`fill` is the padding value for short rows -- 0.0 for plus-times
        SpMV, the semiring's absorbing element (`Semiring.pad_value`, e.g.
        +inf for min-plus) when the container feeds a semiring kernel."""
        lengths = csr.row_lengths()
        width = (int(lengths.max()) if len(lengths) else 0) \
            if max_nnz is None else int(max_nnz)
        data = np.full((csr.n_rows, width), fill,
                       dtype=np.asarray(csr.data).dtype)
        idx = np.zeros((csr.n_rows, width), dtype=np.int32)
        indptr = np.asarray(csr.indptr)
        cols = np.asarray(csr.indices)
        vals = np.asarray(csr.data)
        for r in range(csr.n_rows):
            lo, hi = int(indptr[r]), int(indptr[r + 1])
            k = min(hi - lo, width)
            data[r, :k] = vals[lo:lo + k]
            idx[r, :k] = cols[lo:lo + k]
        return ELL(
            data=jnp.asarray(data),
            indices=jnp.asarray(idx),
            n_rows=csr.n_rows,
            n_cols=csr.n_cols,
            max_nnz=width,
        )

    def storage_bytes(self) -> int:
        return (
            self.data.size * self.data.dtype.itemsize
            + self.indices.size * self.indices.dtype.itemsize
        )


@_register
@dataclasses.dataclass(frozen=True)
class BELL:
    """Blocked-ELL: (bm, bn) dense blocks, fixed `blocks_per_row` per block-row.

    This is the TPU-native unstructured format: each gathered unit is a dense
    (bm, bn) tile whose bn is lane-aligned, so a "random access" still moves a
    fully-useful 2-D tile through the DMA engine.  Padding blocks have
    block_col 0 and all-zero data.
    """

    _static = ("n_rows", "n_cols", "bm", "bn", "blocks_per_row")

    data: Array        # (n_block_rows, blocks_per_row, bm, bn)
    block_cols: Array  # (n_block_rows, blocks_per_row) int32 block-col index
    n_rows: int
    n_cols: int
    bm: int
    bn: int
    blocks_per_row: int

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_cols)

    @property
    def n_block_rows(self) -> int:
        return -(-self.n_rows // self.bm)

    @staticmethod
    def from_csr(csr: CSR, bm: int = 8, bn: int = 128,
                 blocks_per_row: int | None = None) -> "BELL":
        nbr = -(-csr.n_rows // bm)
        nbc = -(-csr.n_cols // bn)
        indptr = np.asarray(csr.indptr, dtype=np.int64)
        cols = np.asarray(csr.indices, dtype=np.int64)
        vals = np.asarray(csr.data)
        rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64),
                         np.diff(indptr))
        # bucket nonzeros by (block_row, block_col); a block row's blocks
        # are numbered in ascending block-column order
        keys, blk = np.unique((rows // bm) * nbc + cols // bn,
                              return_inverse=True)
        blk = blk.reshape(-1)
        kbr, kbc = keys // nbc, keys % nbc
        first = np.searchsorted(kbr, np.arange(nbr))
        kslot = np.arange(keys.size) - first[kbr]
        width = blocks_per_row or (
            int(np.bincount(kbr, minlength=1).max()) if keys.size else 1)
        width = max(width, 1)
        data = np.zeros((nbr, width, bm, bn), dtype=vals.dtype)
        bcols = np.zeros((nbr, width), dtype=np.int32)
        keep = kslot < width
        bcols[kbr[keep], kslot[keep]] = kbc[keep]
        nz = keep[blk]
        np.add.at(data, (rows[nz] // bm, kslot[blk[nz]], rows[nz] % bm,
                         cols[nz] % bn), vals[nz])
        return BELL(
            data=jnp.asarray(data),
            block_cols=jnp.asarray(bcols),
            n_rows=csr.n_rows, n_cols=csr.n_cols,
            bm=bm, bn=bn, blocks_per_row=width,
        )

    def storage_bytes(self) -> int:
        return (
            self.data.size * self.data.dtype.itemsize
            + self.block_cols.size * self.block_cols.dtype.itemsize
        )

    def density(self) -> float:
        """Fraction of stored block entries that are true nonzeros."""
        return float(np.count_nonzero(np.asarray(self.data))) / self.data.size


@_register
@dataclasses.dataclass(frozen=True)
class DIA:
    """Diagonal (banded) storage: the FD fast path.

    `data[k, i]` is A[i, i + offsets[k]].  Out-of-range entries are zero.
    x-accesses for diagonal k are the contiguous window x[off_k : off_k + n] --
    the structurally perfect case from the paper's Fig. 2.
    """

    _static = ("n_rows", "n_cols")

    data: Array      # (n_diags, n_rows)
    offsets: Array   # (n_diags,) int32, column offset of each diagonal
    n_rows: int
    n_cols: int

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_cols)

    @staticmethod
    def from_csr(csr: CSR) -> "DIA":
        indptr = np.asarray(csr.indptr)
        cols = np.asarray(csr.indices)
        vals = np.asarray(csr.data)
        rows = np.repeat(np.arange(csr.n_rows), np.diff(indptr))
        offs = cols.astype(np.int64) - rows
        uniq = np.unique(offs)
        data = np.zeros((len(uniq), csr.n_rows), dtype=vals.dtype)
        np.add.at(data, (np.searchsorted(uniq, offs), rows), vals)
        return DIA(
            data=jnp.asarray(data),
            offsets=jnp.asarray(uniq.astype(np.int32)),
            n_rows=csr.n_rows, n_cols=csr.n_cols,
        )

    @property
    def n_diags(self) -> int:
        return int(self.offsets.shape[0])

    def storage_bytes(self) -> int:
        return (
            self.data.size * self.data.dtype.itemsize
            + self.offsets.size * self.offsets.dtype.itemsize
        )


def hyb_auto_threshold(row_lengths) -> int:
    """Default heavy-row cutoff: the median nnz/row (>= 2).

    The cut is the *typical* row, not the mean: power-law matrices have
    median ≪ mean (most rows are near-empty, hubs carry the mass), so
    everything past the typical row -- the hubs and the heavy tail that
    hold most nonzeros -- moves to the column-sorted heavy stream whose
    x gathers ascend, and the light ELL slab stays narrow instead of
    being sized by outliers.  Near-uniform matrices have median ≈ max,
    so no row crosses the cut and the split degenerates to plain ELL.
    (A mean-based cut keeps the tail rows in the slab and its width
    balloons: at 2^12 R-MAT a 2x-mean cut doubles the streamed slab
    footprint and costs ~2x the warm cycles of this cut.)"""
    lens = np.asarray(row_lengths)
    if lens.size == 0:
        return 2
    return max(2, int(np.median(lens)))


@_register
@dataclasses.dataclass(frozen=True)
class HYB:
    """Hybrid row split: ELL light partition + column-sorted COO heavy tail.

    Rows with more than `threshold` nonzeros are routed whole to the heavy
    partition, stored as flat COO sorted by (column, row): hub-row x
    gathers become one ascending streaming pass over x instead of a
    random walk, and the few hub y rows stay resident.  Remaining rows
    keep ELL layout over the FULL row range (heavy rows are all-padding
    there), so the light width is bounded by `threshold` instead of the
    hub-row maximum.  `fill` pads short light rows -- 0.0 for plus-times,
    the semiring's absorbing element otherwise (same contract as ELL).
    """

    _static = ("n_rows", "n_cols", "threshold", "light_width")

    data: Array        # (n_rows, light_width) light values; padding `fill`
    indices: Array     # (n_rows, light_width) int32; padding points at col 0
    hvals: Array       # (heavy_nnz,) heavy values, column-sorted
    hrows: Array       # (heavy_nnz,) int32 global row per heavy nonzero
    hcols: Array       # (heavy_nnz,) int32 column per heavy nonzero, ascending
    n_rows: int
    n_cols: int
    threshold: int
    light_width: int

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_cols)

    @property
    def heavy_nnz(self) -> int:
        return int(self.hvals.shape[0])

    def heavy_row_ids(self) -> np.ndarray:
        return np.unique(np.asarray(self.hrows))

    @staticmethod
    def from_csr(csr: CSR, threshold: int | None = None,
                 fill: float = 0.0) -> "HYB":
        lengths = csr.row_lengths()
        thr = hyb_auto_threshold(lengths) if threshold is None \
            else int(threshold)
        heavy_rows = np.flatnonzero(lengths > thr)
        heavy_set = np.zeros(csr.n_rows, dtype=bool)
        heavy_set[heavy_rows] = True

        indptr = np.asarray(csr.indptr, dtype=np.int64)
        cols = np.asarray(csr.indices, dtype=np.int64)
        vals = np.asarray(csr.data)
        rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64),
                         np.diff(indptr))
        is_heavy = heavy_set[rows] if len(rows) else \
            np.zeros(0, dtype=bool)

        hr, hc, hv = rows[is_heavy], cols[is_heavy], vals[is_heavy]
        order = np.lexsort((hr, hc))          # ascending column, then row
        hr, hc, hv = hr[order], hc[order], hv[order]

        lr, lc, lv = rows[~is_heavy], cols[~is_heavy], vals[~is_heavy]
        light_lens = np.where(heavy_set, 0, lengths) if len(lengths) else \
            lengths
        width = int(light_lens.max()) if light_lens.size else 0
        data = np.full((csr.n_rows, width), fill, dtype=vals.dtype)
        idx = np.zeros((csr.n_rows, width), dtype=np.int32)
        if len(lr):
            light_ptr = np.zeros(csr.n_rows + 1, dtype=np.int64)
            np.add.at(light_ptr, lr + 1, 1)
            light_ptr = np.cumsum(light_ptr)
            inner = np.arange(len(lr), dtype=np.int64) - light_ptr[lr]
            data[lr, inner] = lv
            idx[lr, inner] = lc.astype(np.int32)
        return HYB(
            data=jnp.asarray(data), indices=jnp.asarray(idx),
            hvals=jnp.asarray(hv),
            hrows=jnp.asarray(hr.astype(np.int32)),
            hcols=jnp.asarray(hc.astype(np.int32)),
            n_rows=csr.n_rows, n_cols=csr.n_cols,
            threshold=thr, light_width=width,
        )

    def storage_bytes(self) -> int:
        return (
            self.data.size * self.data.dtype.itemsize
            + self.indices.size * self.indices.dtype.itemsize
            + self.hvals.size * self.hvals.dtype.itemsize
            + self.hrows.size * self.hrows.dtype.itemsize
            + self.hcols.size * self.hcols.dtype.itemsize
        )
