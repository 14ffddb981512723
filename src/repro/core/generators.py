"""Matrix generators from the paper.

Two families (paper §II-A):

  * FD    -- 2-D 9-point-stencil finite-difference matrices: three diagonal
             bands of three nonzeros each, exactly 9 nnz/row (periodic
             boundaries, matching the paper's nnz = 9 * 2^k accounting).
  * R-MAT -- recursive power-law graphs (Chakrabarti et al.), 8 nnz/row on
             average, rows+columns randomly permuted to remove load imbalance
             (exactly as the paper does).

Plus auxiliary generators (uniform-random, variable-bandwidth banded) used by
structure sweeps and property tests.  All generation is host-side numpy.
"""
from __future__ import annotations

import numpy as np

from .formats import CSR

# Graph500-style R-MAT quadrant probabilities.
RMAT_A, RMAT_B, RMAT_C, RMAT_D = 0.57, 0.19, 0.19, 0.05


def fd_matrix(n_rows: int, dtype=np.float32, seed: int = 0) -> CSR:
    """2-D 9-point-stencil FD matrix with periodic boundaries.

    The grid is g x g with g = floor(sqrt(n_rows)) rounded so that g*g is
    close to n_rows; we use exactly n_rows = g*g when possible, otherwise a
    g x h grid with g*h == n_rows (h = n_rows // g).  Every row has exactly
    nine nonzeros: itself and its eight (periodic) grid neighbours, which
    yields the paper's three bands of three adjacent elements (fewer on
    a grid with a side under 3, where periodic neighbours coincide).
    """
    g = int(np.sqrt(n_rows))
    while n_rows % g != 0:
        g -= 1
    h = n_rows // g  # grid is g rows x h cols, row-major node numbering
    rng = np.random.default_rng(seed)

    node = np.arange(n_rows, dtype=np.int64)
    gi, gj = node // h, node % h
    rows, cols = [], []
    for di in (-1, 0, 1):
        for dj in (-1, 0, 1):
            ni = (gi + di) % g
            nj = (gj + dj) % h
            rows.append(node)
            cols.append(ni * h + nj)
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    vals = rng.uniform(0.5, 1.5, size=rows.shape[0]).astype(dtype)
    if min(g, h) < 3:
        # a grid side under 3 wraps neighbours onto each other: merge the
        # repeated coordinates so the CSR stays duplicate-free
        keys, inv = np.unique(rows * n_rows + cols, return_inverse=True)
        merged = np.zeros(keys.size, dtype=dtype)
        np.add.at(merged, inv, vals)
        rows, cols, vals = keys // n_rows, keys % n_rows, merged
    return CSR.from_coo(rows, cols, vals, n_rows, n_rows, dtype=dtype)


def rmat_edges(n_rows: int, n_edges: int, seed: int = 0,
               a: float = RMAT_A, b: float = RMAT_B,
               c: float = RMAT_C) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized R-MAT edge generation (levels = log2 n)."""
    assert n_rows & (n_rows - 1) == 0, "R-MAT needs power-of-two dimension"
    levels = int(np.log2(n_rows))
    rng = np.random.default_rng(seed)
    rows = np.zeros(n_edges, dtype=np.int64)
    cols = np.zeros(n_edges, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for _ in range(levels):
        r = rng.random(n_edges)
        go_down = (r >= ab).astype(np.int64)          # quadrants c, d
        go_right = ((r >= a) & (r < ab)) | (r >= abc)  # quadrants b, d
        rows = (rows << 1) | go_down
        cols = (cols << 1) | go_right.astype(np.int64)
    return rows, cols


def rmat_matrix(n_rows: int, nnz_per_row: int = 8, dtype=np.float32,
                seed: int = 0, permute: bool = True) -> CSR:
    """R-MAT matrix with ~nnz_per_row average nonzeros/row.

    Duplicate edges are summed (dedup keeps avg-nnz close to the target).
    Rows and columns are randomly permuted (paper §II-A) so the power-law
    hubs do not create thread-level load imbalance.
    """
    n_edges = n_rows * nnz_per_row
    rows, cols = rmat_edges(n_rows, n_edges, seed=seed)
    if permute:
        rng = np.random.default_rng(seed + 1)
        rperm = rng.permutation(n_rows)
        cperm = rng.permutation(n_rows)
        rows = rperm[rows]
        cols = cperm[cols]
    rng2 = np.random.default_rng(seed + 2)
    vals = rng2.uniform(0.5, 1.5, size=n_edges).astype(dtype)
    # merge duplicates by (row, col)
    key = rows * n_rows + cols
    order = np.argsort(key, kind="stable")
    key, rows, cols, vals = key[order], rows[order], cols[order], vals[order]
    uniq_mask = np.empty(len(key), dtype=bool)
    uniq_mask[0] = True
    np.not_equal(key[1:], key[:-1], out=uniq_mask[1:])
    seg_id = np.cumsum(uniq_mask) - 1
    merged_vals = np.zeros(int(seg_id[-1]) + 1, dtype=dtype)
    np.add.at(merged_vals, seg_id, vals)
    return CSR.from_coo(rows[uniq_mask], cols[uniq_mask], merged_vals,
                        n_rows, n_rows, dtype=dtype)


def banded_matrix(n_rows: int, bandwidth: int, nnz_per_row: int = 9,
                  dtype=np.float32, seed: int = 0) -> CSR:
    """Banded matrix with nonzeros uniform inside |c - r| <= bandwidth.

    Interpolates between FD-like (tiny bandwidth) and R-MAT-like (bandwidth
    ~ n) structure: the knob used by the structure-sweep benchmarks.
    """
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), nnz_per_row)
    offs = rng.integers(-bandwidth, bandwidth + 1, size=rows.shape[0])
    cols = np.clip(rows + offs, 0, n_rows - 1)
    vals = rng.uniform(0.5, 1.5, size=rows.shape[0]).astype(dtype)
    # dedup (row, col)
    key = rows * n_rows + cols
    order = np.argsort(key, kind="stable")
    key, rows, cols, vals = key[order], rows[order], cols[order], vals[order]
    uniq = np.ones(len(key), dtype=bool)
    uniq[1:] = key[1:] != key[:-1]
    seg = np.cumsum(uniq) - 1
    mvals = np.zeros(int(seg[-1]) + 1, dtype=dtype)
    np.add.at(mvals, seg, vals)
    return CSR.from_coo(rows[uniq], cols[uniq], mvals, n_rows, n_rows,
                        dtype=dtype)


def uniform_random_matrix(n_rows: int, nnz_per_row: int = 8,
                          dtype=np.float32, seed: int = 0) -> CSR:
    """Uniform-random sparse matrix (no power law): control case."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), nnz_per_row)
    cols = rng.integers(0, n_rows, size=rows.shape[0])
    vals = rng.uniform(0.5, 1.5, size=rows.shape[0]).astype(dtype)
    return CSR.from_coo(rows, cols, vals, n_rows, n_rows, dtype=dtype)


def paper_sizes(max_log2_rows: int = 26, min_log2_rows: int = 11):
    """The paper's size sweep: 2^11 .. 2^26 rows (§II-C)."""
    return [2 ** k for k in range(min_log2_rows, max_log2_rows + 1)]
