"""Plain references for the SpMM cell's check, and their bfloat16 control.

Y = A X for X of shape (n_cols, k), in float64 on the host from the
benchmark's own CSR arrays (`gen.HostCSR`); it imports nothing of the
program.  The check reads two samples of an answer, so the references
compute just those: every row of a few columns (`columns`), and every
column of a few rows (`rows`).  Each returns the product and its
magnitude (|A| |X|) entry by entry, for the componentwise error
`entry_err`.  The controls are the same products with A and X stored in
bfloat16 and summed in float32, computed with jax on the default device;
put in the program's place, they must fail the check.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

RANGE_NNZ = 1 << 18       # nonzeros a host step takes at once


def _row_sums(prod: np.ndarray, starts: np.ndarray, n: int) -> np.ndarray:
    """Sums of prod's rows over the segments that start at `starts`
    (the CSR offsets of n rows, local to prod); empty rows get 0."""
    out = np.zeros((n,) + prod.shape[1:], dtype=np.float64)
    nonempty = np.diff(np.append(starts, prod.shape[0])) > 0
    if nonempty.any():
        out[nonempty] = np.add.reduceat(prod, starts[nonempty], axis=0)
    return out


def columns(a, xc) -> tuple:
    """(A xc, |A| |xc|) in float64 for xc of shape (n_cols, m): every
    row, taken in ranges of about RANGE_NNZ nonzeros on a thread each
    (numpy releases the GIL)."""
    xc = np.asarray(xc, dtype=np.float64)
    indptr = a.indptr.astype(np.int64)
    ref = np.empty((a.n_rows, xc.shape[1]))
    mag = np.empty_like(ref)
    bounds = [0]
    while bounds[-1] < a.n_rows:
        r0 = bounds[-1]
        r1 = int(np.searchsorted(indptr, indptr[r0] + RANGE_NNZ, "right")) - 1
        bounds.append(min(max(r1, r0 + 1), a.n_rows))

    def one(r0, r1):
        p0, p1 = indptr[r0], indptr[r1]
        prod = a.data[p0:p1, None].astype(np.float64) * xc[a.indices[p0:p1]]
        starts = indptr[r0:r1] - p0
        ref[r0:r1] = _row_sums(prod, starts, r1 - r0)
        mag[r0:r1] = _row_sums(np.abs(prod, out=prod), starts, r1 - r0)

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        list(pool.map(one, bounds[:-1], bounds[1:]))
    return ref, mag


def feeding(a, rows) -> np.ndarray:
    """The sorted columns that the nonzeros of `rows` read: the rows of X
    that `rows` needs."""
    return np.unique(np.concatenate(
        [a.indices[a.indptr[r]:a.indptr[r + 1]] for r in rows]
        + [np.zeros(0, a.indices.dtype)]))


def _sampled(a, rows, feed):
    """(values, position in feed, local row offsets) of `rows`."""
    lo, hi = a.indptr[rows].astype(np.int64), a.indptr[rows + 1]
    take = np.concatenate([np.arange(s, e) for s, e in zip(lo, hi)]
                          + [np.zeros(0, np.int64)])
    pos = np.searchsorted(feed, a.indices[take])
    starts = np.concatenate([[0], np.cumsum(hi - lo)[:-1]]).astype(np.int64)
    return a.data[take], pos, starts


def rows(a, rows, feed, xf) -> tuple:
    """(A X, |A| |X|) in float64 on `rows` only, every column; xf holds
    the rows of X at `feed` (`feeding`)."""
    vals, pos, starts = _sampled(a, np.asarray(rows), feed)
    prod = vals[:, None].astype(np.float64) * np.asarray(xf, np.float64)[pos]
    ref = _row_sums(prod, starts, len(rows))
    return ref, _row_sums(np.abs(prod, out=prod), starts, len(rows))


def entry_err(y, ref, mag) -> float:
    """The componentwise error of y: the largest over entries of
    |y - ref| / (|A| |X|), the magnitude floored at 2^-80 of the largest
    as `reference.row_err` does; infinite when y has a non-finite entry
    or the wrong shape."""
    y = np.asarray(y, dtype=np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return float("inf")
    floor = max(float(mag.max(initial=0.0)) * 2.0 ** -80,
                np.finfo(np.float64).tiny)
    return float((np.abs(y - ref) / np.maximum(mag, floor)).max(initial=0.0))


def columns_control(a, xc) -> np.ndarray:
    """A xc with A and xc stored in bfloat16, summed in float32."""
    rows_ = jnp.asarray(a.rows().astype(np.int32))
    cols, vals = jnp.asarray(a.indices), jnp.asarray(a.data)
    out = [reference._spmv_bf16(rows_, cols, vals,
                                jnp.asarray(np.asarray(xc)[:, j],
                                            jnp.float32))[: a.n_rows]
           for j in range(xc.shape[1])]
    return np.stack([np.asarray(o) for o in out], axis=1)


@jax.jit
def _rows_bf16(seg, vals, xf, pos):
    return jax.ops.segment_sum(
        reference._bf16(vals)[:, None] * reference._bf16(xf)[pos], seg,
        num_segments=seg.shape[0])


def rows_control(a, rows, feed, xf) -> np.ndarray:
    """`rows`'s product with A and X stored in bfloat16, summed in
    float32."""
    vals, pos, starts = _sampled(a, np.asarray(rows), feed)
    seg = np.zeros(max(len(vals), len(rows)), np.int32)
    lengths = np.diff(np.append(starts, len(vals)))
    seg[:len(vals)] = np.repeat(np.arange(len(rows), dtype=np.int32), lengths)
    pad = seg.shape[0] - len(vals)
    y = _rows_bf16(jnp.asarray(seg), jnp.asarray(np.pad(vals, (0, pad))),
                   jnp.asarray(xf, jnp.float32),
                   jnp.asarray(np.pad(pos, (0, pad)).astype(np.int32)))
    return np.asarray(y)[: len(rows)]
