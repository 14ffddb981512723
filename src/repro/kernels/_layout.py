"""Shared host-side layout preparation for the Pallas SpMV kernels.

One home for the padding / stripe-splitting / row-blocking arithmetic of
the per-format kernel modules.  Every format gets a `prepare_*` function
that does ALL matrix-side work (padding, reshaping, stripe bucketing)
once, returning a `Prepared*` container, and a `spmv_*_prepared` runner
that performs zero matrix-side work per call -- only the per-call x
pad/reshape plus the Pallas kernel.

This split is what `repro.plan` builds on: `prepare_*` runs at plan-compile
time, `spmv_*_prepared` is the amortized hot path.

Every op of a runner lies in one stage scope (`repro.spans.SCOPES`): the
x pad and reshape in `spmv.x_prep`, the gather in `spmv.x_gather`, the
Pallas call in `spmv.kernel`, and what turns its output into y in
`spmv.merge`.

The `Prepared*` containers are pytrees (arrays are leaves, sizes and
knobs are static), so each runner is one jitted program taking the layout
as an argument: the x pad, the XLA gather, the kernel and the merge fuse
into one executable, shared by every plan of the same shapes.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BELL, CSR, DIA, ELL, HYB
from repro.spans import (MERGE, SPMM_KERNEL, SPMM_MERGE, SPMM_X_GATHER,
                         SPMM_X_PREP, X_PREP)

from . import spmm as _spmm
from . import spmv_bell as _bell
from . import spmv_csr as _csr
from . import spmv_csr_seg as _seg
from . import spmv_dia as _dia
from . import spmv_ell as _ell


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(v: int, m: int) -> int:
    return ceil_div(v, m) * m


def _pytree(*data_fields: str):
    """Register a frozen layout dataclass as a pytree whose `data_fields`
    are leaves and every other field is static metadata."""
    def register(cls):
        meta = [f.name for f in dataclasses.fields(cls)
                if f.name not in data_fields]
        return jax.tree_util.register_dataclass(
            cls, data_fields=list(data_fields), meta_fields=meta)
    return register


_runner = functools.partial(jax.jit, static_argnames=("interpret",
                                                      "semiring"))
_runner_plus_times = functools.partial(jax.jit, static_argnames=("interpret",))


# ---------------------------------------------------------------------------
# DIA
# ---------------------------------------------------------------------------

@_pytree("band", "offsets")
@dataclasses.dataclass(frozen=True)
class PreparedDIA:
    """Pre-padded banded layout: band rows padded to a bn multiple."""
    band: jax.Array      # (n_diags, n_pad)
    offsets: jax.Array   # (n_diags,) int32
    n_rows: int
    n_cols: int
    bn: int


def prepare_dia(dia: DIA, bn: int = 512) -> PreparedDIA:
    n_pad = round_up(dia.n_rows, bn)
    band = jnp.pad(dia.data, ((0, 0), (0, n_pad - dia.n_rows)))
    offsets = dia.offsets
    if band.shape[0] == 0:
        # nnz=0 matrix: DIA.from_csr stores zero diagonals, which the
        # Pallas grid (n_diags as a grid axis, scalar-prefetched offsets)
        # cannot represent.  Synthesize one explicit zero main diagonal --
        # zeros are the plus-times identity, so y is exactly zeros.
        band = jnp.zeros((1, n_pad), dia.data.dtype)
        offsets = jnp.zeros((1,), jnp.int32)
    return PreparedDIA(band=band, offsets=offsets, n_rows=dia.n_rows,
                       n_cols=dia.n_cols, bn=bn)


@_runner_plus_times
def spmv_dia_prepared(prep: PreparedDIA, x: jax.Array,
                      interpret: bool | None = None) -> jax.Array:
    with jax.named_scope(X_PREP):
        xp = jnp.pad(x, (0, prep.band.shape[1] - x.shape[0]))
    y = _dia.spmv_dia_pallas(prep.band, prep.offsets, xp, bn=prep.bn,
                             interpret=interpret)
    with jax.named_scope(MERGE):
        return y[: prep.n_rows]


# ---------------------------------------------------------------------------
# BELL (already kernel-shaped; prep only records the x pad width)
# ---------------------------------------------------------------------------

@_pytree("data", "block_cols")
@dataclasses.dataclass(frozen=True)
class PreparedBELL:
    data: jax.Array        # (nbr, bpr, bm, bn)
    block_cols: jax.Array  # (nbr, bpr) int32
    n_rows: int
    n_cols: int
    x_pad: int             # padded x length (nbc * bn)


def prepare_bell(bell: BELL) -> PreparedBELL:
    nbc = ceil_div(bell.n_cols, bell.bn)
    return PreparedBELL(data=bell.data, block_cols=bell.block_cols,
                        n_rows=bell.n_rows, n_cols=bell.n_cols,
                        x_pad=nbc * bell.bn)


@_runner_plus_times
def spmv_bell_prepared(prep: PreparedBELL, x: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    with jax.named_scope(X_PREP):
        xp = jnp.pad(x, (0, prep.x_pad - prep.n_cols))
    y = _bell.spmv_bell_pallas(prep.data, prep.block_cols, xp,
                               interpret=interpret)
    with jax.named_scope(MERGE):
        return y[: prep.n_rows]


# ---------------------------------------------------------------------------
# ELL (row-blocked, fixed width)
# ---------------------------------------------------------------------------

@_pytree("data", "idx")
@dataclasses.dataclass(frozen=True)
class PreparedELL:
    """Row-blocked (B, bm, W) ELL arrays; padding rows index col 0 val 0."""
    data: jax.Array      # (B, bm, W)
    idx: jax.Array       # (B, bm, W) int32
    n_rows: int
    n_cols: int
    x_pad: int           # padded x length


def prepare_ell(ell: ELL, bm: int = 128, pad_mult: int = 128,
                pad_value: float = 0.0) -> PreparedELL:
    """`pad_value` fills the width/row padding slots: 0.0 for plus-times,
    the semiring's absorbing element (`Semiring.pad_value`) otherwise.
    The container itself must already use the same fill
    (`ELL.from_csr(..., fill=...)`) for its own short-row padding."""
    n, w = ell.data.shape
    # max(n, 1): a 0-row container still needs one (all-padding) row
    # block -- a zero-length Pallas grid is not representable.
    n_pad = round_up(max(n, 1), bm)
    w_pad = round_up(max(w, 1), pad_mult)
    data = jnp.pad(ell.data, ((0, n_pad - n), (0, w_pad - w)),
                   constant_values=pad_value)
    idx = jnp.pad(ell.indices, ((0, n_pad - n), (0, w_pad - w)))
    b_dim = n_pad // bm
    return PreparedELL(
        data=data.reshape(b_dim, bm, w_pad),
        idx=idx.reshape(b_dim, bm, w_pad).astype(jnp.int32),
        n_rows=n, n_cols=ell.n_cols,
        x_pad=round_up(ell.n_cols, pad_mult))


@_runner
def spmv_ell_prepared(prep: PreparedELL, x: jax.Array,
                      interpret: bool | None = None,
                      semiring=None) -> jax.Array:
    with jax.named_scope(X_PREP):
        xp = jnp.pad(x, (0, prep.x_pad - prep.n_cols))
    y = _ell.spmv_ell_pallas(prep.data, prep.idx, xp, interpret=interpret,
                             semiring=semiring)
    with jax.named_scope(MERGE):
        return y.reshape(-1)[: prep.n_rows]


# ---------------------------------------------------------------------------
# ELL row shards (host prep for the shard_map row-parallel path)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedELL:
    """Row-partitioned ELL layout: one (rows, width) slab per shard,
    stacked so `shard_map` can split the leading axis across devices.
    Column indices stay global (x is replicated); padding slots index
    col 0 with value 0."""
    data: jax.Array      # (parts, rows_pad, W)
    idx: jax.Array       # (parts, rows_pad, W) int32, global columns
    n_rows: int
    n_cols: int
    starts: np.ndarray   # (parts+1,) row range per shard
    bm: int              # row-block size the kernel tiles rows_pad into


def prepare_ell_shards(csr: CSR, partition, bm: int = 128,
                       pad_mult: int = 128) -> ShardedELL:
    """Pack each `RowPartition` part into one padded ELL slab.

    All shards share the global max row width (padded to `pad_mult`) and
    the max part row count (padded to `bm`), so the stacked arrays are
    rectangular -- the price of `shard_map`-compatible layout is padding,
    exactly like `prepare_csr`'s per-cell padding.
    """
    starts = np.asarray(partition.starts, dtype=np.int64)
    n_parts = len(starts) - 1
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    row_len = np.diff(indptr)
    w = round_up(max(int(row_len.max()) if len(row_len) else 1, 1), pad_mult)
    rows_pad = round_up(max(int(np.diff(starts).max()), 1), bm)

    D = np.zeros((n_parts, rows_pad, w), dtype=np.asarray(csr.data).dtype)
    C = np.zeros((n_parts, rows_pad, w), dtype=np.int32)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), row_len)
    part_of = np.searchsorted(starts, rows, side="right") - 1
    inner = np.arange(csr.nnz, dtype=np.int64) - indptr[rows]
    D[part_of, rows - starts[part_of], inner] = np.asarray(csr.data)
    C[part_of, rows - starts[part_of], inner] = \
        np.asarray(csr.indices).astype(np.int32)
    return ShardedELL(data=jnp.asarray(D), idx=jnp.asarray(C),
                      n_rows=csr.n_rows, n_cols=csr.n_cols,
                      starts=starts, bm=bm)


# ---------------------------------------------------------------------------
# CSR (column-blocked, padded)
# ---------------------------------------------------------------------------

@_pytree("vals", "cols", "rowin")
@dataclasses.dataclass(frozen=True)
class PaddedCSR:
    """Host-prepped column-blocked layout for the spmv_csr kernel."""
    vals: jax.Array    # (S, B, W)
    cols: jax.Array    # (S, B, W) stripe-rebased
    rowin: jax.Array   # (S, B, W) row within block
    n_rows: int
    n_cols: int
    stripe_w: int
    bm: int


def prepare_csr(csr: CSR, n_stripes: int = 1, bm: int = 128,
                pad_mult: int = 128, pad_value: float = 0.0) -> PaddedCSR:
    """Pad each (stripe x row-block) cell to the max nonzero count.

    `pad_value` fills the value padding slots (cols/rowin pad to 0): 0.0
    for plus-times, the semiring's absorbing element otherwise, so the
    kernel's segment-⊕ treats padding as the empty contribution."""
    stripe_w = round_up(ceil_div(csr.n_cols, n_stripes), 128)
    n_blocks = ceil_div(csr.n_rows, bm)
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    cols = np.asarray(csr.indices, dtype=np.int64)
    vals = np.asarray(csr.data)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(indptr))
    s_of = cols // stripe_w
    b_of = rows // bm
    cell = s_of * n_blocks + b_of
    order = np.argsort(cell, kind="stable")
    cell_s, rows_s, cols_s, vals_s = (cell[order], rows[order], cols[order],
                                      vals[order])
    counts = np.bincount(cell_s, minlength=n_stripes * n_blocks)
    w = max(int(counts.max()), 1)
    w = round_up(w, pad_mult)
    V = np.full((n_stripes, n_blocks, w), pad_value, dtype=vals.dtype)
    C = np.zeros((n_stripes, n_blocks, w), dtype=np.int32)
    R = np.zeros((n_stripes, n_blocks, w), dtype=np.int32)
    # position within cell
    cell_start = np.zeros(n_stripes * n_blocks + 1, dtype=np.int64)
    np.cumsum(counts, out=cell_start[1:])
    inner = np.arange(len(cell_s)) - cell_start[cell_s]
    s_idx = cell_s // n_blocks
    b_idx = cell_s % n_blocks
    V[s_idx, b_idx, inner] = vals_s
    C[s_idx, b_idx, inner] = (cols_s % stripe_w).astype(np.int32)
    R[s_idx, b_idx, inner] = (rows_s % bm).astype(np.int32)
    return PaddedCSR(
        vals=jnp.asarray(V), cols=jnp.asarray(C), rowin=jnp.asarray(R),
        n_rows=csr.n_rows, n_cols=csr.n_cols, stripe_w=stripe_w, bm=bm,
    )


@_runner
def spmv_csr_prepared(prep: PaddedCSR, x: jax.Array,
                      interpret: bool | None = None,
                      semiring=None) -> jax.Array:
    s_dim = prep.vals.shape[0]
    with jax.named_scope(X_PREP):
        xp = jnp.pad(x, (0, s_dim * prep.stripe_w - prep.n_cols))
        x_stripes = xp.reshape(s_dim, prep.stripe_w)
    partials = _csr.spmv_csr_pallas(prep.vals, prep.cols, prep.rowin,
                                    x_stripes, interpret=interpret,
                                    semiring=semiring)
    with jax.named_scope(MERGE):
        if semiring is None or semiring.name == "plus_times":
            y = partials.sum(axis=0).reshape(-1)  # reduce over stripes
        else:
            y = semiring.reduce(partials, axis=0).reshape(-1)
        return y[: prep.n_rows]


# ---------------------------------------------------------------------------
# Segmented CSR (nnz-balanced flat stream; the merge-CSR layout)
# ---------------------------------------------------------------------------

@_pytree("vals", "cols", "rid", "row_ids", "nonempty")
@dataclasses.dataclass(frozen=True)
class PreparedSegCSR:
    """Flat nonzero stream cut into equal-nnz segments for spmv_csr_seg.

    `rid` holds the per-segment-dense row rank of each nonzero and
    `row_ids[s, r]` maps rank r of segment s back to its global row (pad
    ranks park on the dummy row n_rows, sliced off after the carry
    merge).  `nonempty` marks rows with at least one nonzero so the
    non-plus-times combine can restore the ⊕-identity on empty rows."""
    vals: jax.Array      # (S, L)
    cols: jax.Array      # (S, L) int32
    rid: jax.Array       # (S, L) int32 rank within segment
    row_ids: jax.Array   # (S, R) int32 global row per rank; pad -> n_rows
    nonempty: jax.Array  # (n_rows,) bool
    n_rows: int
    n_cols: int
    rwin: int            # R: static rank-window width
    seg_len: int         # L: padded nonzeros per segment
    x_pad: int


def _seg_arrays(rows, cols, vals, n_rows: int, seg_len: int, pad_mult: int,
                pad_value: float):
    """Cut a (rows, cols, vals) nonzero stream -- in whatever order the
    caller chose (row-major for merge-CSR, column-sorted for the HYB
    heavy partition) -- into S equal segments of L slots, ranking rows
    densely within each segment."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals)
    seg = round_up(max(int(seg_len), 1), pad_mult)
    nnz = len(vals)
    n_segs = max(ceil_div(nnz, seg), 1)
    total = n_segs * seg
    v = np.full(total, pad_value, dtype=vals.dtype)
    c = np.zeros(total, dtype=np.int32)
    r = np.full(total, n_rows, dtype=np.int64)   # pads on the dummy row
    v[:nnz], c[:nnz], r[:nnz] = vals, cols.astype(np.int32), rows
    v2, c2, r2 = v.reshape(n_segs, seg), c.reshape(n_segs, seg), \
        r.reshape(n_segs, seg)
    rid = np.zeros((n_segs, seg), dtype=np.int32)
    uniques = []
    for s in range(n_segs):
        uniq, inv = np.unique(r2[s], return_inverse=True)
        rid[s] = inv.astype(np.int32)
        uniques.append(uniq)
    rwin = round_up(max(len(u) for u in uniques), pad_mult)
    row_ids = np.full((n_segs, rwin), n_rows, dtype=np.int32)
    for s, uniq in enumerate(uniques):
        row_ids[s, : len(uniq)] = uniq
    return v2, c2, rid, row_ids, rwin, seg


def prepare_csr_seg(csr: CSR, seg_len: int = 512, pad_mult: int = 128,
                    pad_value: float = 0.0) -> PreparedSegCSR:
    """Flatten the CSR nonzero stream row-major and cut it into
    equal-nnz segments.  `pad_value` fills the tail slots: 0.0 for
    plus-times, the semiring's absorbing element otherwise."""
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    lengths = np.diff(indptr)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), lengths)
    v2, c2, rid, row_ids, rwin, seg = _seg_arrays(
        rows, np.asarray(csr.indices), np.asarray(csr.data), csr.n_rows,
        seg_len, pad_mult, pad_value)
    return PreparedSegCSR(
        vals=jnp.asarray(v2), cols=jnp.asarray(c2), rid=jnp.asarray(rid),
        row_ids=jnp.asarray(row_ids), nonempty=jnp.asarray(lengths > 0),
        n_rows=csr.n_rows, n_cols=csr.n_cols, rwin=rwin, seg_len=seg,
        x_pad=round_up(max(csr.n_cols, 1), pad_mult))


@_runner
def spmv_csr_seg_prepared(prep: PreparedSegCSR, x: jax.Array,
                          interpret: bool | None = None,
                          semiring=None) -> jax.Array:
    with jax.named_scope(X_PREP):
        xp = jnp.pad(x, (0, prep.x_pad - prep.n_cols))
    partials = _seg.spmv_csr_seg_pallas(prep.vals, prep.cols, prep.rid, xp,
                                        rwin=prep.rwin, interpret=interpret,
                                        semiring=semiring)
    with jax.named_scope(MERGE):
        flat, ids = partials.reshape(-1), prep.row_ids.reshape(-1)
        if semiring is None or semiring.name == "plus_times":
            # carry-out merge: rows straddling a segment boundary have one
            # rank in each segment; the segment sum stitches them together.
            return jax.ops.segment_sum(
                flat, ids, num_segments=prep.n_rows + 1)[: prep.n_rows]
        y = semiring.segment(flat, ids,
                             num_segments=prep.n_rows + 1)[: prep.n_rows]
        # jax's segment_min/max fill empty segments with +/-inf, which is
        # only the ⊕-identity for min_plus -- restore it for the rest.
        return jnp.where(prep.nonempty, y, jnp.asarray(semiring.identity,
                                                       y.dtype))


# ---------------------------------------------------------------------------
# HYB (ELL light partition + column-sorted COO heavy tail)
# ---------------------------------------------------------------------------

@_pytree("light", "heavy")
@dataclasses.dataclass(frozen=True)
class PreparedHYB:
    """Two fused launches per SpMV: the ELL kernel over the light rows
    and the segmented kernel over the column-sorted heavy stream, joined
    by one ⊕.  Heavy rows are all-padding in the light slab (identity)
    and light rows never appear in the heavy stream (identity via
    `heavy.nonempty`), so the join is exact for every semiring."""
    light: PreparedELL
    heavy: PreparedSegCSR
    n_rows: int
    n_cols: int


def prepare_hyb(hyb: HYB, seg_len: int = 512, bm: int = 128,
                pad_mult: int = 128, pad_value: float = 0.0) -> PreparedHYB:
    light_ell = ELL(data=hyb.data, indices=hyb.indices, n_rows=hyb.n_rows,
                    n_cols=hyb.n_cols, max_nnz=hyb.light_width)
    light = prepare_ell(light_ell, bm=bm, pad_mult=pad_mult,
                        pad_value=pad_value)
    v2, c2, rid, row_ids, rwin, seg = _seg_arrays(
        np.asarray(hyb.hrows), np.asarray(hyb.hcols), np.asarray(hyb.hvals),
        hyb.n_rows, seg_len, pad_mult, pad_value)
    heavy_mask = np.zeros(hyb.n_rows, dtype=bool)
    heavy_mask[hyb.heavy_row_ids()] = True
    heavy = PreparedSegCSR(
        vals=jnp.asarray(v2), cols=jnp.asarray(c2), rid=jnp.asarray(rid),
        row_ids=jnp.asarray(row_ids), nonempty=jnp.asarray(heavy_mask),
        n_rows=hyb.n_rows, n_cols=hyb.n_cols, rwin=rwin, seg_len=seg,
        x_pad=round_up(max(hyb.n_cols, 1), pad_mult))
    return PreparedHYB(light=light, heavy=heavy, n_rows=hyb.n_rows,
                       n_cols=hyb.n_cols)


@_runner
def spmv_hyb_prepared(prep: PreparedHYB, x: jax.Array,
                      interpret: bool | None = None,
                      semiring=None) -> jax.Array:
    y_light = spmv_ell_prepared(prep.light, x, interpret=interpret,
                                semiring=semiring)
    y_heavy = spmv_csr_seg_prepared(prep.heavy, x, interpret=interpret,
                                    semiring=semiring)
    with jax.named_scope(MERGE):
        if semiring is None or semiring.name == "plus_times":
            return y_light + y_heavy
        return semiring.add(y_light, y_heavy)


# ---------------------------------------------------------------------------
# SpMM (k vectors at once, features last: row-blocked chunks of nonzeros)
# ---------------------------------------------------------------------------

@_pytree("cols", "vals", "rloc", "blk", "first")
@dataclasses.dataclass(frozen=True)
class PreparedSpMM:
    """Row blocks of `bm` rows, each block's nonzeros padded to whole
    chunks of `chunk` slots, the chunks in whole groups of `group`
    (`kernels.spmm` describes the arrays)."""
    cols: jax.Array      # (T, chunk) int32
    vals: jax.Array      # (T, chunk)
    rloc: jax.Array      # (T, chunk) int32 row within the block
    blk: jax.Array       # (T,) int32 row block of each chunk
    first: jax.Array     # (T,) int32 1 on each block's first chunk
    n_rows: int
    n_cols: int
    bm: int
    chunk: int
    group: int

    @property
    def n_blocks(self) -> int:
        return max(ceil_div(self.n_rows, self.bm), 1)


def prepare_spmm(csr: CSR, bm: int = 128, chunk: int = 512,
                 group: int = 512) -> PreparedSpMM:
    """Cut the CSR rows into blocks of `bm` and pad each block's
    nonzeros, in row-major order, to whole chunks (at least one, so that
    every block of Y is written); the chunk count is padded to whole
    groups of `group` (fewer, in steps of 8, for a small matrix) with
    all-padding chunks on the last block."""
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    n_rows = int(csr.n_rows)
    n_blocks = max(ceil_div(n_rows, bm), 1)
    bounds = indptr[np.minimum(np.arange(n_blocks + 1) * bm, n_rows)]
    per_block = np.diff(bounds)
    chunks = np.maximum(-(-per_block // chunk), 1)
    cstart = np.zeros(n_blocks + 1, dtype=np.int64)
    np.cumsum(chunks, out=cstart[1:])
    n_chunks = int(cstart[-1])
    group = min(group, round_up(n_chunks, _spmm.SUBLANES))
    total = round_up(n_chunks, group)
    nnz = int(bounds[-1])
    # a nonzero's slot: its block's first slot plus its place in the block
    slot = np.arange(nnz, dtype=np.int64)
    slot += np.repeat(cstart[:-1] * chunk - bounds[:-1], per_block)
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(indptr))
    data = np.asarray(csr.data)
    cols = np.zeros(total * chunk, dtype=np.int32)
    vals = np.zeros(total * chunk, dtype=data.dtype)
    rloc = np.zeros(total * chunk, dtype=np.int32)
    cols[slot] = np.asarray(csr.indices)
    vals[slot] = data
    rloc[slot] = rows % bm
    blk = np.full(total, n_blocks - 1, dtype=np.int32)
    blk[:n_chunks] = np.repeat(np.arange(n_blocks, dtype=np.int32), chunks)
    first = np.zeros(total, dtype=np.int32)
    first[cstart[:-1]] = 1
    return PreparedSpMM(
        cols=jnp.asarray(cols.reshape(total, chunk)),
        vals=jnp.asarray(vals.reshape(total, chunk)),
        rloc=jnp.asarray(rloc.reshape(total, chunk)),
        blk=jnp.asarray(blk), first=jnp.asarray(first), n_rows=n_rows,
        n_cols=int(csr.n_cols), bm=bm, chunk=chunk, group=group)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmm_prepared(prep: PreparedSpMM, x: jax.Array,
                  interpret: bool | None = None) -> jax.Array:
    """Y = A X for x of shape (n_cols, k): one loop over the groups of
    chunks, each an XLA row gather of X and one kernel call."""
    g = prep.group
    with jax.named_scope(SPMM_X_PREP):
        x = x.astype(prep.vals.dtype)
        y = jnp.zeros((prep.n_blocks * prep.bm, x.shape[1]), x.dtype)

    def step(i, y):
        c0 = i * g
        with jax.named_scope(SPMM_X_GATHER):
            cols = jax.lax.dynamic_slice_in_dim(prep.cols, c0, g)
            xg = jnp.take(x, cols.reshape(-1), axis=0, mode="clip")
        with jax.named_scope(SPMM_KERNEL):
            blk = jax.lax.dynamic_slice_in_dim(prep.blk, c0, g)
            first = jax.lax.dynamic_slice_in_dim(prep.first, c0, g)
            g0 = jnp.reshape(c0, (1,)).astype(jnp.int32)
        return _spmm.spmm_rows_pallas(g0, blk, first, xg, prep.vals,
                                      prep.rloc, y, bm=prep.bm,
                                      interpret=interpret)

    y = jax.lax.fori_loop(0, prep.blk.shape[0] // g, step, y)
    with jax.named_scope(SPMM_MERGE):
        return y[: prep.n_rows]


__all__ = [
    "ceil_div", "round_up",
    "PreparedDIA", "prepare_dia", "spmv_dia_prepared",
    "PreparedBELL", "prepare_bell", "spmv_bell_prepared",
    "PreparedELL", "prepare_ell", "spmv_ell_prepared",
    "ShardedELL", "prepare_ell_shards",
    "PaddedCSR", "prepare_csr", "spmv_csr_prepared",
    "PreparedSegCSR", "prepare_csr_seg", "spmv_csr_seg_prepared",
    "PreparedHYB", "prepare_hyb", "spmv_hyb_prepared",
    "PreparedSpMM", "prepare_spmm", "spmm_prepared",
]
