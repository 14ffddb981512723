"""Segmented (merge-style) CSR SpMV Pallas kernel -- nnz-balanced grid.

The row-blocked kernels (`spmv_csr`, `spmv_ell`) partition work by ROWS,
so a power-law matrix hands one grid step a 4000-nonzero hub row and its
neighbor eight -- the load-imbalance half of the paper's R-MAT penalty.
This kernel partitions the FLAT nonzero stream instead (Bergmans et al.'s
merge-based CSR, PAPERS.md): every grid step owns exactly `seg_len`
nonzeros regardless of how rows fall, and rows that straddle a segment
boundary are finished by a carry-out merge after the grid.

Layout (host prep in `_layout.prepare_csr_seg`):

  vals : (S, L)  f32   flat row-major nonzero stream cut into S segments,
                       padded with the semiring's absorbing element
  cols : (S, L)  int32 column per nonzero, padding 0
  rid  : (S, L)  int32 LOCAL row rank within the segment (dense: 0..R-1 in
                       stream order), padding R-1
  xg   : (S, L)  f32   x[cols], gathered by XLA outside the kernel and
                       streamed under the same block as the values

Ranks are per-segment-dense rather than row offsets so the partial window
R is bounded by L even when empty rows interleave; `row_ids[s, r]` (host
side) maps rank r back to the global row, with pad ranks parked on a
dummy row n_rows.  A row crossing segments s and s+1 appears as the last
rank of s and rank 0 of s+1; the host-side segment-⊕ over `row_ids` is
the merge that stitches those partials back together.

In-segment accumulation reuses `spmv_csr.segment_reduce`: the one-hot
matmul (segment sum on the MXU; TPU has no scatter), or a masked
⊕-reduce on the VPU for non-plus-times semirings.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.spans import KERNEL

from . import gather_x, resolve_interpret
from .spmv_csr import segment_reduce


def _kernel(vals_ref, xg_ref, rid_ref, part_ref, *, rwin, semiring=None):
    # absorbing pad slots contribute the identity wherever their rank lands
    part_ref[0] = segment_reduce(vals_ref[0], xg_ref[0], rid_ref[0], rwin,
                                 semiring).astype(part_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rwin", "interpret", "semiring"))
def spmv_csr_seg_pallas(vals: jax.Array, cols: jax.Array, rid: jax.Array,
                        x: jax.Array, rwin: int,
                        interpret: bool | None = None,
                        semiring=None) -> jax.Array:
    """Partial pass: returns (S, rwin) per-segment rank partials.

    vals/cols/rid : (S, L) -- equal-nnz segments of the flat stream
    x             : (n_pad,) padded so every col index is in range
    rwin          : static rank-window width (max distinct rows touched by
                    any one segment, rounded up to a lane multiple)
    semiring      : None or a `repro.graph.semiring.Semiring`; None (and
                    plus_times) takes the byte-identical MXU one-hot path

    The caller finishes with a segment-⊕ of the partials at
    `row_ids[s, r]` -- the carry-out merge across segment boundaries.
    """
    if semiring is not None and semiring.name == "plus_times":
        semiring = None                 # one compiled path, bit-identical
    s_dim, seg_len = vals.shape
    xg = gather_x(x, cols)

    def segs(a):                        # (S, L) -> (S, 1, L) blocks
        return a.reshape(s_dim, 1, seg_len)

    block = pl.BlockSpec((1, 1, seg_len), lambda s: (s, 0, 0))
    with jax.named_scope(KERNEL):
        partials = pl.pallas_call(
            functools.partial(_kernel, rwin=rwin, semiring=semiring),
            name="spmv_csr_seg_pallas",
            grid=(s_dim,),
            in_specs=[block, block, block],
            out_specs=pl.BlockSpec((1, 1, rwin), lambda s: (s, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((s_dim, 1, rwin), vals.dtype),
            interpret=resolve_interpret(interpret),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
            ),
        )(segs(vals), segs(xg), segs(rid))
        return partials.reshape(s_dim, rwin)
