"""Row-gather SpMM Pallas kernel: Y = A X for k vectors at once.

X is features-last, (n_cols, k), and Y is (n_rows, k).  Each nonzero
A[i, j] fetches the whole row X[j] (k floats, 1 KiB at k = 256), so the
gather is lane-dense where the one-vector kernels gather scalars.

Layout (host prep in `_layout.prepare_spmm`): the rows are cut into
blocks of `bm`, and each block's nonzeros, in row-major order, are
padded to whole chunks of `chunk` slots, so no chunk holds two blocks.
Padding slots read column 0 with value 0 at local row 0.

  cols  : (T, chunk)   int32  column of each slot
  vals  : (T, chunk)   f32    value of each slot
  rloc  : (T, chunk)   int32  row of each slot within its block
  blk   : (T,)         int32  the row block of each chunk
  first : (T,)         int32  1 on the first chunk of each block

T, the number of chunks, is a whole number of groups of `group`
chunks.  The runner loops over the groups: XLA gathers the group's rows
of X, `jnp.take(X, cols, axis=0)`, and one kernel call reduces them into
Y's row blocks.  Grid = the group's chunks; the output block is the
chunk's row block (`blk`, scalar-prefetched), resident in VMEM while the
block's chunks follow one another and written back when the block
changes.  A block whose chunks straddle two groups is carried through Y
itself: the call aliases Y in and out, and its first chunk starts from
Y's stored rows unless it is the block's first chunk.

Each chunk's products run on the MXU at `precision=HIGHEST`: the values
sit in a (bm, chunk) one-hot of the local rows, times the gathered
(chunk, k) rows.

The gather stays in XLA although it writes and re-reads nnz x k floats:
a kernel that fetches X's rows itself, one DMA per nonzero from HBM with
the next chunk's in flight, took twice as long on a TPU v5e at 2^21 rows
and k = 256 (3.31 s against 1.62 s a multiply: about 30 ns a nonzero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.spans import SPMM_KERNEL

from . import resolve_interpret

SUBLANES = 8            # chunks per (8, chunk) block of vals and rloc


def _kernel(g0_ref, blk_ref, first_ref, xg_ref, vals_ref, rloc_ref,
            yin_ref, y_ref):
    """Add chunk i's products into its row block's output."""
    i = pl.program_id(0)
    new = (i == 0) | (blk_ref[i] != blk_ref[jnp.maximum(i - 1, 0)])

    @pl.when(new & (first_ref[i] == 1))
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(new & (first_ref[i] == 0))
    def _carry():                       # only chunk 0 of a call
        y_ref[...] = yin_ref[...]

    r = i % SUBLANES
    v = vals_ref[pl.ds(r, 1), :]                            # (1, chunk)
    rl = rloc_ref[pl.ds(r, 1), :]
    bm, chunk = y_ref.shape[0], v.shape[1]
    onehot = jax.lax.broadcasted_iota(jnp.int32, (bm, chunk), 0) == rl
    w = jnp.where(onehot, v, jnp.zeros_like(v))             # (bm, chunk)
    y_ref[...] += jnp.dot(w, xg_ref[...],
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def spmm_rows_pallas(g0: jax.Array, blk: jax.Array, first: jax.Array,
                     xg: jax.Array, vals: jax.Array, rloc: jax.Array,
                     y: jax.Array, bm: int,
                     interpret: bool | None = None) -> jax.Array:
    """One group's reduction into y, updated in place.

    g0        : (1,) int32, the group's first chunk (a multiple of 8)
    blk/first : (G,) int32, the group's slice of the chunk tables
    xg        : (G * chunk, k) the gathered rows of X, slot by slot
    vals/rloc : (T, chunk) the whole layout's tables
    y         : (n_blocks * bm, k) the output so far
    """
    n_chunks, chunk, k = blk.shape[0], vals.shape[1], y.shape[1]

    def table(i, g0_ref, blk_ref, first_ref):
        return ((g0_ref[0] + i) // SUBLANES, 0)

    with jax.named_scope(SPMM_KERNEL):
        return pl.pallas_call(
            _kernel,
            name="spmm_rows_pallas",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_chunks,),
                in_specs=[
                    pl.BlockSpec((chunk, k), lambda i, *_: (i, 0)),
                    pl.BlockSpec((SUBLANES, chunk), table),
                    pl.BlockSpec((SUBLANES, chunk), table),
                    pl.BlockSpec((bm, k), lambda i, g0, b, f: (b[0], 0)),
                ],
                out_specs=pl.BlockSpec((bm, k),
                                       lambda i, g0, b, f: (b[i], 0)),
            ),
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            input_output_aliases={6: 0},
            interpret=resolve_interpret(interpret),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
        )(g0, blk, first, xg, vals, rloc, y)

