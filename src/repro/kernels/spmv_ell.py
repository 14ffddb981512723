"""ELLPACK SpMV Pallas kernel -- fixed-width rows, VPU row sums.

ELL pads every row to `max_nnz` entries, so the kernel is a dense (bm, W)
elementwise multiply over gathered x values -- no row pointers, no
segment sum.  That regular shape is what makes ELL the natural middle
ground between DIA (pure streaming) and CSR (segmented reduction).

Mosaic has no general in-kernel gather, so x is gathered by XLA outside
the kernel (`gather_x`, from HBM) and the gathered values stream into the
kernel under the same block as the matrix values (paper P1: every array
streams exactly once); nothing is pinned in VMEM, so x may be any size.

Layout (host prep in `_layout.prepare_ell`):

  data : (B, bm, W)  f32   rows padded to bm row-blocks, W = max_nnz
  idx  : (B, bm, W)  int32 column per slot; padding points at col 0 with
                           the absorbing pad value, so it multiplies away
  xg   : (B, bm, W)  f32   x[idx], gathered per call
  y    : (B, 1, bm)

Grid = (B,).  Each step multiplies the value tile by the gathered tile and
row-reduces into y's (1, bm) block.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.spans import KERNEL

from . import gather_x, resolve_interpret


def _kernel(data_ref, xg_ref, y_ref, *, semiring=None):
    if semiring is None:                                   # plus-times
        y = (data_ref[0] * xg_ref[0]).sum(axis=1)
    else:
        # generalized inner loop: ⊗ elementwise, ⊕-reduce over slots.
        # Padding slots hold semiring.pad_value (absorbing), so they
        # vanish under the reduction exactly like 0.0 does under sum.
        y = semiring.reduce(semiring.mul(data_ref[0], xg_ref[0]), axis=1)
    y_ref[0] = y.reshape(1, -1)


@functools.partial(jax.jit, static_argnames=("interpret", "semiring"))
def spmv_ell_pallas(data: jax.Array, idx: jax.Array, x: jax.Array,
                    interpret: bool | None = None, semiring=None
                    ) -> jax.Array:
    """y = A (⊕,⊗) x for A in row-blocked ELL layout.

    data / idx : (B, bm, W)
    x          : (n_pad,) -- padded so every idx is in range
    semiring   : None or a `repro.graph.semiring.Semiring`; None (and
                 plus_times) takes the byte-identical historical path
    returns    : (B, bm)
    """
    if semiring is not None and semiring.name == "plus_times":
        semiring = None                 # one compiled path, bit-identical
    b_dim, bm, w = data.shape
    xg = gather_x(x, idx)
    block = pl.BlockSpec((1, bm, w), lambda b: (b, 0, 0))
    with jax.named_scope(KERNEL):
        y = pl.pallas_call(
            functools.partial(_kernel, semiring=semiring),
            name="spmv_ell_pallas",
            grid=(b_dim,),
            in_specs=[block, block],
            out_specs=pl.BlockSpec((1, 1, bm), lambda b: (b, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((b_dim, 1, bm), data.dtype),
            interpret=resolve_interpret(interpret),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
            ),
        )(data, xg)
        return y.reshape(b_dim, bm)
