"""Column-blocked padded-CSR SpMV Pallas kernel -- the paper's P2+P3.

The software realization of the paper's proposed architecture fixes:
partition A into column stripes (P2: bound the x working set) and let the
row-pointer metadata drive the schedule (P3: kernel-directed placement).
The matrix arrays stream exactly once (P1: no cache to pollute).

Host-side prep (`_layout.prepare_csr`) pads each (row_block x stripe)
cell to a fixed nonzero count W so shapes are static:

  vals  : (S, B, W)  f32   padding value 0.0 (or the semiring's pad)
  cols  : (S, B, W)  int32 stripe-rebased column, padding 0
  rowin : (S, B, W)  int32 row-within-block, padding 0

Mosaic has no general in-kernel gather, so x is gathered by XLA outside
the kernel and streams in as one more (S, B, W) input under the same
block as the values.  Grid = (S, B); each (s, b) cell writes a partial y
block and a dense reduction over S finishes the sum (the y-spill term of
core.traffic.col_blocked_policy).

In-kernel accumulation uses a one-hot matmul (1 x W @ W x bm) so the
segment sum runs on the MXU instead of a scatter -- scatters don't exist
in the TPU memory model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gather_x, resolve_interpret


def segment_reduce(vals, xg, ranks, rwin: int, semiring=None):
    """(1, W) values, gathered x and segment ranks -> (1, rwin) partials:
    out[r] = ⊕ over slots s with ranks[s] == r of vals[s] ⊗ xg[s]."""
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (rwin, ranks.shape[1]), 0)
              == ranks)                                        # (rwin, W)
    if semiring is None:                                       # plus-times
        return jax.lax.dot_general(                            # MXU
            vals * xg, onehot.astype(vals.dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    # generalized segment-⊕: min/max have no matmul form, so select each
    # rank's slots with the same one-hot mask (identity elsewhere) and
    # ⊕-reduce on the VPU instead of the MXU.
    masked = jnp.where(onehot, semiring.mul(vals, xg),
                       jnp.asarray(semiring.identity, vals.dtype))
    return semiring.reduce(masked, axis=1).reshape(1, rwin)


def _kernel(vals_ref, xg_ref, rowin_ref, part_ref, *, bm, semiring=None):
    part_ref[0, 0] = segment_reduce(
        vals_ref[0, 0], xg_ref[0, 0], rowin_ref[0, 0], bm,
        semiring).astype(part_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "semiring"))
def spmv_csr_pallas(vals: jax.Array, cols: jax.Array, rowin: jax.Array,
                    x_stripes: jax.Array, interpret: bool | None = None,
                    semiring=None) -> jax.Array:
    """Partial-product pass: returns (S, B, bm) partials; ⊕ over S outside.

    vals/cols/rowin : (S, B, W)
    x_stripes       : (S, stripe_w)
    semiring        : None or a `repro.graph.semiring.Semiring`; None
                      (and plus_times) takes the byte-identical
                      historical MXU one-hot path
    """
    if semiring is not None and semiring.name == "plus_times":
        semiring = None
    s_dim, b_dim, w = vals.shape
    bm = 128  # rows per block (fixed by _layout.prepare_csr)
    stripe_w = x_stripes.shape[1]
    # XLA gather: each stripe's rebased columns index its own x stripe
    base = (jnp.arange(s_dim, dtype=jnp.int32) * stripe_w)[:, None, None]
    xg = gather_x(x_stripes.reshape(-1), cols + base)

    def cells(a):                       # (S, B, W) -> (S, B, 1, W) blocks
        return a.reshape(s_dim, b_dim, 1, w)

    block = pl.BlockSpec((1, 1, 1, w), lambda s, b: (s, b, 0, 0))
    partials = pl.pallas_call(
        functools.partial(_kernel, bm=bm, semiring=semiring),
        grid=(s_dim, b_dim),
        in_specs=[block, block, block],
        out_specs=pl.BlockSpec((1, 1, 1, bm), lambda s, b: (s, b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s_dim, b_dim, 1, bm), vals.dtype),
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(cells(vals), cells(xg), cells(rowin))
    return partials.reshape(s_dim, b_dim, bm)
