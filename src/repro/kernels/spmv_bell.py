"""Blocked-ELL SpMV Pallas kernel -- the TPU-native unstructured path.

Paper mapping: R-MAT's random x-gathers are the pathology (demand-miss
plateau, prefetcher shutoff).  On TPU a per-element gather would move a
full DMA tile per nonzero; instead we restructure the matrix into dense
(bm x bn) blocks so every "random access" fetches a *fully useful* 2-D x
tile, chosen by a scalar-prefetched block-column index -- the paper's P3
("let the kernel direct placement") as an index_map.

Layout:
  data       : (n_block_rows, blocks_per_row, bm, bn)  dense blocks
  block_cols : (n_block_rows * blocks_per_row,) int32  scalar-prefetched,
                                                       flat (a 2-D SMEM
                                                       array pads its last
                                                       dim to 128 words)
  x tiles    : (n_col_blocks, 1, bn)
  y          : (n_block_rows, 1, bm)

Grid = (block rows of one call, blocks_per_row); the x tile index_map
dereferences block_cols -- a data-dependent DMA schedule, which is
exactly the "prefetcher that can predict non-sequential accesses" the
paper asks hardware for (§V).  The table of a large matrix outgrows SMEM
(FD at 2^22 rows needs 12.6 MB of it), so the block rows are cut into
runs of at most SMEM_TABLE_WORDS table entries, one kernel call each,
looped over by `lax.map`; a call's first block row is prefetched with
its slice of the table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.spans import KERNEL, X_PREP

from . import resolve_interpret


# Table entries one call prefetches into SMEM (1 MiB on v5e): half of it,
# leaving the rest to Mosaic.
SMEM_TABLE_WORDS = 1 << 17


def _kernel(bc_ref, base_ref, data_ref, x_ref, out_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    block = data_ref[0, 0]                       # (bm, bn)
    tile = x_ref[0]                              # (1, bn)
    out_ref[0] += jax.lax.dot_general(           # (1, bm) = tile @ block.T
        tile, block,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(out_ref.dtype)


def _call(data, x_tiles, table, base, rows, interpret):
    """y for block rows base .. base + rows - 1 (rows past the matrix
    re-read its last block row; the caller drops them)."""
    nbr, bpr, bm, bn = data.shape
    last = nbr - 1
    return pl.pallas_call(
        _kernel,
        name="spmv_bell_pallas",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, bpr),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bn), lambda b, k, bc, base: (
                    jnp.minimum(base[0] + b, last), k, 0, 0)),
                pl.BlockSpec((1, 1, bn),
                             lambda b, k, bc, base: (bc[b * bpr + k], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bm),
                                   lambda b, k, bc, base: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, 1, bm), data.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(table, base, data, x_tiles)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmv_bell_pallas(data: jax.Array, block_cols: jax.Array, x: jax.Array,
                     interpret: bool | None = None) -> jax.Array:
    """y = A @ x for A in blocked-ELL layout.

    data       : (nbr, bpr, bm, bn)
    block_cols : (nbr, bpr) int32
    x          : (n_cols,) with n_cols a multiple of bn
    returns y  : (nbr * bm,)
    """
    nbr, bpr, bm, bn = data.shape
    assert x.shape[0] % bn == 0
    with jax.named_scope(X_PREP):
        x_tiles = x.reshape(-1, 1, bn)
    rows = max(1, min(nbr, SMEM_TABLE_WORDS // bpr))
    n_calls = -(-nbr // rows)
    interpret = resolve_interpret(interpret)
    with jax.named_scope(KERNEL):
        table = jnp.pad(block_cols.reshape(-1).astype(jnp.int32),
                        (0, (n_calls * rows - nbr) * bpr))
        bases = jnp.arange(n_calls, dtype=jnp.int32)[:, None] * rows
        out = jax.lax.map(
            lambda tb: _call(data, x_tiles, tb[0], tb[1], rows, interpret),
            (table.reshape(n_calls, rows * bpr), bases))
        return out.reshape(-1)[: nbr * bm]
