"""Banded (DIA) SpMV Pallas kernel -- the FD fast path.

Paper mapping: FD matrices have three bands of three adjacent diagonals; the
x-window for a diagonal is a *contiguous* slice (Fig. 2's red-A pattern), so
the TPU realization is pure streaming: the grid walks row blocks, Mosaic
double-buffers the band and x tiles HBM->VMEM, and no gather ever happens.
This is proposal P1 (stream, don't cache) made structural.

Layout:
  band data : (n_diags, 1, n)        one (1, n) row per diagonal
  offsets   : (n_diags,) int32       scalar-prefetched; drives x index_map
  x padded  : (1, n + 2*halo)        zero halo so every window is in-range
  y         : (1, n)

Grid = (n/bn, n_diags); out block (1, bn) is revisited across the inner
(diagonal) dimension and accumulated in VMEM.  Misaligned windows are read
as two adjacent bn-blocks and rotated in-register (a dynamic lane roll),
keeping every HBM access block-aligned -- the DMA engine never sees a
misaligned request.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.spans import KERNEL, X_PREP

from . import resolve_interpret


def _kernel(offs_ref, band_ref, xlo_ref, xhi_ref, out_ref, *, halo, bn):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    off = offs_ref[j]
    rem = (off + halo) % bn          # block-internal shift (i*bn drops out)
    window2 = jnp.concatenate([xlo_ref[...], xhi_ref[...]], axis=1)
    # rotate left by rem (roll follows jnp.roll: positive shifts go right)
    window = pltpu.roll(window2, (2 * bn - rem) % (2 * bn), 1)[:, :bn]
    out_ref[...] += band_ref[0] * window


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def spmv_dia_pallas(band: jax.Array, offsets: jax.Array, x: jax.Array,
                    bn: int = 512, interpret: bool | None = None
                    ) -> jax.Array:
    """y = A @ x for A in DIA layout.

    band     : (n_diags, n) float -- band[k, i] = A[i, i + offsets[k]]
    offsets  : (n_diags,) int32
    x        : (n,) float
    """
    d, n = band.shape
    assert n % bn == 0, f"n={n} must be a multiple of bn={bn}"
    # halo covers the largest |offset|, rounded up to a block multiple
    halo_blocks = 1 + (n - 1) // bn          # offsets bounded by |off| < n
    halo = halo_blocks * bn
    with jax.named_scope(X_PREP):
        xp = jnp.pad(x, (halo, halo)).reshape(1, -1)

    grid = (n // bn, d)

    def xlo_map(i, j, offs):
        return (0, (i * bn + offs[j] + halo) // bn)

    def xhi_map(i, j, offs):
        return (0, (i * bn + offs[j] + halo) // bn + 1)

    with jax.named_scope(KERNEL):
        out = pl.pallas_call(
            functools.partial(_kernel, halo=halo, bn=bn),
            name="spmv_dia_pallas",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((1, 1, bn), lambda i, j, offs: (j, 0, i)),
                    pl.BlockSpec((1, bn), xlo_map),                 # x low
                    pl.BlockSpec((1, bn), xhi_map),                 # x high
                ],
                out_specs=pl.BlockSpec((1, bn), lambda i, j, offs: (0, i)),
            ),
            out_shape=jax.ShapeDtypeStruct((1, n), band.dtype),
            interpret=resolve_interpret(interpret),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
            ),
        )(offsets.astype(jnp.int32), band.reshape(d, 1, n), xp, xp)
        return out[0]
