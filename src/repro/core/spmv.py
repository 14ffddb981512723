"""Pure-jnp SpMV: one reference kernel per storage format, and `spmv`,
which dispatches a container to its kernel.

The paper's conclusion is that *structure determines performance*; the
stack that acts on it (structure analysis, format choice, the Pallas
kernels and their frozen layouts) is `repro.plan` over `repro.kernels`.
The kernels here are the references the Pallas runners are validated
against: plain gathers and segment sums that XLA jit-caches, with no
layout preparation of their own.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .formats import BELL, CSR, DIA, ELL, HYB


# ---------------------------------------------------------------------------
# Pure-jnp reference implementations (one per format)
# ---------------------------------------------------------------------------

@jax.jit
def spmv_csr_jnp(csr: CSR, x: jax.Array) -> jax.Array:
    """y = A @ x via gather + segment-sum (row ids from indptr)."""
    nnz = csr.data.shape[0]
    lengths = jnp.diff(csr.indptr)
    row_ids = jnp.repeat(jnp.arange(csr.n_rows), lengths,
                         total_repeat_length=nnz)
    prods = csr.data * jnp.take(x, csr.indices, axis=0)
    return jax.ops.segment_sum(prods, row_ids, num_segments=csr.n_rows)


@jax.jit
def spmv_ell_jnp(ell: ELL, x: jax.Array) -> jax.Array:
    return (ell.data * jnp.take(x, ell.indices, axis=0)).sum(axis=1)


@jax.jit
def spmv_bell_jnp(bell: BELL, x: jax.Array) -> jax.Array:
    nbc = -(-bell.n_cols // bell.bn)
    xp = jnp.pad(x, (0, nbc * bell.bn - bell.n_cols))
    x_tiles = xp.reshape(nbc, bell.bn)
    gathered = jnp.take(x_tiles, bell.block_cols, axis=0)  # (nbr, bpr, bn)
    y = jnp.einsum("rkmn,rkn->rm", bell.data, gathered)
    return y.reshape(-1)[: bell.n_rows]


@jax.jit
def spmv_dia_jnp(dia: DIA, x: jax.Array) -> jax.Array:
    n = dia.n_rows
    xp = jnp.pad(x, (n, n))  # zero halo so every window slice is in-range

    def one_diag(band, off):
        window = jax.lax.dynamic_slice(xp, (n + off,), (n,))
        return band * window

    contrib = jax.vmap(one_diag)(dia.data, dia.offsets)
    return contrib.sum(axis=0)


@jax.jit
def spmv_hyb_jnp(hyb: HYB, x: jax.Array) -> jax.Array:
    """Light ELL partial plus heavy COO segment-sum (heavy rows are
    all-padding in the light slab, so the + join is exact)."""
    y = (hyb.data * jnp.take(x, hyb.indices, axis=0)).sum(axis=1)
    prods = hyb.hvals * jnp.take(x, hyb.hcols, axis=0)
    return y + jax.ops.segment_sum(prods, hyb.hrows,
                                   num_segments=hyb.n_rows)


def spmv_dense_jnp(a: jax.Array, x: jax.Array) -> jax.Array:
    return a @ x


# container type -> its reference kernel
JNP_KERNELS = {CSR: spmv_csr_jnp, ELL: spmv_ell_jnp, BELL: spmv_bell_jnp,
               DIA: spmv_dia_jnp, HYB: spmv_hyb_jnp}


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

def spmv(matrix, x: jax.Array, reordering=None) -> jax.Array:
    """Multiply any supported sparse container (or a dense 2-D array) by
    x with its jnp reference kernel.

    `reordering` declares that `matrix` is the REORDERED operand (built via
    `reordering.apply`) while x and the returned y stay in the ORIGINAL
    order: x is gathered through col_perm before the multiply and y
    scattered back through inv_row_perm after.
    """
    if reordering is not None:
        return reordering.restore_y(spmv(matrix, reordering.permute_x(x)))
    kernel = JNP_KERNELS.get(type(matrix))
    if kernel is not None:
        return kernel(matrix, x)
    if isinstance(matrix, jax.Array) and matrix.ndim == 2:
        return spmv_dense_jnp(matrix, x)
    raise TypeError(f"unsupported matrix container: {type(matrix)}")


@partial(jax.jit, static_argnames=("n_iters",))
def power_iteration(matrix, x0: jax.Array, n_iters: int = 16):
    """Example composite analytic from the paper's motivation (§I): repeated
    SpMV drives eigensolvers for graph anomaly detection.  Returns the
    dominant eigenvalue estimate and final vector."""
    def body(carry, _):
        x, _ = carry
        y = spmv(matrix, x)
        norm = jnp.linalg.norm(y)
        y = y / jnp.maximum(norm, 1e-30)
        return (y, norm), None

    (x, lam), _ = jax.lax.scan(body, (x0, jnp.array(0.0, x0.dtype)),
                               None, length=n_iters)
    return lam, x
