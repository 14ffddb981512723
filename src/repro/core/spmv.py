"""Structure-aware SpMV: a thin client over `repro.plan`.

This is the paper's conclusion turned into a library: *structure determines
performance*, so the stack measures structure (core.structure) and routes
to the format whose TPU access pattern matches it:

    banded        -> DIA   (streaming x windows; FD fast path)
    blocked       -> BELL  (dense 8x128 tiles; useful-byte gathers)
    unstructured  -> CSR   (column-blocked scalar-prefetch kernel)

The decision machinery itself lives in `repro.plan` (compile-once:
analyze -> reorder -> convert -> pre-padded kernel layout, frozen into a
cached `SpmvPlan`).  This module keeps the pure-jnp implementations (the
oracles the Pallas kernels in `repro.kernels` are validated against) and
two thin entry points: `auto_format` delegates the format decision to
`plan.choose_format`/`plan.convert`, and `spmv(..., use_pallas=True)`
fetches the matrix's plan from the process-wide `plan.DEFAULT_CACHE`
(compiling a minimal container plan on first touch), so repeated
multiplies of the same matrix skip all per-call layout prep.  The jnp
path stays direct — it is already jit-cached by XLA.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import structure
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


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

def auto_format(csr: CSR, report: structure.StructureReport | None = None,
                reordering=None, threads: int = 1):
    """Pick the TPU-friendly format for this matrix's structure.

    Thin client of `repro.plan`: the decision rule is
    `plan.choose_format` and the conversion `plan.convert` (one-shot --
    compile a `plan.SpmvPlan` instead to also freeze the kernel layout,
    or `plan.compile(csr, predictor='auto')` to let the learned cost
    model pick the reordering too).

    With `reordering` (a `repro.reorder.Reordering`), the permutation is
    applied first and the structure re-analyzed on the permuted matrix, so
    the format decision reflects the post-reorder structure -- an RCM'd
    scrambled-banded matrix becomes DIA-eligible again.  Pass the same
    reordering to `spmv` to multiply in the original row order.
    `threads` biases dispersed unstructured matrices toward the
    nnz-balanced segmented layout, exactly as plan compilation would.
    """
    from repro import plan as _plan

    if reordering is not None:
        csr = reordering.apply(csr)
        report = None
    rep = report or structure.analyze(csr)
    return _plan.convert(csr, _plan.choose_format(rep, threads=threads))


def spmv(matrix, x: jax.Array, use_pallas: bool = False,
         interpret: bool | None = None, reordering=None) -> jax.Array:
    """Multiply any supported sparse container by x.

    use_pallas=True routes through the matrix's cached execution plan
    (`repro.plan.DEFAULT_CACHE`): the first call on a given container
    compiles a minimal plan (one-time kernel layout prep), subsequent
    calls replay it with zero matrix-side work.  On CPU the kernels run
    in interpret mode, on TPU as compiled Mosaic kernels.  Inside a jit
    trace (tracer containers cannot be fingerprinted) the call falls
    back to the per-call `repro.kernels.ops` wrappers.

    `reordering` declares that `matrix` is the REORDERED operand (built via
    `reordering.apply` / `auto_format(..., reordering=...)`) while x and the
    returned y stay in the ORIGINAL order: x is gathered through col_perm
    before the multiply and y scattered back through inv_row_perm after.
    """
    if reordering is not None:
        y = spmv(matrix, reordering.permute_x(x), use_pallas=use_pallas,
                 interpret=interpret)
        return reordering.restore_y(y)
    if use_pallas:
        from repro import plan as _plan
        from repro.kernels import ops as kops
        if isinstance(matrix, (CSR, ELL, BELL, DIA, HYB)):
            if _plan.is_concrete(matrix):
                p = _plan.DEFAULT_CACHE.get_or_build(
                    _plan.matrix_fingerprint(matrix) + "|container",
                    lambda: _plan.plan_for_container(matrix))
                return p.execute(x, interpret=interpret)
            # tracer fallback: per-call wrappers (prep under jit where the
            # format permits it)
            direct = {DIA: kops.spmv_dia, BELL: kops.spmv_bell,
                      CSR: kops.spmv_csr, ELL: kops.spmv_ell,
                      HYB: kops.spmv_hyb}
            return direct[type(matrix)](matrix, x, interpret=interpret)
    if isinstance(matrix, CSR):
        return spmv_csr_jnp(matrix, x)
    if isinstance(matrix, ELL):
        return spmv_ell_jnp(matrix, x)
    if isinstance(matrix, HYB):
        return spmv_hyb_jnp(matrix, x)
    if isinstance(matrix, BELL):
        return spmv_bell_jnp(matrix, x)
    if isinstance(matrix, DIA):
        return spmv_dia_jnp(matrix, x)
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


def pagerank(csr: CSR, damping: float = 0.85, n_iters: int = 32):
    """PageRank via repeated SpMV (network-analysis example, paper §I).

    Compatibility wrapper over `repro.graph.pagerank` (the full driver:
    compile-once semiring plan, convergence checks, per-iteration
    telemetry): this entry historically scaled A's *columns* in place,
    which equals the graph driver's documented A[i,j] = edge i->j
    convention applied to A^T — so it delegates on the transpose and
    keeps the fixed iteration count.  Prefer `repro.graph.pagerank`.
    """
    from repro.graph.drivers import pagerank as _graph_pagerank
    from repro.graph.drivers import transpose_csr

    res = _graph_pagerank(transpose_csr(csr), damping=damping, tol=0.0,
                          max_iters=n_iters)
    return jnp.asarray(res.values)
