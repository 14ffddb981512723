"""auto_format dispatch: each structure kind routes to its format, every
routed path agrees with the dense reference."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan
from repro.core import structure
from repro.core.formats import BELL, CSR, DIA, HYB
from repro.core.generators import (banded_matrix, fd_matrix, rmat_matrix,
                                   uniform_random_matrix)
from repro.core.spmv import auto_format, spmv


def _blocked_matrix(n=1024, n_blocks=12, seed=0) -> CSR:
    """A few dense 8x128 tiles: the BELL-native structure."""
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    rr, cc = np.meshgrid(np.arange(8), np.arange(128), indexing="ij")
    for _ in range(n_blocks):
        r0 = int(rng.integers(0, n // 8)) * 8
        c0 = int(rng.integers(0, n // 128)) * 128
        rows.append((r0 + rr).ravel())
        cols.append((c0 + cc).ravel())
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    vals = rng.normal(size=rows.shape[0]).astype(np.float32)
    return CSR.from_coo(rows, cols, vals, n, n)


def _assert_matches_dense(fmt, csr):
    x = jnp.asarray(np.random.default_rng(42)
                    .normal(size=csr.n_cols).astype(np.float32))
    want = np.asarray(csr.to_dense()) @ np.asarray(x)
    got = np.asarray(spmv(fmt, x))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_banded_dispatches_to_dia():
    csr = fd_matrix(1024)
    rep = structure.analyze(csr)
    assert rep.kind == "banded"
    fmt = auto_format(csr, rep)
    assert isinstance(fmt, DIA)
    _assert_matches_dense(fmt, csr)


def test_narrow_band_dispatches_to_dia():
    csr = banded_matrix(512, 8, nnz_per_row=5, seed=2)
    fmt = auto_format(csr)
    assert isinstance(fmt, DIA)
    _assert_matches_dense(fmt, csr)


def test_blocked_dispatches_to_bell():
    csr = _blocked_matrix()
    rep = structure.analyze(csr)
    assert rep.kind == "blocked"
    fmt = auto_format(csr, rep)
    assert isinstance(fmt, BELL)
    _assert_matches_dense(fmt, csr)


def test_power_law_dispatches_to_hyb():
    """Power-law row lengths (high nnz CV) route to the hybrid row split:
    hub rows go to the column-sorted heavy stream, the rest stay ELL."""
    csr = rmat_matrix(2048, seed=5)
    rep = structure.analyze(csr)
    assert rep.kind == "unstructured"
    assert rep.row_nnz_cv >= 1.0        # what triggers the hyb pick
    fmt = auto_format(csr, rep)
    assert isinstance(fmt, HYB)
    _assert_matches_dense(fmt, csr)


def test_flat_unstructured_stays_csr():
    """Unstructured but near-uniform row lengths (low CV): no hub rows to
    split off, so the dispatcher keeps CSR."""
    csr = uniform_random_matrix(2048, nnz_per_row=8, seed=5)
    rep = structure.analyze(csr)
    assert rep.kind == "unstructured"
    assert rep.row_nnz_cv < 1.0
    fmt = auto_format(csr, rep)
    assert fmt is csr
    _assert_matches_dense(fmt, csr)


def test_banded_with_many_offsets_falls_back_to_csr():
    """kind == 'banded' but > 64 distinct diagonals: DIA storage would
    blow up (n_diags x n dense), so the dispatcher must keep CSR."""
    csr = banded_matrix(512, 200, nnz_per_row=7, seed=3)
    rep = structure.analyze(csr)
    wide = dataclasses.replace(rep, kind="banded", n_distinct_offsets=100)
    fmt = auto_format(csr, wide)
    assert fmt is csr
    _assert_matches_dense(fmt, csr)


@pytest.mark.parametrize("n_rows", [
    2 ** 18,        # a 512 x 512 grid: bandwidth_p95 513
    2 ** 19,        # a 512 x 1024 grid: bandwidth_p95 1025
])
def test_fd_plans_to_dia_past_the_cache_line_window(n_rows):
    """DIA streams each diagonal's x window whatever its offset, so a
    stencil stays banded however far its grid rows lie apart."""
    csr = fd_matrix(n_rows)
    rep = structure.analyze(csr)
    assert rep.bandwidth_p95 > 512
    assert rep.kind == "banded" and rep.n_distinct_offsets == 21
    p = plan.compile(csr, reorder="none", use_pallas=False)
    assert p.format_name == "dia" and p.container.n_diags == 21
    x = np.random.default_rng(0).normal(size=n_rows).astype(np.float32)
    rows = np.repeat(np.arange(n_rows), np.diff(np.asarray(csr.indptr)))
    want = np.bincount(rows, weights=np.asarray(csr.data, np.float64)
                       * x[np.asarray(csr.indices)], minlength=n_rows)
    np.testing.assert_allclose(np.asarray(p.execute(jnp.asarray(x))), want,
                               rtol=1e-4, atol=1e-4)


def test_sparse_off_diagonals_stay_csr():
    """Few offsets but a thin fill: 20 off-main diagonals of two entries
    each would pad 20 full-length rows of DIA for 40 nonzeros."""
    n = 4096
    offs = [o for o in range(-10, 11) if o]
    rows = np.concatenate([np.arange(n)] + [[100, 3000]] * len(offs))
    cols = np.concatenate([np.arange(n)] + [[100 + o, 3000 + o]
                                            for o in offs])
    vals = np.random.default_rng(1).uniform(0.5, 1.5, rows.size)
    csr = CSR.from_coo(rows, cols, vals, n, n)
    rep = structure.analyze(csr)
    assert rep.n_distinct_offsets == 21 and rep.bandwidth_p95 <= 512
    assert rep.kind != "banded"
    fmt = auto_format(csr, rep)
    assert fmt is csr
    assert plan.compile(csr, reorder="none", use_pallas=False) \
        .format_name == "csr"
    _assert_matches_dense(fmt, csr)


def test_diagonals_outside_the_sample_keep_csr():
    """The analysis samples 8 windows of rows; a tridiagonal matrix with
    70 more diagonals in rows it skips reads as banded, but the whole
    matrix's 73 diagonals are past DIA's 64, so no band is built."""
    n = 1 << 17
    main = np.arange(n)
    extra = np.arange(70)
    rows = np.concatenate([main, main[1:], main[:-1], 9000 + extra])
    cols = np.concatenate([main, main[:-1], main[1:], 9200 + 2 * extra])
    vals = np.random.default_rng(2).uniform(0.5, 1.5, rows.size)
    csr = CSR.from_coo(rows, cols, vals, n, n)
    rep = structure.analyze(csr)
    assert rep.kind == "banded" and rep.n_distinct_offsets == 3
    assert DIA.from_csr(csr, max_diags=64) is None
    p = plan.compile(csr, reorder="none", use_pallas=False)
    assert p.format_name == "csr" and p.container is csr
    assert auto_format(csr, rep) is csr


@pytest.mark.parametrize("gen,expected", [
    (lambda: fd_matrix(1024), DIA),
    (lambda: _blocked_matrix(), BELL),
    (lambda: rmat_matrix(2048, seed=5), HYB),
    (lambda: uniform_random_matrix(2048, nnz_per_row=8, seed=5), CSR),
])
def test_all_dispatch_paths_agree_with_dense(gen, expected):
    csr = gen()
    fmt = auto_format(csr)
    assert isinstance(fmt, expected)
    _assert_matches_dense(fmt, csr)
