"""auto_format dispatch: each structure kind routes to its format, every
routed path agrees with the dense reference."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan
from repro.core import structure
from repro.core.formats import BELL, CSR, DIA
from repro.core.generators import (banded_matrix, fd_matrix, rmat_matrix,
                                   uniform_random_matrix)
from repro.core.spmv import spmv
from repro.plan import auto_format


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
    """Power-law row lengths no longer route to the hybrid row split:
    HYB pads its light slab to 128 lanes over every row, so its runner
    gathers far more slots than the flat segmented stream, and the plan
    takes the layout that touches the fewest XLA elements."""
    csr = rmat_matrix(2048, seed=5)
    rep = structure.analyze(csr)
    assert rep.kind == "unstructured"
    assert rep.row_nnz_cv >= 1.0        # what picked hyb before the count
    counts = plan.compiler.xla_elements(
        csr, plan.compiler.UNSTRUCTURED_FORMATS)
    assert min(counts, key=counts.get) == "csr-seg"
    assert counts["hyb"] > 10 * counts["csr-seg"]
    assert plan.choose_format(rep, csr) == "csr-seg"
    fmt = auto_format(csr, rep)
    assert fmt is csr                   # csr-seg's container is the CSR
    _assert_matches_dense(fmt, csr)
    p = plan.compile(csr, reorder="none", predictor="none")
    assert p.format_name == "csr-seg"
    x = np.random.default_rng(42).normal(size=csr.n_cols).astype(np.float32)
    want = np.asarray(csr.to_dense(), np.float64) @ x
    np.testing.assert_allclose(np.asarray(p.execute(jnp.asarray(x))), want,
                               rtol=2e-3, atol=2e-3)


def test_flat_unstructured_stays_csr():
    """Unstructured but near-uniform row lengths: every 128-row block
    holds about the same nonzeros, so the column-blocked CSR layout pads
    least and the dispatcher keeps it."""
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


def _sparse_off_diagonals(n=4096) -> CSR:
    """A main diagonal plus 20 off-main diagonals of two entries each."""
    offs = [o for o in range(-10, 11) if o]
    rows = np.concatenate([np.arange(n)] + [[100, 3000]] * len(offs))
    cols = np.concatenate([np.arange(n)] + [[100 + o, 3000 + o]
                                            for o in offs])
    vals = np.random.default_rng(1).uniform(0.5, 1.5, rows.size)
    return CSR.from_coo(rows, cols, vals, n, n)


def test_sparse_off_diagonals_stay_csr():
    """Few offsets but a thin fill: 20 off-main diagonals of two entries
    each would pad 20 full-length rows of DIA for 40 nonzeros."""
    csr = _sparse_off_diagonals()
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
    # csr-seg: the segmented layout runs over the CSR container
    pytest.param(lambda: rmat_matrix(2048, seed=5), CSR, id="rmat-csr-seg"),
    (lambda: uniform_random_matrix(2048, nnz_per_row=8, seed=5), CSR),
])
def test_all_dispatch_paths_agree_with_dense(gen, expected):
    csr = gen()
    fmt = auto_format(csr)
    assert isinstance(fmt, expected)
    _assert_matches_dense(fmt, csr)


def _one_per_row(n=256) -> CSR:
    """A permutation matrix: one segment of 256 rows plus its pad row,
    which widens the rank window past 256."""
    cols = np.random.default_rng(3).permutation(n)
    return CSR.from_coo(np.arange(n), cols, np.ones(n, np.float32), n, n)


def _built_elements(prep, format_name: str) -> int:
    """Gathered index slots plus merged row ids of a built layout."""
    if format_name == "csr":
        return prep.cols.size
    if format_name == "ell":
        return prep.idx.size
    if format_name == "csr-seg":
        return prep.cols.size + prep.row_ids.size
    return (prep.light.idx.size + prep.heavy.cols.size
            + prep.heavy.row_ids.size)


@pytest.mark.parametrize("semiring_safe", [False, True],
                         ids=["plus_times", "semiring"])
@pytest.mark.parametrize("gen,n_stripes", [
    (lambda: rmat_matrix(2 ** 11, seed=5), 1),
    (lambda: rmat_matrix(2 ** 11, seed=5), 2),
    (lambda: rmat_matrix(2 ** 14, seed=1), 1),
    (lambda: uniform_random_matrix(2048, nnz_per_row=8, seed=5), 1),
    (_sparse_off_diagonals, 1),
    (_one_per_row, 1),
], ids=["rmat-2p11", "rmat-2p11-2-stripes", "rmat-2p14", "uniform-8",
        "sparse-off-diagonals", "one-per-row"])
def test_xla_element_counts_match_built_layouts(gen, n_stripes,
                                                semiring_safe):
    """The count the plan ranks by, taken from the row lengths, is the
    size of the index arrays (and merge row ids) `_prepare` builds.  The
    one bound is HYB's heavy rank window: its stream is column-sorted, so
    the count takes the segment length or the heavy rows, whichever is
    fewer."""
    from repro.plan import compiler as pc

    csr = gen()
    rep = structure.analyze(csr)
    assert rep.kind == "unstructured"
    pad = float("inf") if semiring_safe else 0.0
    knobs = dict(bm=128, seg_len=512, n_stripes=n_stripes)
    counts = pc.xla_elements(csr, pc.UNSTRUCTURED_FORMATS + ("ell",),
                             **knobs)
    for fmt in counts:
        prep = pc._prepare(pc.convert(csr, fmt, fill=pad), fmt, bn=512,
                           pad_value=pad, **knobs)
        built = _built_elements(prep, fmt)
        if fmt != "hyb":
            assert counts[fmt] == built, fmt
            continue
        slots = prep.light.idx.size + prep.heavy.cols.size
        rwin_bound = (counts[fmt] - slots) // prep.heavy.cols.shape[0]
        assert prep.heavy.rwin <= rwin_bound <= prep.heavy.seg_len
        assert counts[fmt] >= built
    ranked = pc.UNSTRUCTURED_FORMATS + (("ell",) if semiring_safe else ())
    best = min(ranked, key=counts.get)
    assert plan.choose_format(rep, csr, semiring_safe, **knobs) == best


def test_compile_records_xla_elements():
    """`plan.compile` records each ranked candidate's count and the chosen
    layout's; a forced format records its own count."""
    csr = rmat_matrix(2 ** 11, seed=5)
    p = plan.compile(csr, reorder="none", predictor="none", use_pallas=False)
    by_format = p.compile_stats["xla_elements_by_format"]
    assert set(by_format) == {"csr", "csr-seg", "hyb"}
    assert p.format_name == min(by_format, key=by_format.get) == "csr-seg"
    assert p.compile_stats["xla_elements"] == by_format["csr-seg"]
    forced = plan.compile(csr, reorder="none", predictor="none",
                          use_pallas=False, format="hyb")
    assert "xla_elements_by_format" not in forced.compile_stats
    assert forced.compile_stats["xla_elements"] == by_format["hyb"]
