"""Reordering invariants: permutation validity, round-trips, SpMV
equivalence (bit-identical on exactly-representable values), RCM
bandwidth reduction, and the auto_format re-decision."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import reorder
from repro.core.formats import CSR, DIA
from repro.core.generators import banded_matrix, fd_matrix, rmat_matrix
from repro.core.spmv import spmv
from repro.core.structure import analyze, analyze_reorder
from repro.plan import auto_format

N = 256


def _int_valued(csr: CSR, seed: int = 0) -> CSR:
    """Same pattern, small-integer values: f32 sums are exact, so SpMV
    results must be BIT-identical under any summation order."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 5, size=csr.nnz).astype(np.float32)
    return CSR(data=jnp.asarray(vals), indices=csr.indices,
               indptr=csr.indptr, n_rows=csr.n_rows, n_cols=csr.n_cols)


@pytest.fixture(params=["fd", "rmat"])
def matrix(request):
    if request.param == "fd":
        return fd_matrix(N, seed=1)
    return rmat_matrix(N, seed=1)


@pytest.mark.parametrize("name", list(reorder.STRATEGIES))
def test_strategy_produces_true_permutations(matrix, name):
    r = reorder.STRATEGIES[name](matrix)
    r.validate()   # raises unless both perms are true permutations
    assert reorder.is_permutation(r.row_perm, matrix.n_rows)
    assert reorder.is_permutation(r.col_perm, matrix.n_cols)
    assert r.strategy != ""


@pytest.mark.parametrize("name", ["rcm", "degree-sort", "cache-block"])
def test_permute_roundtrips_through_inverse(matrix, name):
    r = reorder.STRATEGIES[name](matrix)
    back = r.apply(matrix).permute(r.inv_row_perm, r.inv_col_perm)
    np.testing.assert_array_equal(np.asarray(back.indptr),
                                  np.asarray(matrix.indptr))
    np.testing.assert_array_equal(np.asarray(back.indices),
                                  np.asarray(matrix.indices))
    np.testing.assert_array_equal(np.asarray(back.data),
                                  np.asarray(matrix.data))


def test_permute_rejects_non_permutation():
    m = rmat_matrix(N, seed=2)
    bad = np.arange(N)
    bad[1] = 0                                  # duplicate index
    with pytest.raises(ValueError, match="not a permutation"):
        m.permute(row_perm=bad)
    with pytest.raises(ValueError, match="not a permutation"):
        m.permute(col_perm=np.arange(N - 1))    # wrong length


def test_inverse_perm_definition():
    r = reorder.rcm(rmat_matrix(N, seed=2))
    np.testing.assert_array_equal(r.row_perm[r.inv_row_perm], np.arange(N))
    np.testing.assert_array_equal(r.inv_col_perm[r.col_perm], np.arange(N))


@pytest.mark.parametrize("name", list(reorder.STRATEGIES))
def test_spmv_bit_identical_under_reorder(matrix, name):
    """reorder -> multiply -> inverse-scatter == plain multiply, to the bit
    (integer-valued data, so float addition order cannot matter)."""
    m = _int_valued(matrix)
    x = jnp.asarray(np.random.default_rng(3).integers(
        0, 8, size=m.n_cols).astype(np.float32))
    y_ref = np.asarray(spmv(m, x))
    r = reorder.STRATEGIES[name](m)
    y = np.asarray(spmv(r.apply(m), x, reordering=r))
    np.testing.assert_array_equal(y, y_ref)


def test_rcm_strictly_reduces_bandwidth_on_scrambled_banded():
    banded = banded_matrix(512, bandwidth=8, seed=4)
    p = np.random.default_rng(5).permutation(512)
    scrambled = reorder.Reordering(row_perm=p, col_perm=p,
                                   strategy="scramble").apply(banded)
    r = reorder.rcm(scrambled)
    bw_before = analyze(scrambled).bandwidth
    bw_after = analyze(scrambled, reordering=r).bandwidth
    assert bw_after < bw_before                 # strict reduction
    assert bw_after <= 4 * analyze(banded).bandwidth   # near-recovery
    assert r.stats["bandwidth_before"] == bw_before
    assert r.stats["bandwidth_after"] == bw_after


def test_auto_format_redecides_after_rcm():
    """Scrambled banded dispatches to CSR; with the RCM reordering the
    re-analysis makes it DIA-eligible again, and the multiply (through the
    reordered DIA) still matches the unpermuted reference."""
    banded = banded_matrix(512, bandwidth=4, nnz_per_row=5, seed=6)
    p = np.random.default_rng(7).permutation(512)
    scrambled = reorder.Reordering(row_perm=p, col_perm=p).apply(banded)
    assert not isinstance(auto_format(scrambled), DIA)
    r = reorder.rcm(scrambled)
    fmt = auto_format(scrambled, reordering=r)
    assert isinstance(fmt, DIA)
    x = jnp.asarray(np.random.default_rng(8).normal(
        size=512).astype(np.float32))
    y = np.asarray(spmv(fmt, x, reordering=r))
    y_ref = np.asarray(spmv(scrambled, x))
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)


def test_chain_composes_to_single_equivalent_permutation():
    m = rmat_matrix(N, seed=9)
    chained = reorder.chain(reorder.rcm, reorder.cache_block)(m)
    chained.validate()
    step1 = reorder.rcm(m)
    step2 = reorder.cache_block(step1.apply(m))
    two_step = step2.apply(step1.apply(m))
    one_step = chained.apply(m)
    np.testing.assert_array_equal(np.asarray(one_step.indices),
                                  np.asarray(two_step.indices))
    np.testing.assert_array_equal(np.asarray(one_step.indptr),
                                  np.asarray(two_step.indptr))
    assert chained.strategy.startswith("chain(")


def test_degree_sort_backs_partition_wrapper():
    from repro.core.partition import sort_rows_by_nnz

    m = rmat_matrix(N, permute=False, seed=10)
    sorted_csr, perm = sort_rows_by_nnz(m)
    assert (np.diff(sorted_csr.row_lengths()) <= 0).all()
    assert reorder.is_permutation(perm, N)


def test_analyze_reorder_reports_improvement():
    m = rmat_matrix(N, seed=11)
    d = analyze_reorder(m, reorder.rcm(m))
    assert d.before.nnz == d.after.nnz          # permutation moves, not drops
    assert d.improved()
    assert "rcm" in d.summary()


def test_pallas_ops_accept_reordering():
    from repro import plan

    m = _int_valued(rmat_matrix(N, seed=12))
    x = jnp.asarray(np.random.default_rng(13).integers(
        0, 8, size=N).astype(np.float32))
    r = reorder.cache_block(m)
    y_ref = np.asarray(spmv(m, x))
    p = plan.compile(m, reorder=r, format="csr", predictor="none")
    assert p.reordering is r
    y = np.asarray(p.execute(x, interpret=True))
    np.testing.assert_array_equal(y, y_ref)


def test_sweep_reorder_dimension():
    from repro.telemetry.report import reorder_gap_report
    from repro.telemetry.sweep import SweepPoint, reorder_sweep

    pts = reorder_sweep(log2ns=(9,),
                        reorderings={"none": None, "rcm": reorder.rcm})
    assert {p.reorder for p in pts} == {"none", "rcm"}
    assert "reorder" in SweepPoint.header()
    report = reorder_gap_report(pts)
    assert "gap_closed" in report.splitlines()[1]
    assert any(line.split(",")[2] == "rcm" for line in report.splitlines()[2:])
