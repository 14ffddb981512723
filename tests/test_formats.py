"""Format containers: construction, round-trips, storage accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _opt_deps import given, settings, st

from repro.core.formats import BELL, CSR, DIA, ELL
from repro.core.generators import fd_matrix, rmat_matrix


def random_coo(n, m, nnz, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, nnz)
    cols = rng.integers(0, m, nnz)
    vals = rng.normal(size=nnz).astype(dtype)
    return rows, cols, vals


def test_csr_from_coo_dense_roundtrip():
    rows, cols, vals = random_coo(13, 17, 40)
    csr = CSR.from_coo(rows, cols, vals, 13, 17)
    dense = np.zeros((13, 17), np.float32)
    np.add.at(dense, (rows, cols), vals)
    np.testing.assert_allclose(np.asarray(csr.to_dense()), dense, rtol=1e-6)


def test_csr_storage_accounting_matches_paper():
    """Paper §II-A: CSR stores 2m + n + 1 elements."""
    csr = fd_matrix(64)
    n, m = csr.n_rows, csr.nnz
    n_elems = (csr.data.size + csr.indices.size + csr.indptr.size)
    assert n_elems == 2 * m + n + 1


@pytest.mark.parametrize("fmt", [ELL, BELL, DIA])
def test_format_conversion_preserves_matrix(fmt):
    csr = rmat_matrix(128, seed=3)
    other = fmt.from_csr(csr)
    x = jnp.asarray(np.random.default_rng(0).normal(size=128)
                    .astype(np.float32))
    from repro.core.spmv import spmv
    np.testing.assert_allclose(np.asarray(spmv(other, x)),
                               np.asarray(spmv(csr, x)),
                               rtol=1e-4, atol=1e-4)


def test_bell_blocks_are_lane_shaped():
    csr = rmat_matrix(256, seed=1)
    bell = BELL.from_csr(csr, bm=8, bn=128)
    assert bell.data.shape[2:] == (8, 128)
    assert 0.0 < bell.density() <= 1.0


def test_dia_offsets_sorted_unique():
    dia = DIA.from_csr(fd_matrix(144))
    offs = np.asarray(dia.offsets)
    assert (np.diff(offs) > 0).all()


@pytest.mark.parametrize("n,m,nnz", [(13, 17, 60), (17, 13, 60), (1, 5, 4),
                                     (9, 9, 0)])
def test_dia_band_matches_dense(n, m, nnz):
    """Every stored diagonal is the dense matrix's, summed duplicates and
    rectangular shapes included, and only diagonals holding a nonzero
    are stored."""
    rows, cols, vals = random_coo(n, m, nnz, seed=n + m)
    csr = CSR.from_coo(rows, cols, vals, n, m)
    dense = np.asarray(csr.to_dense())
    dia = DIA.from_csr(csr)
    offs = np.asarray(dia.offsets)
    np.testing.assert_array_equal(offs, np.unique(cols - rows))
    band = np.zeros((offs.size, n), np.float32)
    for k, off in enumerate(offs):
        i = np.arange(max(0, -off), min(n, m - off))
        band[k, i] = dense[i, i + off]
    np.testing.assert_array_equal(np.asarray(dia.data), band)


def test_formats_are_pytrees():
    csr = fd_matrix(64)
    leaves = jax.tree.leaves(csr)
    assert len(leaves) == 3
    # jit through the container
    f = jax.jit(lambda c, x: c.data.sum() + x.sum())
    f(csr, jnp.ones(4))


@settings(max_examples=25, deadline=None)
@given(n=st.integers(8, 64), nnz=st.integers(1, 200), seed=st.integers(0, 99))
def test_property_all_formats_agree(n, nnz, seed):
    rows, cols, vals = random_coo(n, n, nnz, seed)
    csr = CSR.from_coo(rows, cols, vals, n, n)
    x = np.random.default_rng(seed).normal(size=n).astype(np.float32)
    xj = jnp.asarray(x)
    from repro.core.spmv import spmv
    ref = np.asarray(csr.to_dense()) @ x
    for fmt in (csr, ELL.from_csr(csr), BELL.from_csr(csr),
                DIA.from_csr(csr)):
        got = np.asarray(spmv(fmt, xj))
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
