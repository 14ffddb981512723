"""Dispatcher + composite analytics (paper §I motivation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import BELL, CSR, DIA, ELL, HYB
from repro.core.generators import banded_matrix, fd_matrix, rmat_matrix
from repro.core.spmv import power_iteration, spmv
from repro.graph import pagerank, transpose_csr
from repro.plan import auto_format


def test_auto_format_banded_goes_dia():
    assert isinstance(auto_format(fd_matrix(1024)), DIA)


def test_auto_format_unstructured_goes_csr_bell_or_hyb():
    fmt = auto_format(rmat_matrix(1024))
    assert isinstance(fmt, (CSR, BELL, HYB))


def test_spmv_pallas_path_matches_jnp():
    from repro import plan

    csr = fd_matrix(256)
    x = jnp.asarray(np.random.default_rng(0).normal(size=256)
                    .astype(np.float32))
    p = plan.compile(csr, reorder="none", predictor="none")
    assert type(p.container) is type(auto_format(csr))
    y_pallas = p.execute(x, interpret=True)
    y_jnp = spmv(csr, x)
    np.testing.assert_allclose(np.asarray(y_pallas), np.asarray(y_jnp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("container", [CSR, ELL, HYB, BELL, DIA])
def test_spmv_jnp_dispatch_traces_under_jit(container):
    """The jnp dispatch is traceable: a traced container multiplies to
    what the eager call gives."""
    csr = rmat_matrix(256, seed=9)
    m = csr if container is CSR else container.from_csr(csr)
    x = jnp.asarray(np.random.default_rng(1).normal(size=256)
                    .astype(np.float32))
    np.testing.assert_allclose(np.asarray(jax.jit(spmv)(m, x)),
                               np.asarray(spmv(m, x)), rtol=1e-5, atol=1e-5)


def test_power_iteration_converges_on_spd():
    # A = B B^T + n I is SPD with known dominant behaviour
    n = 128
    csr = banded_matrix(n, 4, nnz_per_row=3, seed=1)
    dense = np.asarray(csr.to_dense())
    spd = dense @ dense.T + n * np.eye(n, dtype=np.float32)
    lam, v = power_iteration(jnp.asarray(spd),
                             jnp.ones((n,), jnp.float32) / np.sqrt(n),
                             n_iters=200)
    w = np.linalg.eigvalsh(spd)
    assert float(lam) == pytest.approx(float(w[-1]), rel=1e-3)


def test_pagerank_is_distribution():
    r = pagerank(transpose_csr(rmat_matrix(512)), tol=0, max_iters=16).values
    assert float(jnp.sum(r)) == pytest.approx(1.0, abs=0.05)
    assert float(jnp.min(r)) >= 0.0
