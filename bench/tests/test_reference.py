"""The float64 references agree with the program at a tiny size (Pallas
kernels in interpret mode), and the bfloat16 controls do not."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, reference, registry
from bench.tests.test_gen import FD, RMAT

SPMV_LIMIT = registry.traffic("spmv_chain")["limits"]["max_row_err"]
PAGERANK = registry.traffic("pagerank5")


@pytest.fixture(scope="module")
def fd():
    return gen.generate(FD, seed=1)


@pytest.fixture(scope="module")
def rm():
    return gen.generate(dict(RMAT, log2_rows=10), seed=2)


@pytest.mark.parametrize("which,fmt", [("fd", "csr"), ("fd", "dia"),
                                       ("rm", "hyb"), ("rm", "csr-seg")])
def test_spmv_reference_agrees_with_the_plan(which, fmt, request):
    from repro import plan

    a = request.getfixturevalue(which)
    x = gen.start_vector(7, a.n_cols)
    y = plan.compile(gen.to_program(a), reorder="none", format=fmt,
                     interpret=True).execute(x)
    assert reference.rel_err(y, reference.spmv(a, np.asarray(x))) < 1e-6
    assert reference.row_err(y, a, x) < 1e-6


def test_pagerank_reference_agrees_with_graph_pagerank(rm):
    from repro import graph

    res = graph.pagerank(gen.to_program(rm), damping=PAGERANK["damping"],
                         tol=0.0, max_iters=PAGERANK["max_iters"],
                         interpret=True)
    assert res.n_iters == PAGERANK["max_iters"]
    ref = reference.pagerank(rm, PAGERANK["max_iters"],
                             PAGERANK["damping"])
    assert abs(ref.sum() - 1.0) < 1e-12
    assert reference.rel_err(res.values, ref) < 1e-5


@pytest.mark.parametrize("which", ["fd", "rm"])
def test_spmv_control_fails_the_limit(which, request):
    a = request.getfixturevalue(which)
    x = np.asarray(gen.start_vector(8, a.n_cols))
    err = reference.row_err(reference.spmv_control(a, x), a, x)
    assert err > 3 * SPMV_LIMIT


def test_pagerank_control_fails_the_limit(rm):
    n_iters, damping = PAGERANK["max_iters"], PAGERANK["damping"]
    ctl = reference.pagerank_control(rm, n_iters, damping)
    ref = reference.pagerank(rm, n_iters, damping)
    assert reference.rel_err(ctl, ref) > 3 * PAGERANK["limits"]["max_rel_err"]


def test_rel_err_marks_non_finite_and_misshapen_answers():
    ref = np.array([1.0, -2.0, 4.0])
    assert reference.rel_err(jnp.array([1.0, -2.0, 4.0]), ref) == 0.0
    assert reference.rel_err(np.array([1.0, -1.0, 4.0]), ref) == 0.25
    assert reference.rel_err(np.array([1.0, np.nan, 4.0]), ref) == np.inf
    assert reference.rel_err(np.array([1.0, -2.0]), ref) == np.inf


def test_row_err_weighs_each_row_by_its_own_magnitudes():
    # rows: [1, 1e6] x, [1] x, empty
    a = gen.HostCSR(indptr=np.array([0, 2, 3, 3], np.int32),
                    indices=np.array([0, 1, 2], np.int32),
                    data=np.array([1.0, 1e6, 1.0], np.float32),
                    n_rows=3, n_cols=3)
    x = np.array([1.0, 1.0, 2.0])
    exact = np.array([1e6 + 1.0, 2.0, 0.0])
    assert reference.row_err(exact, a, x) == 0.0
    # an error of 1e-3 is nothing beside row 0's 1e6, half of row 1's 2
    assert reference.row_err(exact + [1e-3, 0, 0], a, x) < 1e-8
    assert reference.row_err(exact + [0, 1.0, 0], a, x) == 0.5
    # an empty row must read exactly zero
    assert reference.row_err(exact + [0, 0, 1e-9], a, x) > 1.0
    assert reference.row_err(exact[:2], a, x) == np.inf
    assert reference.row_err(exact + [0, np.nan, 0], a, x) == np.inf
