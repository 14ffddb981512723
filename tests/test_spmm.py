"""k vectors at once through the plan: `SpmvPlan.spmm` (features last) and
plus-times `execute_many` (vectors as rows) against the plain float64
reference, on the benchmark's symmetrised Kronecker graph with GCN
normalisation at 2^10-2^11 rows, in interpret mode on the CPU; the SpMM
layout; the semiring `execute_many` taking its arrays as arguments; and
the graph's invariants."""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, reference_spmm
from bench.structures import kron_gcn
from repro import plan
from repro.graph import OR_AND
from repro.kernels import _layout as kl

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads((ROOT / "bench" / "configs"
                     / "ogbn-products-kron.2p21.json").read_text())
SEED = 2 ** 31 + 7
# float32 sums of each entry's products, against |A| |X|: at most 2.8e-7
# over every case here in interpret mode; the bfloat16 control reads 5.6e-3
TOL = 1e-5


def _cfg(log2_rows):
    return dict(CONFIG, log2_rows=log2_rows)


@pytest.fixture(scope="module")
def graph():
    a = gen.generate(_cfg(10), SEED)
    return a, plan.compile(gen.to_program(a), reorder="none",
                           interpret=True)


def _x(n, k, seed=0):
    return np.random.default_rng(seed).standard_normal((n, k)) \
        .astype(np.float32)


def _err(a, X, Y):
    ref, mag = reference_spmm.columns(a, X)
    return reference_spmm.entry_err(Y, ref, mag)


@pytest.mark.parametrize("k", [1, 8, 128, 200, 256])
@pytest.mark.parametrize("path", ["spmm", "execute_many"])
def test_k_vectors_match_the_float64_reference(graph, path, k):
    a, p = graph
    X = _x(a.n_cols, k, seed=k)
    if path == "spmm":
        Y = p.spmm(jnp.asarray(X))
    else:
        Y = p.execute_many(jnp.asarray(X.T)).T
    assert Y.shape == (a.n_rows, k) and Y.dtype == jnp.float32
    assert _err(a, X, Y) < TOL


@pytest.mark.parametrize("path", ["spmm", "execute_many"])
def test_a_reordered_plan_restores_rows_and_columns(path):
    a = gen.generate(_cfg(11), SEED + 1)
    p = plan.compile(gen.to_program(a), reorder="rcm", predictor="none",
                     interpret=True)
    assert p.reordering is not None
    X = _x(a.n_cols, 16, seed=3)
    Y = p.spmm(jnp.asarray(X)) if path == "spmm" else \
        p.execute_many(jnp.asarray(X.T)).T
    assert _err(a, X, Y) < TOL


def test_the_bfloat16_control_fails_the_tolerance(graph):
    a, _ = graph
    X = _x(a.n_cols, 4, seed=1)
    assert _err(a, X, reference_spmm.columns_control(a, X)) > 100 * TOL


def test_the_layout_is_built_once_on_the_first_multiply(graph):
    a, _ = graph
    p = plan.compile(gen.to_program(a), reorder="none", interpret=True)
    assert "prepare_spmm_s" not in p.compile_stats
    p.spmm(jnp.asarray(_x(a.n_cols, 2)))
    prep = p.prepare_spmm()
    assert p.compile_stats["prepare_spmm_s"] > 0
    p.execute_many(jnp.asarray(_x(a.n_cols, 3).T))
    assert p.prepare_spmm() is prep


def test_the_layout_pads_each_row_block_to_whole_chunks():
    a = gen.generate(_cfg(10), SEED)
    prep = kl.prepare_spmm(gen.to_program(a), bm=128, chunk=256, group=16)
    blk, first = np.asarray(prep.blk), np.asarray(prep.first)
    vals = np.asarray(prep.vals)
    assert blk.shape[0] % prep.group == 0 and prep.group % 8 == 0
    assert np.all(np.diff(blk) >= 0)                 # blocks in order
    assert first.sum() == prep.n_blocks              # one start a block
    assert np.all(blk[first == 1] == np.arange(prep.n_blocks))
    assert np.count_nonzero(vals) == a.nnz           # values all positive
    indptr = a.indptr.astype(np.int64)
    for b in (0, prep.n_blocks - 1):
        lo, hi = indptr[b * 128], indptr[min((b + 1) * 128, a.n_rows)]
        slots = np.flatnonzero(np.repeat(blk == b, prep.chunk))
        np.testing.assert_array_equal(
            np.asarray(prep.cols).reshape(-1)[slots][: hi - lo],
            a.indices[lo:hi])


def test_an_empty_row_block_and_a_group_boundary_hold():
    """Rows with no nonzeros (a whole empty block among them) and a block
    whose chunks straddle two kernel calls."""
    rows = np.array([0, 0, 3, 300, 300, 301], np.int64)
    cols = np.array([1, 5, 2, 0, 7, 7], np.int64)
    vals = np.arange(1, 7, dtype=np.float32)
    from repro.core.formats import CSR

    csr = CSR.from_coo(rows, cols, vals, 400, 9)
    dense = np.zeros((400, 9))
    dense[rows, cols] = vals
    prep = kl.prepare_spmm(csr, bm=128, chunk=128, group=8)
    X = _x(9, 5, seed=2)
    Y = kl.spmm_prepared(prep, jnp.asarray(X), interpret=True)
    np.testing.assert_allclose(np.asarray(Y), dense @ X, rtol=1e-6,
                               atol=1e-6)


def test_spmm_refuses_a_semiring_plan_and_a_wrong_shape(graph):
    a, p = graph
    with pytest.raises(ValueError, match="n_cols"):
        p.spmm(jnp.ones((a.n_cols + 1, 2), jnp.float32))
    bp = plan.compile(gen.to_program(a), reorder="none", semiring="or_and",
                      interpret=True)
    with pytest.raises(ValueError, match="plus-times"):
        bp.spmm(jnp.ones((a.n_cols, 2), jnp.float32))


def test_zero_vectors_give_an_empty_product(graph):
    a, p = graph
    assert p.spmm(jnp.zeros((a.n_cols, 0), jnp.float32)).shape == \
        (a.n_rows, 0)


def test_semiring_execute_many_takes_its_arrays_as_arguments(graph):
    """The semiring path's jitted function receives the container's
    arrays as parameters; none is baked into the program."""
    a, _ = graph
    p = plan.compile(gen.to_program(a), reorder="none", semiring=OR_AND,
                     interpret=True)
    X = jnp.asarray((_x(a.n_cols, 3) > 1.0).astype(np.float32).T)
    Y = p.execute_many(X)
    many = p._many_fn
    text = many.func.lower(*many.args, X).as_text()
    params = text[text.index("func.func public @main("):]
    params = params[: params.index("{")]
    for leaf in jax.tree.leaves(p._source_container()):
        assert f"tensor<{'x'.join(map(str, leaf.shape))}x" in params
    for i in range(X.shape[0]):
        np.testing.assert_array_equal(np.asarray(Y[i]),
                                      np.asarray(p.execute(X[i])))


# ---------------------------------------------------------------------------
# the structure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("log2_rows", [10, 12])
def test_the_graph_is_a_symmetric_gcn_normalised_adjacency(log2_rows):
    a = gen.generate(_cfg(log2_rows), SEED)
    n, rows = a.n_rows, a.rows()
    dense = np.zeros((n, n), np.float32)
    dense[rows, a.indices] = a.data
    np.testing.assert_array_equal(dense, dense.T)            # symmetric
    assert np.all(np.diag(dense) > 0)                        # self-loops
    keys = rows * n + a.indices
    assert np.all(np.diff(keys) > 0)                         # merged, sorted
    deg = np.diff(a.indptr).astype(np.float64)
    np.testing.assert_allclose(a.data, 1.0 / np.sqrt(deg[rows]
                                                     * deg[a.indices]),
                               rtol=1e-7)
    assert a.data.dtype == np.float32 and a.indices.dtype == np.int32


def test_every_seed_gives_the_same_graph_relabelled():
    a = gen.generate(_cfg(10), SEED)
    b = gen.generate(_cfg(10), SEED + 1)
    assert a.nnz == b.nnz
    np.testing.assert_array_equal(np.sort(np.diff(a.indptr)),
                                  np.sort(np.diff(b.indptr)))
    assert not np.array_equal(a.indices, b.indices)


def test_the_configuration_keeps_ogbs_degree_within_two_percent():
    """The stored nnz the configuration reads at its size on the chip (a
    2^21-vertex graph is not generated on the CPU): off the diagonal
    within 2% of OGB's 50.5 a vertex, and one self-loop a vertex."""
    n = 1 << CONFIG["log2_rows"]
    measured = CONFIG["stored_nnz"]
    target = CONFIG["published"]["average_degree"] * n
    assert abs(measured["off_diagonal"] - target) <= 0.02 * target
    assert measured["total"] == measured["off_diagonal"] + n
    # the count grows with the edge factor as the configuration says
    small = [gen.generate(dict(_cfg(12), edge_factor=f), SEED).nnz
             for f in (CONFIG["edge_factor"] - 1, CONFIG["edge_factor"])]
    assert small[0] < small[1]


def test_the_structure_module_normalises_any_pattern():
    a = gen.csr_from_sorted(np.array([0, 0, 1, 1]), np.array([0, 1, 0, 1]),
                            np.ones(4, np.float32), 2, 2)
    np.testing.assert_allclose(kron_gcn.normalise(a).data, [0.5] * 4)
