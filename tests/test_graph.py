"""Semiring graph layer: drivers vs dense references on FD and R-MAT,
plus-times bit-identity with the existing Pallas path, empty-frontier
termination, and the per-iteration telemetry hook."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan
from repro.core.formats import CSR
from repro.core.generators import fd_matrix, rmat_matrix
from repro.graph import (MIN_PLUS, OR_AND, PLUS_TIMES, SEMIRINGS, bfs,
                         connected_components, pagerank,
                         spmv_semiring_jnp, sssp, transpose_csr)
from repro.kernels import _layout as kl

N = 256


def _graphs():
    return [("fd", fd_matrix(N, seed=2)), ("rmat", rmat_matrix(N, seed=2))]


def _empty(n=16):
    z = np.array([], dtype=np.int64)
    return CSR.from_coo(z, z, np.array([], dtype=np.float32), n, n)


# ---------------------------------------------------------------------------
# dense references (pure numpy, independent of the kernel stack)
# ---------------------------------------------------------------------------

def _nz_mask(csr):
    m = np.zeros((csr.n_rows, csr.n_cols), dtype=bool)
    ip, ci = np.asarray(csr.indptr), np.asarray(csr.indices)
    for r in range(csr.n_rows):
        m[r, ci[ip[r]:ip[r + 1]]] = True
    return m


def _bfs_ref(csr, src):
    """Hop depths along edges i->j by frontier expansion on the dense
    adjacency."""
    adj = _nz_mask(csr)
    n = csr.n_rows
    depth = np.full(n, np.inf)
    depth[src] = 0
    frontier = {src}
    level = 0
    while frontier:
        level += 1
        nxt = set()
        for u in frontier:
            for v in np.nonzero(adj[u])[0]:
                if np.isinf(depth[v]):
                    depth[v] = level
                    nxt.add(v)
        frontier = nxt
    return depth


def _sssp_ref(csr, src):
    """Bellman-Ford on the dense weights."""
    w = np.where(_nz_mask(csr), np.asarray(csr.to_dense(), np.float64),
                 np.inf)
    n = csr.n_rows
    d = np.full(n, np.inf)
    d[src] = 0.0
    for _ in range(n):
        nd = np.minimum(d, (w + d[:, None]).min(axis=0))
        if np.array_equal(nd, d):
            break
        d = nd
    return d


# ---------------------------------------------------------------------------
# semiring algebra + kernels
# ---------------------------------------------------------------------------

def test_semiring_registry_padding_is_absorbing():
    for name, sr in SEMIRINGS.items():
        x = jnp.asarray([0.5, 2.0, 0.0], jnp.float32)
        contrib = sr.mul(jnp.full_like(x, sr.pad_value), x)
        assert np.all(np.asarray(contrib) == sr.identity), name


@pytest.mark.parametrize("fmt", ["ell", "csr"])
@pytest.mark.parametrize("srname", ["min_plus", "or_and", "max_times"])
def test_semiring_pallas_matches_dense_reference(fmt, srname):
    sr = SEMIRINGS[srname]
    m = rmat_matrix(N, seed=1)
    if srname != "min_plus":
        # nonnegative values for the max-family semirings
        m = CSR(data=jnp.abs(m.data), indices=m.indices, indptr=m.indptr,
                n_rows=N, n_cols=N)
    x = jnp.asarray(np.abs(np.random.default_rng(0).normal(size=N))
                    .astype(np.float32))
    p = plan.compile(m, semiring=srname, format=fmt, reorder="none",
                     predictor="none")
    got = np.asarray(p.execute(x))

    nz = _nz_mask(m)
    dense = np.asarray(m.to_dense(), np.float64)
    xv = np.asarray(x, np.float64)
    if srname == "min_plus":
        want = np.where(nz, dense + xv[None, :], np.inf).min(axis=1)
    else:
        want = np.where(nz, dense * xv[None, :], 0.0).max(axis=1)
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-6)
    # jnp reference path agrees with the Pallas path
    np.testing.assert_allclose(
        np.asarray(spmv_semiring_jnp(p.container, x, sr)), got, rtol=1e-6)


def test_plus_times_semiring_bit_identical_to_existing_pallas():
    m = rmat_matrix(N, seed=4)
    x = jnp.asarray(np.random.default_rng(1).normal(size=N)
                    .astype(np.float32))
    pairs = {"ell": (kl.prepare_ell, kl.spmv_ell_prepared),
             "csr": (kl.prepare_csr, kl.spmv_csr_prepared)}
    for fmt in ("ell", "csr"):
        prepare, run = pairs[fmt]
        prep = prepare(plan.convert(m, fmt))
        base = run(prep, x)
        via_semiring = run(prep, x, semiring=PLUS_TIMES)
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(via_semiring))
        p = plan.compile(m, semiring="plus_times", format=fmt,
                         reorder="none", predictor="none")
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(p.execute(x)))


def test_semiring_plan_requires_sparse_slot_format():
    m = fd_matrix(64)
    with pytest.raises(ValueError, match="ell.*csr|csr.*ell"):
        plan.compile(m, semiring="min_plus", format="dia")
    p = plan.compile(m, semiring="min_plus")        # default: ell
    assert p.format_name == "ell" and p.semiring == "min_plus"


def test_semiring_plan_checkpoint_roundtrip(tmp_path):
    from repro.plan import load_plan, save_plan

    m = rmat_matrix(128, seed=5)
    p = plan.compile(m, semiring="min_plus", reorder="none",
                     predictor="none")
    x = jnp.asarray(np.abs(np.random.default_rng(2).normal(size=128))
                    .astype(np.float32))
    save_plan(p, str(tmp_path / "ck"))
    p2, _ = load_plan(str(tmp_path / "ck"))
    assert p2.semiring == "min_plus"
    np.testing.assert_array_equal(np.asarray(p.execute(x)),
                                  np.asarray(p2.execute(x)))


# ---------------------------------------------------------------------------
# drivers vs references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["fd", "rmat"])
def test_pagerank_matches_dense_power_iteration(kind):
    m = dict(_graphs())[kind]
    n = m.n_rows
    res = pagerank(m, tol=1e-10, max_iters=300)
    assert res.converged

    out_deg = np.diff(np.asarray(m.indptr)).astype(np.float64)
    nz = _nz_mask(m)
    P = np.where(nz, 1.0 / np.maximum(out_deg[:, None], 1.0), 0.0).T
    dang = (out_deg == 0).astype(np.float64)
    r = np.full(n, 1.0 / n)
    for _ in range(300):
        r = 0.85 * (P @ r + (dang @ r) / n) + 0.15 / n
    np.testing.assert_allclose(res.values, r, atol=1e-6)
    assert abs(float(res.values.sum()) - 1.0) < 1e-4


@pytest.mark.parametrize("kind", ["fd", "rmat"])
def test_bfs_depths_match_reference(kind):
    m = dict(_graphs())[kind]
    src = int(np.argmax(np.diff(np.asarray(m.indptr))))
    res = bfs(m, src)
    assert res.converged
    np.testing.assert_array_equal(res.values, _bfs_ref(m, src))


def test_bfs_multi_source_execute_many():
    m = rmat_matrix(N, seed=2)
    lens = np.diff(np.asarray(m.indptr))
    srcs = list(np.argsort(lens)[-3:])
    res = bfs(m, srcs)
    assert res.values.shape == (3, N)
    for i, s in enumerate(srcs):
        np.testing.assert_array_equal(res.values[i], _bfs_ref(m, int(s)))


@pytest.mark.parametrize("kind", ["fd", "rmat"])
def test_sssp_matches_bellman_ford(kind):
    m = dict(_graphs())[kind]
    mw = CSR(data=jnp.abs(m.data), indices=m.indices, indptr=m.indptr,
             n_rows=m.n_rows, n_cols=m.n_cols)
    src = int(np.argmax(np.diff(np.asarray(m.indptr))))
    res = sssp(mw, src)
    assert res.converged
    np.testing.assert_allclose(res.values, _sssp_ref(mw, src), atol=1e-5)


@pytest.mark.parametrize("kind", ["fd", "rmat"])
def test_connected_components_labels(kind):
    m = dict(_graphs())[kind]
    res = connected_components(m)
    assert res.converged
    # reference: union-find over the symmetrized edge list
    parent = list(range(m.n_rows))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    ip, ci = np.asarray(m.indptr), np.asarray(m.indices)
    for r in range(m.n_rows):
        for c in ci[ip[r]:ip[r + 1]]:
            ra, rb = find(r), find(int(c))
            if ra != rb:
                parent[max(ra, rb)] = min(ra, rb)
    want = np.asarray([find(v) for v in range(m.n_rows)], np.float32)
    np.testing.assert_array_equal(res.values, want)


# ---------------------------------------------------------------------------
# degenerate graphs / termination
# ---------------------------------------------------------------------------

def test_bfs_empty_graph_terminates_immediately():
    res = bfs(_empty(), 3)
    assert res.converged and res.n_iters == 1
    assert res.values[3] == 0.0
    assert np.isinf(np.delete(res.values, 3)).all()


def test_sssp_empty_graph_all_unreachable():
    res = sssp(_empty(), 0)
    assert res.converged
    assert res.values[0] == 0.0 and np.isinf(res.values[1:]).all()


def test_connected_components_edgeless_graph_is_all_singletons():
    res = connected_components(_empty(8))
    assert res.converged
    np.testing.assert_array_equal(res.values, np.arange(8, dtype=np.float32))


def test_pagerank_empty_graph_is_uniform():
    res = pagerank(_empty(8), max_iters=50)
    assert res.converged
    np.testing.assert_allclose(res.values, np.full(8, 1 / 8), rtol=1e-5)


# ---------------------------------------------------------------------------
# source edge cases through the plan path (empty / duplicate / bad sources)
# ---------------------------------------------------------------------------

def test_bfs_empty_sources_is_well_defined():
    """An empty source list is a zero-lane run: (0, n) values, converged
    at zero iterations, nothing executed through the plan."""
    m = fd_matrix(N, seed=2)
    res = bfs(m, [])
    assert res.values.shape == (0, N)
    assert res.converged and res.n_iters == 0 and res.history == []


@pytest.mark.parametrize("reorder", ["none", "rcm"])
def test_bfs_duplicate_sources_produce_equal_rows(reorder):
    """Duplicate source indices are distinct lanes with identical
    frontiers -- the batched path (including the reordered gather /
    scatter) must keep them bit-identical to the deduplicated run."""
    m = rmat_matrix(N, seed=2)
    res = bfs(m, [7, 7, 3], reorder=reorder)
    assert res.values.shape == (3, N)
    np.testing.assert_array_equal(res.values[0], res.values[1])
    solo = bfs(m, 7, reorder=reorder)
    np.testing.assert_array_equal(res.values[0], solo.values)


@pytest.mark.parametrize("bad", [[-1], [0, N + 3], N + 3])
def test_bfs_out_of_range_sources_raise_value_error(bad):
    m = fd_matrix(N, seed=2)
    with pytest.raises(ValueError, match="out of range"):
        bfs(m, bad)


def test_sssp_out_of_range_source_raises_value_error():
    with pytest.raises(ValueError, match="out of range"):
        sssp(fd_matrix(N, seed=2), N)


def test_transpose_csr_roundtrip():
    m = rmat_matrix(128, seed=7)
    tt = transpose_csr(transpose_csr(m))
    np.testing.assert_array_equal(np.asarray(tt.to_dense()),
                                  np.asarray(m.to_dense()))


# ---------------------------------------------------------------------------
# telemetry wiring
# ---------------------------------------------------------------------------

def test_iteration_telemetry_warm_iterations_miss_less():
    from repro.graph import iteration_summaries

    res = pagerank(rmat_matrix(512, seed=0), max_iters=8, tol=0.0)
    sums = iteration_summaries(res.plan, res.n_iters)
    assert len(sums) == res.n_iters
    # cold first pass misses at least as much as any warm iteration
    assert sums[0].l2_mpki >= max(s.l2_mpki for s in sums[1:])


def test_graph_sweep_produces_gap_rows():
    from repro.telemetry import graph_gap_report, graph_sweep

    pts = graph_sweep(log2ns=(8,), analytics=("bfs",), max_iters=16)
    assert {p.kind for p in pts} == {"fd", "rmat"}
    rep = graph_gap_report(pts)
    assert "gap_total" in rep and "bfs" in rep


# ---------------------------------------------------------------------------
# review regressions
# ---------------------------------------------------------------------------

def test_graph_sweep_runs_connected_components():
    from repro.telemetry import graph_sweep

    pts = graph_sweep(log2ns=(8,), analytics=("connected_components",),
                      max_iters=16)
    assert all(p.analytic == "connected_components" for p in pts)
    assert {p.kind for p in pts} == {"fd", "rmat"}


def _padding_probe_graph():
    """Empty rows, rows of one or two edges, a hub row that outgrows the
    rest, and an explicit weight-0.0 edge into vertex 0: the slots a
    padded layout must tell apart from its own padding."""
    rows = [0, 0, 2, 4, 4, 6]
    cols = [1, 2, 0, 0, 3, 5]
    vals = [3.0, 1.0, 0.0, 2.0, 4.0, 1.0]
    hub = list(range(1, 31))
    rows += [5] * len(hub)
    cols += hub
    vals += [float(1 + c % 4) for c in hub]
    return CSR.from_coo(rows, cols, vals, 32, 32)


@pytest.mark.parametrize("fmt", ["ell", "hyb", "csr", "csr-seg"])
def test_min_plus_plan_padding_is_absorbing(fmt):
    """A min-plus plan pads with +inf wherever it builds its layout, so no
    padding slot reads as a weight-0 edge to vertex 0, while a real
    weight-0 edge into vertex 0 still counts."""
    m = _padding_probe_graph()
    x = np.arange(5.0, 5.0 + m.n_cols)            # x[0] = 5 is the smallest
    p = plan.compile(m, semiring="min_plus", format=fmt, reorder="none",
                     predictor="none")
    got = np.asarray(p.execute(jnp.asarray(x, jnp.float32)))

    w = np.where(_nz_mask(m), np.asarray(m.to_dense(), np.float64), np.inf)
    want = (w + x[None, :]).min(axis=1)
    assert want[2] == 5.0 and np.isinf(want[1])
    np.testing.assert_array_equal(got, want.astype(np.float32))


def test_compile_rejects_unregistered_semiring_instance():
    import dataclasses

    from repro.graph.semiring import MIN_PLUS as REG

    custom = dataclasses.replace(REG, name="my_custom_sr")
    with pytest.raises(ValueError, match="not registered"):
        plan.compile(fd_matrix(64), semiring=custom)
    # registry instances pass through fine
    p = plan.compile(fd_matrix(64), semiring=REG)
    assert p.semiring == "min_plus"


def test_core_spmv_pagerank_delegates_with_legacy_semantics():
    """PageRank on the transpose reproduces the historical
    column-stochastic iteration exactly (same math, fixed iterations)."""
    m = rmat_matrix(256, seed=9)
    n = m.n_rows
    got = np.asarray(pagerank(transpose_csr(m), tol=0, max_iters=16).values)

    ip, ci = np.asarray(m.indptr), np.asarray(m.indices)
    col_deg = np.bincount(ci, minlength=n).astype(np.float64)
    rows = np.repeat(np.arange(n), np.diff(ip))
    S = np.zeros((n, n))
    for r_, c_ in zip(rows, ci):
        S[r_, c_] += 1.0 / max(col_deg[c_], 1.0)
    dang = (col_deg == 0).astype(np.float64)
    r = np.full(n, 1.0 / n)
    for _ in range(16):
        r = 0.85 * (S @ r + (dang @ r) / n) + 0.15 / n
    np.testing.assert_allclose(got, r, atol=1e-6)
