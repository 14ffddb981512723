"""Plan/executor pipeline: fingerprints, cache correctness, zero-work
cached execution, bit-identity with the `_layout` runner, serialization."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan
from repro.core.formats import BELL, CSR, DIA, ELL, HYB
from repro.core.generators import banded_matrix, fd_matrix, rmat_matrix
from repro.core.spmv import spmv
from repro.kernels import _layout as kl


def _x(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=n)
                       .astype(np.float32))


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def test_fingerprint_stable_across_equal_matrices():
    a = rmat_matrix(256, seed=3)
    b = rmat_matrix(256, seed=3)
    assert a is not b
    assert plan.matrix_fingerprint(a) == plan.matrix_fingerprint(b)


def test_fingerprint_changes_when_data_changes():
    a = rmat_matrix(256, seed=3)
    data = np.asarray(a.data).copy()
    data[0] += 1.0
    b = CSR(data=jnp.asarray(data), indices=a.indices, indptr=a.indptr,
            n_rows=a.n_rows, n_cols=a.n_cols)
    assert plan.matrix_fingerprint(a) != plan.matrix_fingerprint(b)


def test_fingerprint_distinguishes_container_types():
    csr = fd_matrix(64)
    assert plan.matrix_fingerprint(csr) != \
        plan.matrix_fingerprint(ELL.from_csr(csr))


# ---------------------------------------------------------------------------
# cache correctness
# ---------------------------------------------------------------------------

def test_cache_hit_on_equal_matrix_and_miss_on_changed_data():
    cache = plan.PlanCache()
    a = rmat_matrix(256, seed=1)
    p1 = cache.get_or_compile(a, reorder="none", predictor="none")
    p2 = cache.get_or_compile(rmat_matrix(256, seed=1),
                              reorder="none", predictor="none")
    stats = cache.stats()
    assert p1 is p2 and stats["plans"] == 1
    assert stats["hits"] == 1 and stats["misses"] == 1
    assert stats["compiles"] == 1 and stats["compile_s"] > 0.0
    assert stats["hit_rate"] == 0.5 and stats["evictions"] == 0

    data = np.asarray(a.data).copy()
    data[0] *= 2.0
    changed = CSR(data=jnp.asarray(data), indices=a.indices, indptr=a.indptr,
                  n_rows=a.n_rows, n_cols=a.n_cols)
    p3 = cache.get_or_compile(changed, reorder="none", predictor="none")
    assert p3 is not p1 and cache.misses == 2   # content-addressed invalidation


def test_cache_key_includes_options():
    cache = plan.PlanCache()
    a = fd_matrix(256)
    p1 = cache.get_or_compile(a, reorder="none", predictor="none")
    p2 = cache.get_or_compile(a, reorder="none", predictor="none",
                              format="ell")
    assert p1 is not p2 and p2.format_name == "ell"


def test_cache_lru_eviction_and_invalidate():
    cache = plan.PlanCache(max_plans=2)
    mats = [rmat_matrix(128, seed=s) for s in range(3)]
    for m in mats:
        cache.get_or_compile(m, reorder="none", predictor="none")
    assert len(cache) == 2                      # oldest evicted
    assert cache.invalidate(plan.matrix_fingerprint(mats[-1])) == 1
    assert len(cache) == 1


# ---------------------------------------------------------------------------
# zero-work cached execution + bit-identity (acceptance criterion)
# ---------------------------------------------------------------------------

def _install_work_counters(monkeypatch, counts):
    """Count every structure-analysis / reorder / conversion / layout-prep
    entry point; a cached plan execute must drive them all to zero."""
    from repro.core import structure as _structure

    def wrap(obj, name):
        orig = getattr(obj, name)

        def counting(*a, **k):
            counts[name] = counts.get(name, 0) + 1
            return orig(*a, **k)
        monkeypatch.setattr(obj, name, counting)

    wrap(_structure, "analyze")
    wrap(CSR, "permute")
    for cls in (DIA, BELL, ELL, HYB):
        wrap(cls, "from_csr")
    for fn in ("prepare_csr", "prepare_dia", "prepare_ell", "prepare_bell",
               "prepare_ell_shards", "prepare_csr_seg", "prepare_hyb"):
        wrap(kl, fn)


def test_cached_execute_zero_work_bit_identical_rmat_4k(monkeypatch):
    """R-MAT 2^12: a cached plan execute performs zero structure analysis,
    reordering, format conversion, or layout padding, and its result is
    bit-identical to the CSR `_layout` pair run on the same matrix."""
    csr = rmat_matrix(2 ** 12, seed=0)
    x = _x(csr.n_cols, seed=5)
    y_percall = kl.spmv_csr_prepared(kl.prepare_csr(csr), x, interpret=True)

    cache = plan.PlanCache()
    # format pinned to csr: bit-identity against the CSR runner is the
    # point here (auto would pick csr-seg for this matrix; csr-seg/hyb
    # bit-identity to the CSR kernel is pinned by the property suite)
    opts = dict(reorder="none", predictor="analytic", threads=4,
                format="csr")
    p_cold = cache.get_or_compile(csr, **opts)
    p = cache.get_or_compile(csr, **opts)       # warm: cache hit
    assert p is p_cold and cache.hits == 1

    counts = {}
    _install_work_counters(monkeypatch, counts)
    y_plan = p.execute(x, interpret=True)
    assert counts == {}, f"cached execute did per-call work: {counts}"
    assert np.array_equal(np.asarray(y_plan), np.asarray(y_percall))


def test_reordered_plan_matches_reordered_spmv_bitwise():
    base = banded_matrix(512, 6, nnz_per_row=4, seed=1)
    perm = np.random.default_rng(0).permutation(512)
    from repro.reorder import Reordering
    scrambled = Reordering(row_perm=perm, col_perm=perm).apply(base)

    p = plan.compile(scrambled, reorder="rcm", predictor="none",
                     format="csr")
    assert p.reordering is not None
    x = _x(512, seed=2)
    y_plan = p.execute(x, interpret=True)
    y_ref = p.reordering.restore_y(kl.spmv_csr_prepared(
        kl.prepare_csr(p.container), p.reordering.permute_x(x),
        interpret=True))
    assert np.array_equal(np.asarray(y_plan), np.asarray(y_ref))
    # and both equal the unpermuted multiply up to float tolerance
    np.testing.assert_allclose(np.asarray(y_plan),
                               np.asarray(spmv(scrambled, x)),
                               rtol=1e-4, atol=1e-4)


def test_predictor_scores_candidates():
    csr = rmat_matrix(2 ** 10, seed=4)
    p = plan.compile(csr, reorder="auto", predictor="replay", threads=4)
    assert set(p.predicted) == {"none", "rcm"}
    assert all(v["gflops"] > 0 for v in p.predicted.values())
    if p.chosen != "none":
        # a reordered winner must clear the transport margin over identity
        assert p.predicted[p.chosen]["gflops"] > \
            p.predicted["none"]["gflops"] * (1 + plan.compiler.REORDER_MARGIN)


# ---------------------------------------------------------------------------
# repeated-traffic surfaces
# ---------------------------------------------------------------------------

def test_execute_many_matches_per_vector_execute():
    csr = rmat_matrix(512, seed=6)
    p = plan.compile(csr, reorder="rcm", predictor="none")
    X = jnp.stack([_x(512, seed=s) for s in range(4)])
    Y = p.execute_many(X)
    assert Y.shape == (4, 512)
    for k in range(4):
        np.testing.assert_allclose(
            np.asarray(Y[k]), np.asarray(p.execute(X[k], interpret=True)),
            rtol=1e-4, atol=1e-4)


def test_power_iteration_amortized_driver():
    n = 128
    csr = banded_matrix(n, 4, nnz_per_row=3, seed=1)
    dense = np.asarray(csr.to_dense())
    spd = dense @ dense.T + n * np.eye(n, dtype=np.float32)
    rows, cols = np.nonzero(spd)
    spd_csr = CSR.from_coo(rows, cols, spd[rows, cols], n, n)
    p = plan.compile(spd_csr, reorder="none", predictor="none")
    lam, _ = p.power_iteration(jnp.ones((n,), jnp.float32) / np.sqrt(n),
                               n_iters=200)
    w = np.linalg.eigvalsh(spd)
    assert float(lam) == pytest.approx(float(w[-1]), rel=1e-3)


def test_warm_execute_amortizes_compile():
    csr = rmat_matrix(2 ** 11, seed=7)
    x = _x(csr.n_cols)
    t0 = time.perf_counter()
    p = plan.compile(csr, reorder="auto", predictor="analytic")
    p.execute(x, interpret=True).block_until_ready()
    cold = time.perf_counter() - t0

    warm_ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        p.execute(x, interpret=True).block_until_ready()
        warm_ts.append(time.perf_counter() - t0)
    warm = float(np.median(warm_ts))
    assert warm < cold / 2, f"warm {warm:.4f}s vs cold {cold:.4f}s"


# ---------------------------------------------------------------------------
# serialization through checkpoint
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind",
                         ["dia", "csr-reordered", "bell", "csr-seg", "hyb"])
def test_plan_checkpoint_roundtrip(tmp_path, kind):
    if kind == "dia":
        p = plan.compile(fd_matrix(256), reorder="none", predictor="none")
        assert p.format_name == "dia"
    elif kind == "bell":
        p = plan.compile(fd_matrix(256), reorder="none", predictor="none",
                         format="bell")
    elif kind in ("csr-seg", "hyb"):
        p = plan.compile(rmat_matrix(256, seed=2), reorder="none",
                         predictor="none", format=kind)
        assert p.format_name == kind
    else:
        p = plan.compile(rmat_matrix(256, seed=2), reorder="rcm",
                         predictor="none", format="csr")
        assert p.format_name == "csr" and p.reordering is not None

    d = str(tmp_path / kind)
    plan.save_plan(p, d, step=3)
    p2, step = plan.load_plan(d)
    assert step == 3
    assert p2.fingerprint == p.fingerprint
    assert p2.format_name == p.format_name
    assert p2.report == p.report
    if p.reordering is not None:
        assert np.array_equal(p2.reordering.row_perm, p.reordering.row_perm)

    x = _x(256, seed=4)
    assert np.array_equal(np.asarray(p.execute(x, interpret=True)),
                          np.asarray(p2.execute(x, interpret=True)))


def test_sharded_plan_roundtrip_and_execute(tmp_path):
    from repro.distributed import row_mesh

    csr = rmat_matrix(256, seed=8)
    mesh = row_mesh()
    p = plan.compile(csr, mesh=mesh, reorder="none", predictor="none")
    assert p.format_name == "ell-sharded"
    x = _x(256, seed=1)
    y = p.execute(x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(spmv(csr, x)),
                               rtol=1e-4, atol=1e-4)

    d = str(tmp_path / "sharded")
    plan.save_plan(p, d)
    p2, _ = plan.load_plan(d)               # meshes are never serialized
    with pytest.raises(ValueError):
        p2.execute(x, interpret=True)
    p3, _ = plan.load_plan(d, mesh=mesh)    # rebind to this process's devices
    assert np.array_equal(np.asarray(y), np.asarray(p3.execute(x,
                                                               interpret=True)))


def test_sweep_reuses_plan_trace():
    """scaling_sweep replays ONE cached plan/trace across the thread axis
    (and across repeated sweeps in the same process)."""
    from repro.core.cache_model import SANDY_BRIDGE
    from repro.telemetry.sweep import scaling_sweep, sweep_plan_cache

    cache = sweep_plan_cache()
    before = cache.stats()
    pts = scaling_sweep(log2ns=(8,), kinds=("rmat",), threads_list=(1, 2),
                        seed=11, sweeps=1)
    mid = cache.stats()
    assert mid["misses"] == before["misses"] + 1     # compiled once
    scaling_sweep(log2ns=(8,), kinds=("rmat",), threads_list=(1,),
                  seed=11, sweeps=1)
    after = cache.stats()
    assert after["misses"] == mid["misses"]          # second sweep: all hits
    assert after["hits"] > mid["hits"]
    key = next(k for k in cache._plans)
    assert any(SANDY_BRIDGE in p._traces for p in cache._plans.values())
    assert len(pts) == 2
    del key


def test_cache_distinguishes_closures_over_different_constants():
    """Two lambdas with the same name but different closed-over constants
    must produce different cache keys (sweep reorderings pass these)."""
    from repro.reorder import cache_block

    a = rmat_matrix(256, seed=12)
    mk = [lambda c, k=k: cache_block(c, rows_per_block=k) for k in (4, 8)]
    cache = plan.PlanCache()
    p4 = cache.get_or_compile(a, reorder=mk[0], predictor="none")
    p8 = cache.get_or_compile(a, reorder=mk[1], predictor="none")
    assert cache.misses == 2 and p4 is not p8   # distinct keys, no collision
    assert p4.reordering.params != p8.reordering.params


def test_fingerprint_memoized_per_object():
    a = rmat_matrix(256, seed=13)
    from repro.plan import fingerprint as fpm

    fp1 = plan.matrix_fingerprint(a)
    assert fpm._FP_MEMO[id(a)][1] == fp1
    assert plan.matrix_fingerprint(a) == fp1      # served from the memo


def test_dropped_container_frees_fingerprint_memo_entry():
    """The memo holds containers by weak reference: dropping the last
    strong reference must evict the entry, or long-running serve fleets
    leak one entry per matrix ever fingerprinted (and id() reuse could
    then serve a *stale* digest for a new object at the same address)."""
    import gc

    from repro.core.delta import EdgeDelta
    from repro.plan import delta_fingerprint
    from repro.plan import fingerprint as fpm

    a = rmat_matrix(256, seed=17)
    plan.matrix_fingerprint(a)
    key = id(a)
    assert key in fpm._FP_MEMO
    del a
    gc.collect()
    assert key not in fpm._FP_MEMO

    d = EdgeDelta.from_updates(rmat_matrix(64, seed=3),
                               inserts=[(0, 1, 2.0)])
    delta_fingerprint(d)
    dkey = id(d)
    assert dkey in fpm._DELTA_MEMO
    del d
    gc.collect()
    assert dkey not in fpm._DELTA_MEMO


def test_fingerprint_memo_capped():
    """Even without collection pressure the memo cannot grow without
    bound: the FIFO backstop holds it at `_MEMO_CAP` entries."""
    from repro.plan import fingerprint as fpm

    keep = [rmat_matrix(16, seed=s) for s in range(8)]
    for m in keep:
        plan.matrix_fingerprint(m)
    assert len(fpm._FP_MEMO) <= fpm._MEMO_CAP


def test_execute_many_without_retained_csr_raises_clearly():
    from repro.distributed import row_mesh

    csr = rmat_matrix(128, seed=14)
    p = plan.compile(csr, mesh=row_mesh(), reorder="none",
                     predictor="none", keep_csr=False)
    with pytest.raises(ValueError, match="keep_csr"):
        p.execute_many(jnp.ones((2, 128), jnp.float32))


def test_predictor_none_with_auto_reorder_does_no_candidate_work(monkeypatch):
    calls = {}
    from repro import reorder as _reorder

    orig = _reorder.STRATEGIES["rcm"]

    def counting(csr):
        calls["rcm"] = calls.get("rcm", 0) + 1
        return orig(csr)

    monkeypatch.setitem(_reorder.STRATEGIES, "rcm", counting)
    p = plan.compile(rmat_matrix(256, seed=15), predictor="none")
    assert calls == {} and p.chosen == "none" and p.reordering is None


# ---------------------------------------------------------------------------
# degenerate geometries (nnz=0, single row, in-place mutation)
# ---------------------------------------------------------------------------

def _empty_csr(n=8):
    z = np.array([], dtype=np.int64)
    return CSR.from_coo(z, z, np.array([], dtype=np.float32), n, n)


def test_empty_matrix_plan_executes_to_zeros():
    """nnz=0 regression: the auto-chosen format (DIA with zero diagonals)
    used to crash the Pallas grid with a zero-size scalar-prefetch
    operand."""
    m = _empty_csr(8)
    x = jnp.ones((8,), jnp.float32)
    p = plan.compile(m)
    np.testing.assert_array_equal(np.asarray(p.execute(x)), np.zeros(8))
    np.testing.assert_array_equal(np.asarray(spmv(m, x)), np.zeros(8))
    # every forced format survives nnz=0 too
    for fmt in ("dia", "bell", "ell", "csr"):
        pf = plan.compile(m, format=fmt, reorder="none", predictor="none")
        np.testing.assert_array_equal(np.asarray(pf.execute(x)), np.zeros(8))


def test_empty_matrix_semiring_plan_yields_identity():
    m = _empty_csr(8)
    p = plan.compile(m, semiring="min_plus")
    y = np.asarray(p.execute(jnp.ones((8,), jnp.float32)))
    assert np.isinf(y).all()                     # min-plus ⊕-identity


def test_single_row_matrix_plan_and_spmv():
    m = CSR.from_coo([0, 0], [0, 2], [1.0, 2.0], 1, 3)
    x = jnp.asarray([1.0, 10.0, 100.0], jnp.float32)
    p = plan.compile(m)
    np.testing.assert_array_equal(np.asarray(p.execute(x)), [201.0])
    np.testing.assert_array_equal(np.asarray(spmv(m, x)), [201.0])


def test_invalidate_accepts_mutated_matrix():
    """In-place mutation regression: the per-object fingerprint memo used
    to keep serving the pre-mutation digest, so `invalidate` could never
    find (and the cache kept serving) the stale plan."""
    cache = plan.PlanCache()
    m = CSR(data=np.ones(2, np.float32), indices=np.array([0, 1], np.int32),
            indptr=np.array([0, 1, 2], np.int32), n_rows=2, n_cols=2)
    cache.get_or_compile(m, format="csr", reorder="none", predictor="none")
    fp_before = plan.matrix_fingerprint(m)
    np.asarray(m.data)[0] = 5.0                  # in-place: memo now stale
    assert plan.matrix_fingerprint(m) == fp_before   # the failure mode
    assert cache.invalidate(m) == 1              # drops the stale entry
    assert len(cache) == 0
    assert plan.matrix_fingerprint(m) != fp_before   # memo evicted, re-hashed
