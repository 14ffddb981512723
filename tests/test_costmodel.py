"""Learned plan compiler: fit determinism, shipped-artifact integrity,
replay agreement, the compiler's model fast path + oracle fallback, and
the plan cache's predictor/oracle compile-counter split."""
import os

import numpy as np
import pytest

from repro import plan
from repro.core.generators import banded_matrix, rmat_matrix
from repro.plan import costmodel as cm
from repro.plan.serial import load_model, save_model

CORPUS = os.path.join(os.path.dirname(cm.__file__), "_data",
                      "costmodel_corpus.json")


@pytest.fixture(scope="module")
def corpus():
    return cm.load_corpus(CORPUS)


@pytest.fixture(scope="module")
def shipped():
    model, step = load_model(cm.DEFAULT_MODEL_DIR)
    assert step == 0
    return model


# ---------------------------------------------------------------------------
# features
# ---------------------------------------------------------------------------

def test_features_width_and_determinism():
    from repro.core import structure

    rep = structure.analyze(rmat_matrix(256, seed=1))
    f1 = cm.features_for(rep, threads=4)
    f2 = cm.features_for(rep, threads=4)
    assert f1.shape == (len(cm.FEATURE_NAMES),)
    assert np.array_equal(f1, f2) and np.isfinite(f1).all()
    # the thread axis must actually reach the model
    f8 = cm.features_for(rep, threads=8)
    assert not np.array_equal(f1, f8)


def test_geometry_reaches_features():
    from repro.core import structure

    rep = structure.analyze(rmat_matrix(256, seed=1))
    default = cm.features_for(rep, threads=2)
    scaled = cm.features_for(rep, threads=2, l2_bytes=16 * 1024,
                             llc_bytes=64 * 1024)
    assert not np.array_equal(default, scaled)


# ---------------------------------------------------------------------------
# fit determinism + shipped-artifact integrity (what CI re-checks)
# ---------------------------------------------------------------------------

KIND_FEATURES = [cm.FEATURE_NAMES.index(k) for k in
                 ("kind_banded", "kind_blocked", "kind_unstructured")]


@pytest.mark.parametrize("kind", cm.LABEL_KINDS)
def test_structure_kind_matches_corpus_rows(corpus, kind):
    """The shipped model was fitted on stored feature rows: the structure
    analysis must still classify every label matrix (each size, seed and
    reordering in the corpus) as its row's `kind_*` one-hot says."""
    from repro.core import structure
    from repro.reorder import STRATEGIES

    stored = {}
    for r in corpus:
        if r.kind == kind:
            oh = tuple(r.features[i] for i in KIND_FEATURES)
            stored.setdefault((r.log2n, r.seed, r.reorder), set()).add(oh)
    assert {n for n, _, _ in stored} == {8, 9, 10}
    for (log2n, seed, reorder), onehots in sorted(stored.items()):
        csr = cm.label_matrix(kind, 2 ** log2n, seed)
        if reorder != "none":
            csr = STRATEGIES[reorder](csr).apply(csr)
        feats = cm.features_for(structure.analyze(csr))
        assert onehots == {tuple(feats[i] for i in KIND_FEATURES)}, \
            (log2n, seed, reorder)


def test_fit_is_deterministic(corpus):
    sub = corpus[:120]
    cfg = {"n_trees": 12}
    a = cm.fit(sub, config=cfg)
    b = cm.fit(sub, config=cfg)
    assert cm.model_bytes(a) == cm.model_bytes(b)


def test_refit_matches_shipped_artifact(corpus, shipped):
    """The shipped model is exactly `fit(checked-in corpus)` -- anyone can
    regenerate it byte-for-byte with `python -m repro.plan.costmodel
    --fit`."""
    assert shipped.meta["corpus_digest"] == cm.corpus_digest(corpus)
    refit = cm.fit(corpus, config=shipped.config)
    assert cm.model_bytes(refit) == cm.model_bytes(shipped)


def test_shipped_agreement_floor(corpus, shipped):
    """Acceptance: the model picks the replay oracle's winner in >=90% of
    corpus cells (grouped per (kind, size, seed, geometry, threads))."""
    ev = cm.evaluate(shipped, corpus)
    assert ev["n_groups"] >= 300
    assert ev["agreement"] >= 0.90, ev
    assert ev["r2"] >= 0.95


def test_model_checkpoint_roundtrip_byte_exact(tmp_path, corpus):
    """float64 thresholds/leaf values survive the checkpoint (raw-byte
    leaves dodge the jnp.asarray float32 truncation)."""
    m = cm.fit(corpus[:120], config={"n_trees": 12})
    d = str(tmp_path / "model")
    save_model(m, d, step=2)
    m2, step = load_model(d)
    assert step == 2
    assert cm.model_bytes(m2) == cm.model_bytes(m)


# ---------------------------------------------------------------------------
# selection rule + evaluation harness
# ---------------------------------------------------------------------------

def test_pick_winner_margin_rule():
    from repro.plan.compiler import REORDER_MARGIN

    assert cm.pick_winner({"none": 1.0, "rcm": 2.0}) == "rcm"
    # inside the transport margin the identity order wins
    within = 1.0 + REORDER_MARGIN / 2
    assert cm.pick_winner({"none": 1.0, "rcm": within}) == "none"
    assert cm.pick_winner({"none": 2.0, "rcm": 1.0}) == "none"


# ---------------------------------------------------------------------------
# the compiler's model fast path
# ---------------------------------------------------------------------------

def _scrambled_banded(n=512, seed=1):
    from repro.reorder import Reordering

    base = banded_matrix(n, max(8, n // 32), seed=seed)
    perm = np.random.default_rng(0).permutation(n)
    return Reordering(row_perm=perm, col_perm=perm).apply(base)


def _scaled_spec():
    # the corpus's 'scaled' geometry: caches small enough that the
    # recovered band actually matters (at machine defaults the whole
    # working set fits in LLC and 'none' wins everywhere)
    from repro.parallel import ParallelSpec

    return ParallelSpec(l2_bytes=16 * 1024, llc_bytes=64 * 1024)


def test_compile_auto_scores_with_model(shipped):
    csr = _scrambled_banded()
    p = plan.compile(csr, reorder="auto", predictor="auto", threads=4,
                     parallel_spec=_scaled_spec())
    assert p.compile_stats["scoring"] == "model"
    assert set(p.predicted) == {"none", "rcm"}
    assert all(v["predictor"] == "model" and v["gflops"] > 0
               for v in p.predicted.values())
    # RCM recovers the band here; the model must see that in the permuted
    # features and agree with the replay oracle's pick
    ref = plan.compile(csr, reorder="auto", predictor="replay", threads=4,
                       parallel_spec=_scaled_spec())
    assert p.chosen == ref.chosen == "rcm"


def test_model_and_oracle_plans_execute_identically(shipped):
    """Scoring mode picks the plan; it must never change what the chosen
    plan computes."""
    import jax.numpy as jnp

    csr = _scrambled_banded()
    x = jnp.asarray(np.random.default_rng(3).normal(size=512)
                    .astype(np.float32))
    pm = plan.compile(csr, reorder="auto", predictor="auto", threads=4,
                      parallel_spec=_scaled_spec())
    po = plan.compile(csr, reorder="auto", predictor="replay", threads=4,
                      parallel_spec=_scaled_spec())
    assert pm.chosen == po.chosen
    assert np.array_equal(np.asarray(pm.execute(x, interpret=True)),
                          np.asarray(po.execute(x, interpret=True)))


def test_predictor_model_falls_back_to_oracle_cleanly():
    prev = cm.set_default_model(None)
    try:
        p = plan.compile(rmat_matrix(512, seed=2), reorder="auto",
                         predictor="model", threads=4)
        assert p.compile_stats["model_fallback"] == 1.0
        assert p.compile_stats["scoring"] == "replay"   # nnz under cutoff
        assert all(v["predictor"] == "replay" for v in p.predicted.values())
    finally:
        cm.set_default_model(prev)


def test_predictor_auto_without_artifact_is_oracle():
    prev = cm.set_default_model(None)
    try:
        p = plan.compile(rmat_matrix(512, seed=2), reorder="auto",
                         predictor="auto", threads=4)
        assert "model_fallback" not in p.compile_stats    # auto, not forced
        assert p.compile_stats["scoring"] == "replay"
    finally:
        cm.set_default_model(prev)


def test_single_candidate_skips_scoring(shipped, monkeypatch):
    # reorder='none' enumerates one candidate: nothing to score
    p = plan.compile(rmat_matrix(256, seed=3), reorder="none",
                     predictor="auto")
    assert p.compile_stats["scoring"] == "none" and p.predicted == {}

    # dedup: when RCM returns a permutation equal to identity, the
    # candidate list collapses to one and scoring is skipped too
    from repro import reorder as _reorder

    def identity_rcm(csr):
        n = csr.n_rows
        perm = np.arange(n, dtype=np.int64)
        return _reorder.Reordering(row_perm=perm, col_perm=perm,
                                   strategy="rcm", params={}, stats={})

    def boom(self, X):
        raise AssertionError("deduped compile must not score")

    monkeypatch.setitem(_reorder.STRATEGIES, "rcm", identity_rcm)
    monkeypatch.setattr(type(shipped), "predict", boom)
    p2 = plan.compile(rmat_matrix(256, seed=3), reorder="auto",
                      predictor="auto", threads=4)
    assert p2.compile_stats["scoring"] == "none"
    assert p2.chosen == "none"


# ---------------------------------------------------------------------------
# plan cache counter split
# ---------------------------------------------------------------------------

def test_cache_splits_predictor_and_oracle_counters(shipped):
    cache = plan.PlanCache()
    a, b, c = (rmat_matrix(256, seed=s) for s in (21, 22, 23))
    cache.get_or_compile(a, reorder="auto", predictor="auto", threads=4)
    cache.get_or_compile(b, reorder="auto", predictor="replay", threads=4)
    cache.get_or_compile(c, reorder="none", predictor="none")
    s = cache.stats()
    assert s["compiles"] == 3
    assert s["predictor_compiles"] == 1 and s["oracle_compiles"] == 1
    assert 0.0 < s["predictor_compile_s"] <= s["compile_s"]
    assert 0.0 < s["oracle_compile_s"] <= s["compile_s"]
    # unscored compile lands in neither bucket
    assert s["predictor_compiles"] + s["oracle_compiles"] < s["compiles"]
    cache.clear()
    s2 = cache.stats()
    assert s2["predictor_compiles"] == s2["oracle_compiles"] == 0
    assert s2["predictor_compile_s"] == s2["oracle_compile_s"] == 0.0


def test_plan_cache_report_has_split_columns(shipped):
    from repro.telemetry.report import plan_cache_report

    cache = plan.PlanCache()
    before = cache.stats()
    cache.get_or_compile(rmat_matrix(256, seed=31), reorder="auto",
                         predictor="auto", threads=4)
    rep = plan_cache_report(cache.stats(), before=before)
    header, row = rep.splitlines()[1:3]
    cells = dict(zip(header.split(","), row.split(",")))
    assert cells["predictor_compiles"] == "1"
    assert cells["oracle_compiles"] == "0"
    assert float(cells["predictor_compile_s"]) > 0.0


# ---------------------------------------------------------------------------
# corpus I/O
# ---------------------------------------------------------------------------

def test_corpus_roundtrip(tmp_path, corpus):
    path = str(tmp_path / "corpus.json")
    cm.save_corpus(corpus[:10], path)
    back = cm.load_corpus(path)
    assert back == cm.sort_rows(corpus[:10])
    assert cm.corpus_digest(back) == cm.corpus_digest(corpus[:10])


def test_label_cell_replays_compiler_prediction():
    """A label row's gflops must equal what `predictor='replay'` scores
    for the same candidate -- the corpus labels ARE the oracle."""
    pt = cm.run_label_cell("banded", 8, "none", 4, spec_label="default")
    from repro.core.cache_model import SANDY_BRIDGE
    from repro.plan.compiler import _predict

    csr = cm.label_matrix("banded", 2 ** 8, 0)
    from repro.parallel import ParallelSpec

    ref = _predict(csr, 4, SANDY_BRIDGE, ParallelSpec(), "replay")
    assert pt.gflops == pytest.approx(ref["gflops"], rel=1e-12)
