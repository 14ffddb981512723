"""Amortization benchmark: cold plan compile vs cached execute vs SpMM.

The compile-once claim, measured: for FD and R-MAT at the paper-regime
2^12 rows (2^10 under --smoke / --fast), time

  cold     `plan.compile` + first execute (analysis, predictor scoring,
           format conversion, layout padding, kernel warm-up);
  warm     median cached `SpmvPlan.execute` over `REPEATS` multiplies
           (zero matrix-side work per call);
  spmm     `execute_many` on a REPEATS-vector batch, per vector (the
           batched jnp SpMM path).

`warm_frac` = warm / cold must stay < 0.20 for the amortized path to be
doing its job (asserted here so `run.py --smoke` fails on regression).

Second section, the learned-compiler claim: on a scrambled-banded
matrix at the paper-regime 2^12 scaled-geometry cell, candidate scoring
through the shipped cost model must be >= 50x faster than the replay
oracle it replaces (`SCORING_SPEEDUP_MIN`, on `compile_stats.predict_s`
-- the component the model eliminates; reordering/analysis/conversion
are shared by both modes, so the end-to-end cold-compile ratio is
reported as its own column, not asserted).  Both modes must also pick
the same reordering here, or the speedup is bought with a wrong plan.

Invoked by `benchmarks.run` (section name: plan) or directly:

    PYTHONPATH=src python -m benchmarks.plan_bench [--fast] [--smoke]
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import plan
from repro.core.generators import fd_matrix, rmat_matrix

from . import common

REPEATS = 8          # acceptance: warm < 20% of cold over >= 8 multiplies
WARM_FRAC_MAX = 0.20
SCORING_SPEEDUP_MIN = 50.0   # model vs replay scoring at the 2^12 cell
SCORING_SPEEDUP_MIN_SMOKE = 10.0   # 2^10: replay is ~4x cheaper there


def _log2n() -> int:
    if common.SMOKE or common.EMPIRICAL_MAX_LOG2 <= 16:
        return 10
    return 12


def _time(fn) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def main() -> None:
    log2n = _log2n()
    n = 2 ** log2n
    rows = []
    for kind, gen in (("fd", fd_matrix), ("rmat", rmat_matrix)):
        csr = gen(n, seed=0)
        x = jnp.asarray(np.random.default_rng(0).normal(size=n)
                        .astype(np.float32))
        cache = plan.PlanCache()
        opts = dict(reorder="auto", predictor="analytic", threads=8)

        cold = _time(lambda: cache.get_or_compile(csr, **opts)
                     .execute(x))
        p = cache.get_or_compile(csr, **opts)        # cache hit

        warm = float(np.median([
            _time(lambda: p.execute(x))
            for _ in range(REPEATS)]))

        X = jnp.stack([x] * REPEATS)
        p.execute_many(X)                            # build + jit once
        spmm = _time(lambda: p.execute_many(X)) / REPEATS

        frac = warm / max(cold, 1e-12)
        rows.append([kind, log2n, csr.nnz, p.format_name, p.chosen,
                     cold * 1e3, warm * 1e3, frac, spmm * 1e3,
                     cold / max(warm, 1e-12)])
        assert frac < WARM_FRAC_MAX, (
            f"{kind} 2^{log2n}: warm per-call cost is {frac:.1%} of cold "
            f"(must be < {WARM_FRAC_MAX:.0%}) — the amortized path regressed")

    common.emit(rows,
                ["kind", "log2n", "nnz", "format", "reorder", "cold_ms",
                 "warm_ms", "warm_frac", "spmm_per_vec_ms", "amortization_x"],
                f"plan amortization: cold compile vs cached execute "
                f"(2^{log2n}, {REPEATS} repeats)")
    _scoring_section(log2n)


def _scoring_section(log2n: int) -> None:
    """Learned cost model vs replay oracle on the hot compile path."""
    from repro.parallel import ParallelSpec
    from repro.plan.costmodel import default_model
    from repro.reorder import Reordering

    if default_model() is None:
        print("# learned scoring: no model artifact shipped, skipping")
        return

    n = 2 ** log2n
    from repro.core.generators import banded_matrix

    bandm = banded_matrix(n, max(8, n // 32), seed=0)
    perm = np.random.default_rng(0).permutation(n)
    csr = Reordering(row_perm=perm, col_perm=perm).apply(bandm)
    spec = ParallelSpec(l2_bytes=16 * 1024, llc_bytes=64 * 1024)

    timed = {}
    for pred in ("auto", "replay"):
        t0 = time.perf_counter()
        p = plan.compile(csr, reorder="auto", predictor=pred, threads=8,
                         parallel_spec=spec)
        timed[pred] = (time.perf_counter() - t0, p)
    cold_m, pm = timed["auto"]
    cold_o, po = timed["replay"]
    assert pm.compile_stats["scoring"] == "model"
    score_m = pm.compile_stats["predict_s"]
    score_o = po.compile_stats["predict_s"]
    speedup = score_o / max(score_m, 1e-12)
    floor = SCORING_SPEEDUP_MIN_SMOKE if log2n < 12 else SCORING_SPEEDUP_MIN
    common.emit(
        [["scrambled", log2n, csr.nnz, pm.chosen, po.chosen,
          score_m * 1e3, score_o * 1e3, speedup,
          cold_m * 1e3, cold_o * 1e3, cold_o / max(cold_m, 1e-12)]],
        ["kind", "log2n", "nnz", "model_pick", "oracle_pick",
         "model_score_ms", "oracle_score_ms", "scoring_speedup_x",
         "model_cold_ms", "oracle_cold_ms", "cold_speedup_x"],
        f"learned scoring vs replay oracle (scaled LLC cell, 2^{log2n}, "
        f"threads=8)")
    assert pm.chosen == po.chosen, (
        f"model picked {pm.chosen!r} but the replay oracle picked "
        f"{po.chosen!r} on the scrambled-banded cell")
    assert speedup >= floor, (
        f"scoring speedup {speedup:.1f}x below the {floor:.0f}x floor at "
        f"2^{log2n} — the learned fast path regressed")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="2^10 rows")
    ap.add_argument("--smoke", action="store_true",
                    help="2^10 rows (benchmark smoke job)")
    args = ap.parse_args()
    if args.fast:
        common.EMPIRICAL_MAX_LOG2 = 14
    if args.smoke:
        common.SMOKE = True
    main()
