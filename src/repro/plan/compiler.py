"""`compile` — turn a matrix into a frozen `SpmvPlan`.

The slow half of the compile-once split.  One call runs the whole
decision chain the per-call stack used to repeat on every multiply:

  fingerprint -> candidate reorderings -> predicted contended-LLC
  throughput (per candidate) -> winning reordering ->
  structure.analyze -> format -> conversion -> pre-padded kernel
  layout -> SpmvPlan.

Candidates are (format, reordering) pairs, enumerated in sorted name
order so plan choice is deterministic across runs.  Each reordering
candidate's *permuted access stream* is scored by the same models the
telemetry/parallel subsystems report with, and its format is read off
its permuted structure (DIA for recovered bands, BELL for block density,
HYB/segmented-CSR for power-law nnz dispersion, CSR otherwise) — so what
the predictor scored is exactly the stream that format will exploit.
Forcing `format=` skips the O(nnz) structure analysis altogether.

Predictors (`predictor=`):

  * 'model'     the learned cost model (`plan.costmodel`): each
                candidate's permuted structure report is featurized and
                scored by the shipped gradient-boosted ensemble in
                microseconds — no trace replay.  Falls back to 'oracle'
                (recorded in `compile_stats`) when no model is loaded.
  * 'oracle'    the simulation-backed scorer the model was trained
                against: 'replay' when nnz <= REPLAY_NNZ_MAX, else
                'analytic'.
  * 'replay'    `repro.parallel.simulate_parallel` — per-thread trace
                replay through private caches + the shared contended LLC,
                scored by `ParallelMetrics.gflops_est()`.  Exact but
                Python-speed; right for small/medium matrices.
  * 'analytic'  `core.cache_model.analytic_metrics(..., threads=)` — the
                Che-approximation model (with its shared-LLC thread
                scaling), scored by `CacheMetrics.gflops`.  O(distinct
                line counts); right for the 2^26 regime.
  * 'auto'      'model' when a pretrained model ships in-repo
                (`costmodel.default_model()`), else 'oracle' — the
                default: plan-cache misses on the serving path score in
                microseconds instead of seconds.
  * 'none'      no scoring: keeps the single given candidate (used by
                sweep harnesses that pin the reordering themselves);
                with reorder='auto' it degenerates to the identity
                ordering — no candidate work is done at all.

`compile_stats['scoring']` records the *resolved* mode ('model',
'replay', 'analytic', or 'none'), which is what `PlanCache` buckets its
predictor-vs-oracle compile counters by.  Its `*_s` keys are the seconds
of the compile's phase spans (`plan.reorder`, `plan.analyze`,
`plan.predict`, `plan.convert`, `plan.prepare`, inside `plan.compile`).
"""
from __future__ import annotations

from typing import Dict, Optional

from repro.core import structure
from repro.core.cache_model import SANDY_BRIDGE, MachineModel
from repro.core.formats import BELL, CSR, DIA, ELL, HYB
from repro.kernels import _layout as kl
from repro.spans import span, spanned

from .fingerprint import matrix_fingerprint
from .plan import SpmvPlan

# 'auto' predictor switches from trace replay to the analytic model above
# this nnz (replay is Python-speed: ~5 trace entries per nonzero per sweep).
REPLAY_NNZ_MAX = 16384

# A reordered candidate must beat the identity ordering by this fraction of
# predicted throughput to win: executing under a reordering pays an x-gather
# and y-scatter per multiply that the stream-level predictors do not model,
# so a sub-margin "win" is a loss in practice.
REORDER_MARGIN = 0.02


# Power-law detection for the nnz-balanced formats: above this nnz/row
# coefficient of variation an unstructured matrix routes to the hybrid
# row split (R-MAT sits at 1.7-3.2 across 2^8..2^12; uniform random at
# ~0.37, FD at 0.0).  Between SEG_MIN_CV and HYB_MIN_CV, a multithreaded
# plan takes the segmented (merge) CSR layout: rows are dispersed enough
# that row partitions imbalance but not enough to justify a row split.
HYB_MIN_CV = 1.0
SEG_MIN_CV = 0.5

# Semiring plans need absorbing padding, which the dense-footprint
# formats (DIA bands, BELL tiles) cannot express -- see graph.semiring.
SEMIRING_FORMATS = ("csr", "csr-seg", "ell", "hyb")

# Most diagonals a chosen DIA band may hold: the analysis counts the
# sampled rows' offsets, `convert_chosen` the whole matrix's.
DIA_MAX_DIAGS = 64


def choose_format(report, threads: int = 1,
                  semiring_safe: bool = False) -> str:
    """Format name for a structure report (the dispatch rule that used to
    live inline in `core.spmv.auto_format`).

    `threads` biases unstructured dispersion toward the nnz-balanced
    segmented layout (row partitions imbalance at scale);
    `semiring_safe` restricts the choice to absorbing-pad formats
    (`SEMIRING_FORMATS`), with ELL replacing the dense-footprint picks.
    """
    if not semiring_safe:
        if (report.kind == "banded"
                and report.n_distinct_offsets <= DIA_MAX_DIAGS):
            return "dia"
        if report.kind == "blocked":
            return "bell"
    if report.kind == "unstructured":
        if report.row_nnz_cv >= HYB_MIN_CV:
            return "hyb"                # power-law: split the hub rows off
        if threads > 1 and report.row_nnz_cv >= SEG_MIN_CV:
            return "csr-seg"            # dispersed: balance by nonzeros
    return "ell" if semiring_safe else "csr"


def convert(csr: CSR, format_name: str, fill: float = 0.0):
    """Convert a CSR to the named storage format.  `fill` is the padding
    value for layouts that materialize padding slots (ELL, the HYB light
    partition): 0.0 for plus-times, the semiring's absorbing element
    otherwise.  'csr-seg' is a kernel layout over the CSR container, not
    a distinct storage format, so it converts to the CSR itself."""
    if format_name == "dia":
        return DIA.from_csr(csr)
    if format_name == "bell":
        return BELL.from_csr(csr)
    if format_name == "ell":
        return ELL.from_csr(csr, fill=fill)
    if format_name == "hyb":
        return HYB.from_csr(csr, fill=fill)
    if format_name in ("csr", "csr-seg"):
        return csr
    raise ValueError(f"unknown format {format_name!r}")


def convert_chosen(csr: CSR, format_name: str, fill: float = 0.0):
    """`convert` for a format `choose_format` picked: (format, container).

    The structure analysis samples rows, so a matrix it calls banded can
    hold diagonals in the rows it skipped.  A DIA pick whose whole matrix
    holds more than `DIA_MAX_DIAGS` diagonals keeps CSR, as a banded
    report over that count does, and no band is allocated."""
    if format_name == "dia":
        dia = DIA.from_csr(csr, max_diags=DIA_MAX_DIAGS)
        return ("dia", dia) if dia is not None else ("csr", csr)
    return format_name, convert(csr, format_name, fill=fill)


def _prepare(container, format_name: str, *, bn: int, bm: int,
             n_stripes: int, seg_len: int = 512, pad_value: float = 0.0):
    """Pre-padded kernel layout for the chosen container (plan-build time;
    `SpmvPlan.execute` replays it with zero matrix-side work)."""
    if format_name == "dia":
        return kl.prepare_dia(container, bn=bn)
    if format_name == "bell":
        return kl.prepare_bell(container)
    if format_name == "ell":
        return kl.prepare_ell(container, bm=bm, pad_value=pad_value)
    if format_name == "csr":
        return kl.prepare_csr(container, n_stripes=n_stripes, bm=bm,
                              pad_value=pad_value)
    if format_name == "csr-seg":
        return kl.prepare_csr_seg(container, seg_len=seg_len,
                                  pad_value=pad_value)
    if format_name == "hyb":
        return kl.prepare_hyb(container, seg_len=seg_len, bm=bm,
                              pad_value=pad_value)
    raise ValueError(f"unknown format {format_name!r}")


def _candidates(csr: CSR, reorder) -> Dict[str, object]:
    """label -> Reordering|None for the `reorder=` argument forms:
    'auto' (none + rcm), 'none'/None, a strategy name, a strategy
    callable, or a concrete Reordering."""
    from repro.reorder import STRATEGIES, Reordering

    if reorder is None or reorder == "none":
        return {"none": None}
    if reorder == "auto":
        return {"none": None, "rcm": STRATEGIES["rcm"](csr)}
    if isinstance(reorder, str):
        return {reorder: STRATEGIES[reorder](csr)}
    if isinstance(reorder, Reordering):
        return {reorder.strategy: reorder}
    if callable(reorder):
        r = reorder(csr)
        return {getattr(r, "strategy", getattr(reorder, "__name__", "custom")): r}
    raise TypeError(f"unsupported reorder argument: {reorder!r}")


def _predict(csr: CSR, threads: int, machine: MachineModel,
             parallel_spec, predictor: str) -> Dict:
    """Predicted contended-LLC throughput of one candidate's stream."""
    if predictor == "auto":
        predictor = "replay" if csr.nnz <= REPLAY_NNZ_MAX else "analytic"
    if predictor == "replay":
        from repro.core.partition import rowblock_balanced
        from repro.parallel import ParallelSpec, simulate_parallel

        spec = parallel_spec if parallel_spec is not None else ParallelSpec()
        part = rowblock_balanced(csr, threads)
        _, m = simulate_parallel(csr, part, machine, spec, sweeps=2)
        return {"predictor": "replay", "gflops": m.gflops_est(),
                "time_s": m.time_s, "dram_util": m.dram_util,
                "l2_mpki": m.l2_mpki_mean}
    if predictor == "analytic":
        from repro.core.cache_model import analytic_metrics

        m = analytic_metrics(csr, machine, threads=threads)
        return {"predictor": "analytic", "gflops": m.gflops,
                "l2_mpki": m.l2_miss_rate,
                "dram_util": m.dram_utilization}
    raise ValueError(f"unknown predictor {predictor!r}")


@spanned("plan.compile")
def compile(matrix: CSR, *,                       # noqa: A001 (plan.compile)
            threads: int = 1,
            mesh=None,
            partition=None,
            reorder="auto",
            machine: MachineModel = SANDY_BRIDGE,
            parallel_spec=None,
            predictor: str = "auto",
            format: Optional[str] = None,         # noqa: A002
            use_pallas: bool = True,
            interpret: Optional[bool] = None,
            semiring: str = "plus_times",
            bn: int = 512, bm: int = 128, n_stripes: int = 1,
            seg_len: int = 512,
            keep_csr: bool = True,
            sample_rows: Optional[int] = 65536) -> SpmvPlan:
    """Compile a CSR matrix into a frozen `SpmvPlan`.

    threads    target thread count the predictor scores contention at
    mesh       a device mesh: build a row-sharded plan (`shard_map` ELL
               path) over `partition` (default `rowblock_equal`)
    reorder    'auto' (predictor picks none-vs-RCM) | 'none'/None | a
               strategy name/callable | a concrete Reordering
    format     force a storage format
               ('dia'|'bell'|'ell'|'csr'|'csr-seg'|'hyb'); default reads
               it off each candidate's permuted structure -- power-law
               dispersion (row_nnz_cv) routes to the nnz-balanced 'hyb'
               and 'csr-seg' layouts, see `choose_format`
    seg_len    nonzeros per segment for the 'csr-seg'/'hyb' layouts
    semiring   name (or `Semiring`) of the (⊕, ⊗) pair the plan executes
               under ('plus_times' default).  Non-plus-times plans are
               restricted to the absorbing-pad formats
               (`SEMIRING_FORMATS`); the reordering/predictor machinery
               is semiring-independent (same access stream)
    keep_csr   retain the permuted CSR on the plan (needed for
               `execute_many`'s SpMM path and telemetry trace replay)
    """
    fp = matrix_fingerprint(matrix)
    stats: Dict[str, object] = {}   # timings + the resolved scoring mode

    sr = None
    if semiring != "plus_times":
        from repro.graph.semiring import SEMIRINGS, resolve
        sr = resolve(semiring)
        if SEMIRINGS.get(sr.name) is not sr:
            # plans store the semiring by NAME (it must survive
            # serialization and cache keys), so an unregistered instance
            # would compile fine and KeyError on the first execute
            raise ValueError(
                f"semiring {sr.name!r} is not registered in "
                "repro.graph.semiring.SEMIRINGS; plans resolve semirings "
                "by name, so add custom semirings to the registry first")
        if sr.name == "plus_times":
            sr = None
        semiring = sr.name if sr is not None else "plus_times"
    pad_value = sr.pad_value if sr is not None else 0.0
    if sr is not None:
        if mesh is not None:
            raise ValueError("sharded plans are plus-times only")
        if format is not None and format not in SEMIRING_FORMATS:
            raise ValueError(
                f"semiring {semiring!r} requires a format in "
                f"{SEMIRING_FORMATS} (dense-footprint {format!r} stores "
                "absent entries as 0.0, which is only absorbing under "
                "plus_times)")

    if predictor == "none" and reorder == "auto":
        # no scoring requested, so don't build candidates that could only
        # be chosen by a score: 'auto' degenerates to the identity order
        reorder = "none"

    # Resolve 'auto'/'model'/'oracle' to a concrete scorer up front so the
    # candidate loop below is mode-free and the cache can bucket compile
    # counters by what actually ran.
    model = None
    if predictor in ("auto", "model"):
        from .costmodel import default_model

        model = default_model()
        if model is None:
            if predictor == "model":
                stats["model_fallback"] = 1.0
            predictor = "oracle"
        else:
            predictor = "model"
    if predictor == "oracle":
        predictor = "replay" if matrix.nnz <= REPLAY_NNZ_MAX else "analytic"

    with span("plan.reorder", stats, "reorder_s"):
        cands = _candidates(matrix, reorder)
        permuted_by = {label: (r.apply(matrix) if r is not None else matrix)
                       for label, r in cands.items()}

    # Candidate enumeration: one (format, reordering) pair per reordering
    # candidate, the format read off that candidate's permuted structure
    # (forcing `format=` skips the O(nnz) analysis and pins the pair's
    # format).  The list is sorted by (format, reordering) name so the
    # enumeration -- and every tie-break below -- is deterministic across
    # runs and processes, keeping fingerprint-salted cache entries stable.
    fmt_by: Dict[str, str] = {}
    report_by: Dict[str, object] = {}
    with span("plan.analyze", stats if format is None else None,
              "analyze_s"):
        for label in sorted(cands):
            if format is not None:
                fmt_by[label], report_by[label] = format, None
            else:
                rep = structure.analyze(permuted_by[label],
                                        sample_rows=sample_rows)
                report_by[label] = rep
                fmt_by[label] = choose_format(rep, threads=threads,
                                              semiring_safe=sr is not None)
    ordered = sorted(cands, key=lambda lab: (fmt_by[lab], lab))

    if len(ordered) > 1:
        # Drop candidates whose (permuted bytes, format) duplicates an
        # earlier one -- RCM on an already-banded matrix returns the
        # identity permutation, and scoring it would replay the exact
        # stream 'none' already covers.  'none' is preferred as survivor
        # (no x-gather/y-scatter at execute time); a compile this leaves
        # with one candidate skips scoring entirely below.
        pref = [lab for lab in ("none",) if lab in cands] + \
            [lab for lab in ordered if lab != "none"]
        seen: Dict[object, str] = {}
        for label in pref:
            sig = (matrix_fingerprint(permuted_by[label]), fmt_by[label])
            seen.setdefault(sig, label)
        keep = set(seen.values())
        ordered = [lab for lab in ordered if lab in keep]

    with span("plan.predict", stats, "predict_s"):
        predicted: Dict[str, Dict] = {}
        if predictor == "none" or len(ordered) == 1:
            chosen = ordered[0]
            stats["scoring"] = "none"
        else:
            if predictor == "model":
                import numpy as _np

                from .costmodel import features_for

                l2b = getattr(parallel_spec, "l2_bytes", None)
                llcb = getattr(parallel_spec, "llc_bytes", None)
                feats = []
                for label in ordered:
                    rep = report_by[label]
                    if rep is None:
                        # format was forced, so the loop above skipped the
                        # analysis -- the model still needs features
                        rep = structure.analyze(permuted_by[label],
                                                sample_rows=sample_rows)
                        report_by[label] = rep
                    feats.append(features_for(rep, threads, l2_bytes=l2b,
                                              llc_bytes=llcb, machine=machine))
                scores = model.predict(_np.stack(feats))
                for label, yhat in zip(ordered, scores):
                    predicted[label] = {"predictor": "model",
                                        "gflops": float(2.0 ** yhat)}
            else:
                for label in ordered:
                    predicted[label] = _predict(permuted_by[label], threads,
                                                machine, parallel_spec,
                                                predictor)
            chosen = ordered[0]
            for label in ordered[1:]:       # strict >: ties keep sorted order
                if predicted[label]["gflops"] > predicted[chosen]["gflops"]:
                    chosen = label
            if chosen != "none" and "none" in predicted:
                # reordered winners must clear the transport margin
                bar = predicted["none"]["gflops"] * (1.0 + REORDER_MARGIN)
                if predicted[chosen]["gflops"] <= bar:
                    chosen = "none"
            stats["scoring"] = predictor

    reordering, permuted = cands[chosen], permuted_by[chosen]
    report, format_name = report_by[chosen], fmt_by[chosen]

    if mesh is not None:
        return _compile_sharded(fp, permuted, reordering, report, mesh,
                                partition, bm=bm, threads=threads,
                                predicted=predicted, chosen=chosen,
                                interpret=interpret, stats=stats,
                                keep_csr=keep_csr)

    with span("plan.convert", stats, "convert_s"):
        if format is None:
            format_name, container = convert_chosen(permuted, format_name,
                                                    fill=pad_value)
        else:
            container = convert(permuted, format_name, fill=pad_value)

    with span("plan.prepare", stats, "prepare_s"):
        prep = _prepare(container, format_name, bn=bn, bm=bm,
                        n_stripes=n_stripes, seg_len=seg_len,
                        pad_value=pad_value) if use_pallas else None

    return SpmvPlan(
        fingerprint=fp, format_name=format_name, container=container,
        prep=prep, reordering=reordering, report=report,
        csr=permuted if keep_csr else None, threads=threads,
        use_pallas=use_pallas, interpret=interpret, semiring=semiring,
        predicted=predicted, chosen=chosen, compile_stats=stats)


def _compile_sharded(fp, permuted, reordering, report, mesh, partition, *,
                     bm, threads, predicted, chosen, interpret, stats,
                     keep_csr) -> SpmvPlan:
    """Row-sharded plan: `prepare_ell_shards` is the plan-build step, the
    `shard_map` Pallas ELL kernel is the executor."""
    from repro.distributed.spmv import default_row_partition

    with span("plan.prepare", stats, "prepare_s"):
        if partition is None:
            partition = default_row_partition(permuted, mesh)
        prep = kl.prepare_ell_shards(permuted, partition, bm=bm)
    return SpmvPlan(
        fingerprint=fp, format_name="ell-sharded", container=None,
        prep=prep, reordering=reordering, report=report,
        csr=permuted if keep_csr else None, threads=threads,
        use_pallas=True, interpret=interpret, predicted=predicted,
        chosen=chosen, compile_stats=stats, mesh=mesh)


def plan_for_container(matrix, interpret: Optional[bool] = None) -> SpmvPlan:
    """Minimal plan for an ALREADY-CONVERTED container (no analysis, no
    reordering decision — the caller chose the format): just the one-time
    kernel layout prep.  This is what `core.spmv.spmv` caches so repeated
    per-call dispatch stops re-padding the matrix."""
    names = {DIA: "dia", BELL: "bell", ELL: "ell", CSR: "csr", HYB: "hyb"}
    format_name = names[type(matrix)]
    prep = _prepare(matrix, format_name, bn=512, bm=128, n_stripes=1)
    return SpmvPlan(
        fingerprint=matrix_fingerprint(matrix), format_name=format_name,
        container=matrix, prep=prep,
        csr=matrix if isinstance(matrix, CSR) else None,
        interpret=interpret, chosen="container")
