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
otherwise the CSR, segmented-CSR or HYB layout whose runner touches the
fewest device elements in XLA, see `choose_format`) — so what the
predictor scored is exactly the stream that format will exploit.
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
Its `xla_elements` key is the chosen layout's count of the device
elements its runner gathers and merges per multiply (see `xla_elements`),
and `xla_elements_by_format` holds every candidate's when the rule ranked
them.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from repro.core import structure
from repro.core.cache_model import SANDY_BRIDGE, MachineModel
from repro.core.formats import BELL, CSR, DIA, ELL, HYB, hyb_auto_threshold
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


# The layouts `choose_format` ranks for an unstructured matrix; ties in
# the ranking go to the earlier name.  Semiring plans add ELL.
UNSTRUCTURED_FORMATS = ("csr", "csr-seg", "hyb")

# Semiring plans need absorbing padding, which the dense-footprint
# formats (DIA bands, BELL tiles) cannot express -- see graph.semiring.
SEMIRING_FORMATS = ("csr", "csr-seg", "ell", "hyb")

# Most diagonals a chosen DIA band may hold: the analysis counts the
# sampled rows' offsets, `convert_chosen` the whole matrix's.
DIA_MAX_DIAGS = 64

# Lanes every gathered and merged array of the unstructured layouts pads
# to (the `pad_mult` of their `kernels._layout.prepare_*`).
LANES = 128


def _seg_elements(indptr: np.ndarray, lengths: np.ndarray,
                  seg_len: int) -> int:
    """Gathered slots plus merged partials of `prepare_csr_seg`'s
    row-major stream: S segments of `seg_len` slots, each with a rank
    window of the most distinct rows (the pad row included) that any one
    segment touches."""
    nnz = int(indptr[-1])
    seg = kl.round_up(max(int(seg_len), 1), LANES)
    n_segs = max(kl.ceil_div(nnz, seg), 1)
    lo = np.arange(n_segs, dtype=np.int64) * seg
    hi = np.minimum(lo + seg, nnz)
    nonempty = lengths > 0
    starts, ends = indptr[:-1][nonempty], indptr[1:][nonempty]
    # non-empty rows that start before a segment's end and end after its
    # start; a segment with padding ranks the pad row too
    rows = (np.searchsorted(starts, hi, side="left")
            - np.searchsorted(ends, lo, side="right"))
    rows = rows + (lo + seg > nnz)
    rwin = kl.round_up(int(rows.max()), LANES)
    return n_segs * seg + n_segs * rwin


def xla_elements(csr: CSR, formats, *, bm: int = 128, seg_len: int = 512,
                 n_stripes: int = 1) -> Dict[str, int]:
    """format -> device elements its runner touches in XLA per multiply,
    for each of `formats`: the x slots it gathers plus the partials its
    merge scatters, counted from the row lengths alone (the columns too
    for a striped CSR), with the knobs `_prepare` builds the layout with.

      csr      stripes x row blocks x the fullest cell, lane-padded; the
               stripe reduce is a dense sum and scatters nothing
      csr-seg  S x L slots plus S x rwin partials
      hyb      the light slab's rows x lane-padded width, plus the heavy
               stream's slots and partials, its rank window bounded by
               the segment length and the heavy rows (the stream is
               column-sorted, so its exact window needs the columns)
      ell      rows x the lane-padded widest row
    """
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    lengths = np.diff(indptr)
    n_rows = int(csr.n_rows)
    rows_pad = kl.round_up(max(n_rows, 1), bm)

    def count(format_name: str) -> int:
        if format_name == "csr":
            n_blocks = kl.ceil_div(n_rows, bm)
            if n_stripes == 1:
                bounds = indptr[np.minimum(np.arange(n_blocks + 1) * bm,
                                           n_rows)]
                per_cell = np.diff(bounds)
            else:
                stripe_w = kl.round_up(kl.ceil_div(csr.n_cols, n_stripes),
                                       LANES)
                cols = np.asarray(csr.indices, dtype=np.int64)
                blocks = np.repeat(np.arange(n_rows, dtype=np.int64) // bm,
                                   lengths)
                per_cell = np.bincount(cols // stripe_w * n_blocks + blocks,
                                       minlength=n_stripes * n_blocks)
            width = kl.round_up(max(int(per_cell.max(initial=0)), 1), LANES)
            return n_stripes * n_blocks * width
        if format_name == "csr-seg":
            return _seg_elements(indptr, lengths, seg_len)
        if format_name == "ell":
            return rows_pad * kl.round_up(max(int(lengths.max(initial=0)), 1),
                                          LANES)
        if format_name == "hyb":
            heavy = lengths > hyb_auto_threshold(lengths)
            light = kl.round_up(max(int(lengths[~heavy].max(initial=0)), 1),
                                LANES)
            heavy_nnz = int(lengths[heavy].sum())
            seg = kl.round_up(max(int(seg_len), 1), LANES)
            n_segs = max(kl.ceil_div(heavy_nnz, seg), 1)
            padded = n_segs * seg > heavy_nnz
            rwin = kl.round_up(min(seg, int(heavy.sum()) + padded), LANES)
            return rows_pad * light + n_segs * (seg + rwin)
        raise ValueError(f"no XLA element count for format {format_name!r}")

    return {f: count(f) for f in formats}


def _rank(report, csr: CSR, semiring_safe: bool, *, bm: int, seg_len: int,
          n_stripes: int):
    """(format, {candidate: xla_elements}) for a structure report: DIA and
    BELL for the structures they store densely, else the candidate whose
    runner touches the fewest XLA elements (the counts are None when no
    ranking ran)."""
    if not semiring_safe:
        if (report.kind == "banded"
                and report.n_distinct_offsets <= DIA_MAX_DIAGS):
            return "dia", None
        if report.kind == "blocked":
            return "bell", None
    if report.kind != "unstructured":
        return ("ell" if semiring_safe else "csr"), None
    names = UNSTRUCTURED_FORMATS + (("ell",) if semiring_safe else ())
    counts = xla_elements(csr, names, bm=bm, seg_len=seg_len,
                          n_stripes=n_stripes)
    return min(counts, key=counts.get), counts


def choose_format(report, csr: CSR, semiring_safe: bool = False, *,
                  bm: int = 128, seg_len: int = 512,
                  n_stripes: int = 1) -> str:
    """Format name for a matrix and its structure report (the dispatch
    rule behind `compile` and `auto_format`).

    Banded and blocked structure take DIA and BELL.  An unstructured
    matrix takes the layout among `UNSTRUCTURED_FORMATS` whose runner
    touches the fewest device elements in XLA per multiply
    (`xla_elements`, counted from the row lengths with the layout knobs
    `bm`, `seg_len`, `n_stripes`).  `semiring_safe` restricts the choice
    to absorbing-pad formats (`SEMIRING_FORMATS`), with ELL ranked too
    and replacing the dense-footprint picks.
    """
    return _rank(report, csr, semiring_safe, bm=bm, seg_len=seg_len,
                 n_stripes=n_stripes)[0]


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


def auto_format(csr: CSR, report: structure.StructureReport | None = None,
                reordering=None):
    """Pick the TPU-friendly format for this matrix's structure.

    One-shot: the decision rule is `choose_format` and the conversion
    `convert_chosen` -- compile an `SpmvPlan` instead to also freeze the
    kernel layout, or `compile(csr, predictor='auto')` to let the
    learned cost model pick the reordering too.

    With `reordering` (a `repro.reorder.Reordering`), the permutation is
    applied first and the structure re-analyzed on the permuted matrix, so
    the format decision reflects the post-reorder structure -- an RCM'd
    scrambled-banded matrix becomes DIA-eligible again.  Pass the same
    reordering to `core.spmv.spmv` to multiply in the original row order.
    An unstructured matrix takes the layout whose runner touches the
    fewest device elements, exactly as plan compilation would; for the
    segmented layout that container is the CSR itself.
    """
    if reordering is not None:
        csr = reordering.apply(csr)
        report = None
    rep = report or structure.analyze(csr)
    return convert_chosen(csr, choose_format(rep, csr))[1]


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

    threads    target thread count the predictor scores reorderings at
    mesh       a device mesh: build a row-sharded plan (`shard_map` ELL
               path) over `partition` (default `rowblock_equal`)
    reorder    'auto' (predictor picks none-vs-RCM) | 'none'/None | a
               strategy name/callable | a concrete Reordering
    format     force a storage format
               ('dia'|'bell'|'ell'|'csr'|'csr-seg'|'hyb'); default reads
               it off each candidate's permuted structure and row
               lengths, see `choose_format`
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
    counts_by: Dict[str, Optional[Dict[str, int]]] = {}
    with span("plan.analyze", stats if format is None else None,
              "analyze_s") as analyzed:
        for label in sorted(cands):
            if format is not None:
                fmt_by[label], report_by[label] = format, None
                counts_by[label] = None
            else:
                rep = structure.analyze(permuted_by[label],
                                        sample_rows=sample_rows)
                report_by[label] = rep
                fmt_by[label], counts_by[label] = _rank(
                    rep, permuted_by[label], sr is not None, bm=bm,
                    seg_len=seg_len, n_stripes=n_stripes)
        analyzed.set(format=",".join(fmt_by[lab] for lab in sorted(cands)))
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

    counts = counts_by[chosen]
    if counts is not None:
        stats["xla_elements_by_format"] = counts
    if counts is not None and format_name in counts:
        stats["xla_elements"] = counts[format_name]
    elif format_name in UNSTRUCTURED_FORMATS + ("ell",):
        stats["xla_elements"] = xla_elements(
            permuted, (format_name,), bm=bm, seg_len=seg_len,
            n_stripes=n_stripes)[format_name]

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
