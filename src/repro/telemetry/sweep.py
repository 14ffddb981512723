"""Geometry x mechanism x reordering x thread sweep harness.

Answers the paper's §V question quantitatively: replay the same SpMV
demand traces (FD and R-MAT, several sizes) through candidate hierarchies
-- baseline, victim cache, miss cache, stream buffers, combined -- and
collect topdown metrics for each, so "does a victim cache + stream
buffers close the FD vs R-MAT gap?" becomes a table instead of an
argument.  The reorder axis (`reorderings=` / `reorder_sweep`) crosses
the same grid with the software permutations from `repro.reorder`.

Threads appear in two forms:

  * `run_sweep(threads_list=...)` keeps the analytic shortcut (paper
    finding F2: serial and parallel miss rates match): one
    representative core replays its row slice against an L3 share
    divided by the socket's cores.
  * `scaling_sweep` (the thread axis proper, 1-32) drives
    `repro.parallel`: every thread replays its `RowPartition` slice,
    private L1/L2 per thread, one genuinely shared, contended LLC per
    socket plus a DRAM bandwidth model -- this is what speedup curves
    and `report.scaling_report` are built from.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.cache_model import SANDY_BRIDGE, MachineModel
from repro.core.formats import CSR
from repro.core.generators import fd_matrix, rmat_matrix

from .events import EventCounters
from .hierarchy import Hierarchy, HierarchySpec, spmv_address_trace
from .topdown import TopdownSummary, topdown_summary

# The paper's §V candidate mechanisms, by report label.  Entry sizes follow
# the related SimpleScalar study (small fully-associative structures).
MECHANISMS: Dict[str, HierarchySpec] = {
    "baseline": HierarchySpec(),
    "victim-cache": HierarchySpec(victim_entries=64),
    "miss-cache": HierarchySpec(miss_entries=64),
    "stream-buffers": HierarchySpec(stream_buffers=8, stream_depth=4),
    "combined": HierarchySpec(victim_entries=64, stream_buffers=8,
                              stream_depth=4),
}


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One (matrix, reorder, mechanism, geometry) cell of a sweep."""

    kind: str                 # 'fd' | 'rmat'
    log2n: int
    nnz: int
    threads: int
    mechanism: str
    spec: HierarchySpec
    counters: EventCounters
    summary: TopdownSummary
    reorder: str = "none"     # reordering strategy applied before tracing

    def row(self) -> List:
        return ([self.kind, self.log2n, self.nnz, self.threads,
                 self.reorder, self.mechanism]
                + [getattr(self.summary, f) for f in TopdownSummary.FIELDS]
                + [self.summary.bound()])

    @staticmethod
    def header() -> List[str]:
        return (["kind", "log2n", "nnz", "threads", "reorder", "mechanism"]
                + list(TopdownSummary.FIELDS) + ["bound"])


def _matrix(kind: str, n: int, seed: int = 0) -> CSR:
    return fd_matrix(n, seed=seed) if kind == "fd" \
        else rmat_matrix(n, seed=seed)


# Sweep plans pin a permuted CSR plus a memoized full address trace each
# (several MB per 2^16 cell), so they get their own small cache rather
# than crowding `plan.DEFAULT_CACHE` (whose entries back the graph
# drivers' live traffic).  Lazily constructed to keep module import light.
_PLAN_CACHE = None


def sweep_plan_cache():
    global _PLAN_CACHE
    if _PLAN_CACHE is None:
        from repro.plan import PlanCache

        _PLAN_CACHE = PlanCache(max_plans=8)
    return _PLAN_CACHE


def _planned(base: CSR, strategy):
    """One cached plan per (matrix contents, reordering): the sweep's
    compile-once step.  The plan holds the permuted CSR and memoizes its
    address trace, so crossing the mechanism/thread/geometry axes (and
    re-running a sweep in the same process) re-analyzes and re-permutes
    nothing.  `strategy` is a `repro.reorder` callable or None."""
    return sweep_plan_cache().get_or_compile(
        base, reorder=strategy, predictor="none", format="csr",
        use_pallas=False, keep_csr=True)


def _thread_slice(trace_csr: CSR, threads: int) -> Tuple[CSR, int]:
    """Representative core's row slice (contiguous, like rowblock_equal)."""
    if threads <= 1:
        return trace_csr, trace_csr.nnz
    n = trace_csr.n_rows
    rows_per = -(-n // threads)
    indptr = np.asarray(trace_csr.indptr)
    lo_r, hi_r = 0, min(rows_per, n)   # core 0 (rows are permuted: typical)
    lo_p, hi_p = int(indptr[lo_r]), int(indptr[hi_r])
    sub = CSR(
        data=trace_csr.data[lo_p:hi_p],
        indices=trace_csr.indices[lo_p:hi_p],
        indptr=trace_csr.indptr[lo_r:hi_r + 1] - lo_p,
        n_rows=hi_r - lo_r, n_cols=trace_csr.n_cols,
    )
    return sub, sub.nnz


def run_point(csr: CSR, spec: HierarchySpec,
              machine: MachineModel = SANDY_BRIDGE,
              threads: int = 1, sweeps: int = 2,
              trace=None) -> EventCounters:
    """Replay one matrix through one hierarchy; returns warm-sweep counters.

    With threads > 1 the representative core's slice is replayed through a
    hierarchy whose L3 share is capacity / threads-on-socket.  `trace`
    (ndarray or list of line ids) overrides the matrix-derived trace so a
    prebuilt one can be shared across mechanisms.
    """
    if threads > 1:
        tps = min(threads, machine.cores_per_socket)
        spec = dataclasses.replace(
            spec, l3_bytes=(spec.l3_bytes or machine.l3_bytes) // tps)
    if trace is None:
        if threads > 1:
            csr, _ = _thread_slice(csr, threads)
        trace = spmv_address_trace(csr, machine)
    return spec.instantiate(machine).run_trace(trace, sweeps=sweeps)


# ---------------------------------------------------------------------------
# Per-cell execution (the unit `telemetry.runner` shards and checkpoints).
# Every cell function is a pure function of its arguments, so serial thin
# clients and worker processes produce bit-identical points -- the memos
# below are per-process accelerators, never semantic state.
# ---------------------------------------------------------------------------

# (kind, log2n, rlabel, strategy, threads, seed, machine) -> prepared
# replay inputs.  Sorted cell order keeps consecutive cells on the same
# plan, so a tiny cache suffices; entries hold a full trace list (MBs at
# 2^16), hence the small bound.
_TRACE_MEMO: Dict[Tuple, Tuple] = {}
_TRACE_MEMO_MAX = 3


def _cell_inputs(kind: str, log2n: int, rlabel: str, strategy, threads: int,
                 seed: int, machine: MachineModel):
    """(sub_csr, sub_nnz, full_nnz, trace_list) for one mech cell."""
    key = (kind, log2n, rlabel, strategy, threads, seed, machine)
    hit = _TRACE_MEMO.get(key)
    if hit is not None:
        return hit
    base = _matrix(kind, 2 ** log2n, seed=seed)
    p = _planned(base, strategy)
    full = p.csr
    if threads <= 1:
        sub, sub_nnz = full, full.nnz
        trace = p.address_trace(machine).tolist()
    else:
        sub, sub_nnz = _thread_slice(full, threads)
        trace = spmv_address_trace(sub, machine).tolist()
    if len(_TRACE_MEMO) >= _TRACE_MEMO_MAX:
        _TRACE_MEMO.pop(next(iter(_TRACE_MEMO)))
    out = (sub, sub_nnz, int(full.nnz), trace)
    _TRACE_MEMO[key] = out
    return out


def run_mech_cell(kind: str, log2n: int, rlabel: str, strategy,
                  threads: int, mech_label: str, spec: HierarchySpec,
                  machine: MachineModel = SANDY_BRIDGE,
                  sweeps: int = 2, seed: int = 0) -> SweepPoint:
    """One (matrix, reorder, thread, mechanism) cell of `run_sweep`."""
    sub, sub_nnz, full_nnz, trace = _cell_inputs(
        kind, log2n, rlabel, strategy, threads, seed, machine)
    c = run_point(sub, spec, machine, threads=threads, sweeps=sweeps,
                  trace=trace)
    return SweepPoint(
        kind=kind, log2n=log2n, nnz=full_nnz, threads=threads,
        mechanism=mech_label, spec=spec, counters=c, reorder=rlabel,
        summary=topdown_summary(c, machine, sub_nnz))


def run_sweep(log2ns: Sequence[int] = (12, 14, 16),
              kinds: Sequence[str] = ("fd", "rmat"),
              mechanisms: Optional[Dict[str, HierarchySpec]] = None,
              machine: MachineModel = SANDY_BRIDGE,
              threads_list: Sequence[int] = (1,),
              sweeps: int = 2, seed: int = 0,
              reorderings: Optional[Dict] = None,
              workers: int = 1,
              ckpt_dir: Optional[str] = None) -> List[SweepPoint]:
    """The full grid, in sorted canonical cell order.  Each (kind, size,
    reorder) cell is compiled ONCE into a cached `repro.plan` plan
    (permutation applied, trace memoized) and replayed across the
    mechanism/thread axes, so mechanism columns are exactly comparable
    and repeated sweeps in one process re-analyze nothing.

    `reorderings` maps a label to a `repro.reorder` strategy (callable
    CSR -> Reordering) or None for the unpermuted matrix; each strategy is
    applied to the generated matrix *before* slicing and tracing, making
    the sweep a before/after comparison between software reordering and
    the §V hardware mechanisms.

    This is a thin client of `telemetry.runner`: `workers` shards the
    cells across processes and `ckpt_dir` checkpoints completed cells
    (and resumes from them) -- results are bit-identical either way.
    """
    from . import runner

    mechanisms = mechanisms if mechanisms is not None else MECHANISMS
    reorderings = reorderings if reorderings is not None else {"none": None}
    cells = runner.mech_cells(log2ns=log2ns, kinds=kinds,
                              mechanisms=mechanisms,
                              threads_list=threads_list,
                              reorderings=reorderings)
    cfg = runner.SweepConfig(machine=machine, sweeps=sweeps, seed=seed,
                             mechanisms=dict(mechanisms),
                             reorderings=dict(reorderings))
    return runner.execute_cells(cells, cfg, workers=workers,
                                ckpt_dir=ckpt_dir)


def reorder_sweep(log2ns: Sequence[int] = (12,),
                  kinds: Sequence[str] = ("fd", "rmat"),
                  mechanisms: Optional[Dict[str, HierarchySpec]] = None,
                  reorderings: Optional[Dict] = None,
                  machine: MachineModel = SANDY_BRIDGE,
                  threads_list: Sequence[int] = (1,),
                  sweeps: int = 2, seed: int = 0) -> List[SweepPoint]:
    """Before/after sweep: every reordering strategy crossed with the §V
    mechanisms, so `report.reorder_gap_report` can state how much of the
    FD-vs-R-MAT miss-rate gap each permutation closes on its own and
    combined with the hardware fixes."""
    from repro.reorder import STRATEGIES

    if mechanisms is None:
        mechanisms = {"baseline": MECHANISMS["baseline"],
                      "stream-buffers": MECHANISMS["stream-buffers"]}
    if reorderings is None:
        reorderings = dict(STRATEGIES)
        reorderings["none"] = None       # skip the identity permutation work
    return run_sweep(log2ns=log2ns, kinds=kinds, mechanisms=mechanisms,
                     machine=machine, threads_list=threads_list,
                     sweeps=sweeps, seed=seed, reorderings=reorderings)


@dataclasses.dataclass(frozen=True)
class ScalingPoint:
    """One (matrix, reorder, thread-count) cell of a scaling sweep."""

    kind: str                 # 'fd' | 'rmat'
    log2n: int
    nnz: int
    threads: int
    reorder: str
    partition: str            # 'equal' | 'balanced'
    imbalance: float          # max/mean nnz over threads (1.0 = perfect)
    speedup: float            # time(1 thread) / time(threads), same cell
    efficiency: float         # speedup / threads
    metrics: object           # repro.parallel.ParallelMetrics

    def row(self) -> List:
        m = self.metrics
        fr = m.stages.fractions()
        return ([self.kind, self.log2n, self.nnz, self.reorder,
                 self.partition, self.threads, self.speedup, self.efficiency,
                 m.time_s * 1e6, self.imbalance, m.l2_mpki_mean,
                 m.l2_mpki_max, float(np.mean(m.llc_mpki)), m.dram_util,
                 m.pf_on_frac, m.stages.bound(), fr["retiring"],
                 fr["frontend"], fr["backend_llc"], fr["backend_dram"],
                 fr["backend_contention"], fr["backend_bandwidth"]])

    @staticmethod
    def header() -> List[str]:
        return ["kind", "log2n", "nnz", "reorder", "partition", "threads",
                "speedup", "efficiency", "time_us", "imbalance",
                "l2_mpki_mean", "l2_mpki_max", "llc_mpki_mean", "dram_util",
                "pf_on", "bound", "retiring", "frontend", "llc_frac",
                "dram_frac", "contention", "bw_frac"]


# 1-thread reference times for speedup columns, memoized per process so
# the thread axis pays for its baseline replay once.  Recomputing it in
# another process yields the identical float (the replay and time model
# are deterministic pure functions), so this never breaks bit-identity.
_T1_MEMO: Dict[Tuple, float] = {}


def _scaling_run(kind: str, log2n: int, rlabel: str, strategy,
                 partition: str, threads: int, spec,
                 machine: MachineModel, sweeps: int, seed: int):
    from repro.core.partition import (nnz_split, rowblock_balanced,
                                      rowblock_equal)
    from repro.parallel import nnz_partitioned_traces, simulate_parallel

    base = _matrix(kind, 2 ** log2n, seed=seed)
    p = _planned(base, strategy)
    csr = p.csr
    trace = p.address_trace(machine)
    if partition == "merge":
        part = nnz_split(csr, threads)
        slices = nnz_partitioned_traces(csr, part, machine, trace=trace)
        _, m = simulate_parallel(csr, part, machine, spec, sweeps=sweeps,
                                 traces=slices)
    else:
        part_fn = (rowblock_balanced if partition == "balanced"
                   else rowblock_equal)
        part = part_fn(csr, threads)
        _, m = simulate_parallel(csr, part, machine, spec, sweeps=sweeps,
                                 trace=trace)
    return csr, part, m


def run_scaling_cell(kind: str, log2n: int, rlabel: str, strategy,
                     partition: str, threads: int, spec=None,
                     machine: MachineModel = SANDY_BRIDGE,
                     sweeps: int = 2, seed: int = 0) -> ScalingPoint:
    """One (matrix, reorder, partition, thread-count) cell of
    `scaling_sweep`, including its own 1-thread speedup reference
    (memoized per process)."""
    from repro.parallel import ParallelSpec

    spec = spec if spec is not None else ParallelSpec()
    csr, part, m = _scaling_run(kind, log2n, rlabel, strategy, partition,
                                threads, spec, machine, sweeps, seed)
    t1_key = (kind, log2n, rlabel, partition, spec, machine, sweeps, seed)
    t1_time = _T1_MEMO.get(t1_key)
    if t1_time is None:
        if part.n_parts == 1:
            t1_time = m.time_s
        else:
            _, _, m1 = _scaling_run(kind, log2n, rlabel, strategy, partition,
                                    1, spec, machine, sweeps, seed)
            t1_time = m1.time_s
        _T1_MEMO[t1_key] = t1_time
    speedup = t1_time / max(m.time_s, 1e-30)
    # partitioners cap parts at n_rows; record what ran
    threads_eff = part.n_parts
    return ScalingPoint(
        kind=kind, log2n=log2n, nnz=csr.nnz, threads=threads_eff,
        reorder=rlabel, partition=partition, imbalance=part.imbalance(),
        speedup=speedup, efficiency=speedup / threads_eff, metrics=m)


def scaling_sweep(log2ns: Sequence[int] = (12,),
                  kinds: Sequence[str] = ("fd", "rmat"),
                  threads_list: Sequence[int] = (1, 2, 4, 8, 16, 32),
                  spec=None, machine: MachineModel = SANDY_BRIDGE,
                  partition: str = "equal",
                  reorderings: Optional[Dict] = None,
                  sweeps: int = 2, seed: int = 0,
                  workers: int = 1,
                  ckpt_dir: Optional[str] = None) -> List[ScalingPoint]:
    """The thread axis: multithreaded replay through `repro.parallel`.

    For every (kind, size, reorder) the matrix is partitioned per thread
    count and replayed through private caches + the shared, contended
    LLC; speedup is measured against the same cell's 1-thread replay
    (computed even when 1 is not in `threads_list`).  `reorderings` has
    `run_sweep` semantics, so "how much of the scaling gap does RCM
    close?" is one sweep: `reorderings={"none": None, "rcm": reorder.rcm}`.

    `partition` is 'equal' (row counts), 'balanced' (row blocks split on
    the nnz CDF) or 'merge' (the segmented/merge-CSR execution: equal
    *nonzero* segments that may cut mid-row, sliced from the same global
    trace by `parallel.nnz_partitioned_traces`).

    Thin client of `telemetry.runner` (sorted canonical cell order;
    `workers`/`ckpt_dir` shard and checkpoint the grid, bit-identically
    to the serial path).
    """
    from repro.parallel import ParallelSpec

    from . import runner

    spec = spec if spec is not None else ParallelSpec()
    reorderings = reorderings if reorderings is not None else {"none": None}
    cells = runner.scaling_cells(log2ns=log2ns, kinds=kinds,
                                 threads_list=threads_list,
                                 partition=partition,
                                 reorderings=reorderings)
    cfg = runner.SweepConfig(machine=machine, sweeps=sweeps, seed=seed,
                             reorderings=dict(reorderings),
                             parallel_spec=spec)
    return runner.execute_cells(cells, cfg, workers=workers,
                                ckpt_dir=ckpt_dir)


@dataclasses.dataclass(frozen=True)
class GraphPoint:
    """One (matrix, analytic) cell of a graph sweep: a whole iterative
    analytic, with per-iteration cache behavior from the plan's memoized
    trace (iteration 1 cold, later iterations warm)."""

    kind: str                 # 'fd' | 'rmat'
    log2n: int
    nnz: int                  # of the analytic's operand matrix
    analytic: str             # 'pagerank' | 'bfs' | 'sssp' | ...
    semiring: str
    n_iters: int
    converged: bool
    iters: Tuple              # TopdownSummary per iteration
    format_name: str = "csr"  # the plan's chosen container format

    @property
    def cold_cycles_per_nnz(self) -> float:
        return self.iters[0].cycles_per_nnz if self.iters else 0.0

    @property
    def warm_cycles_per_nnz(self) -> float:
        tail = self.iters[1:] or self.iters
        if not tail:
            return 0.0
        return float(np.mean([s.cycles_per_nnz for s in tail]))

    @property
    def total_cycles_per_nnz(self) -> float:
        """Whole-analytic cost: per-iteration cycles/nnz summed over the
        run -- what the FD/R-MAT gap compounds into."""
        return float(sum(s.cycles_per_nnz for s in self.iters))

    def row(self) -> List:
        # a 0-iteration run (converged before its first SpMV) still renders
        return [self.kind, self.log2n, self.nnz, self.analytic,
                self.semiring, self.format_name, self.n_iters,
                int(self.converged),
                self.cold_cycles_per_nnz, self.warm_cycles_per_nnz,
                self.total_cycles_per_nnz,
                self.iters[0].l2_mpki if self.iters else 0.0,
                self.iters[-1].l2_mpki if self.iters else 0.0,
                self.iters[0].bound() if self.iters else "",
                self.iters[-1].bound() if self.iters else ""]

    @staticmethod
    def header() -> List[str]:
        return ["kind", "log2n", "nnz", "analytic", "semiring", "format",
                "n_iters", "converged", "cold_cyc_nnz", "warm_cyc_nnz",
                "total_cyc_nnz", "l2_mpki_cold", "l2_mpki_warm",
                "bound_cold", "bound_warm"]


def graph_sweep(log2ns: Sequence[int] = (10,),
                kinds: Sequence[str] = ("fd", "rmat"),
                analytics: Sequence[str] = ("pagerank", "bfs", "sssp"),
                spec: Optional[HierarchySpec] = None,
                machine: MachineModel = SANDY_BRIDGE,
                seed: int = 0, max_iters: int = 64,
                format: Optional[str] = None,
                workers: int = 1,
                ckpt_dir: Optional[str] = None) -> List[GraphPoint]:
    """Whole-analytic axis: run each `repro.graph` driver to convergence,
    then replay its plan's memoized address trace once per executed
    iteration through a warm hierarchy.  The per-iteration summaries show
    how the single-SpMV FD-vs-R-MAT gap compounds across a full PageRank /
    BFS / SSSP run (`report.graph_gap_report` tabulates it).

    Source-based analytics (bfs, sssp) start from the max-out-degree
    vertex (a hub -- vertex 0 can be edgeless on sparse R-MAT draws);
    pagerank starts from a seeded random restart vector so near-regular
    FD grids don't begin at their own fixpoint.

    `format=None` (default) lets each plan's structure analysis pick the
    container -- power-law R-MAT auto-routes to the hybrid row split
    (hyb) -- while an explicit name (e.g. "csr") pins every plan to that
    format, giving benches a fixed-format baseline to quantify what the
    nnz-balanced candidates recover.
    """
    from . import runner

    cells = runner.graph_cells(log2ns=log2ns, kinds=kinds,
                               analytics=analytics, format=format)
    cfg = runner.SweepConfig(machine=machine, seed=seed, hier_spec=spec,
                             max_iters=max_iters, graph_format=format)
    return runner.execute_cells(cells, cfg, workers=workers,
                                ckpt_dir=ckpt_dir)


def run_graph_cell(kind: str, log2n: int, analytic: str,
                   spec: Optional[HierarchySpec] = None,
                   machine: MachineModel = SANDY_BRIDGE,
                   seed: int = 0, max_iters: int = 64,
                   format: Optional[str] = None) -> GraphPoint:
    """One (matrix, analytic) cell of `graph_sweep`: run the driver to
    convergence, then replay its plan's trace once per iteration."""
    from repro.graph import DRIVERS
    from repro.graph.telemetry import iteration_summaries

    base = _matrix(kind, 2 ** log2n, seed=seed)
    source = int(np.argmax(np.diff(np.asarray(base.indptr))))
    r0 = np.random.default_rng(seed).uniform(
        0.5, 1.5, size=base.n_rows).astype(np.float32)
    driver = DRIVERS[analytic]
    if analytic in ("bfs", "sssp"):
        res = driver(base, source, max_iters=max_iters, format=format)
    elif analytic == "pagerank":
        res = driver(base, r0=r0, max_iters=max_iters, format=format)
    else:
        res = driver(base, max_iters=max_iters, format=format)
    iters = tuple(iteration_summaries(
        res.plan, res.n_iters, machine=machine, spec=spec))
    return GraphPoint(
        kind=kind, log2n=log2n, nnz=int(res.plan.csr.nnz),
        analytic=analytic, semiring=res.plan.semiring,
        n_iters=int(res.n_iters), converged=bool(res.converged),
        iters=iters, format_name=res.plan.format_name)


def geometry_sweep(log2n: int = 14,
                   kinds: Sequence[str] = ("fd", "rmat"),
                   l2_kb: Sequence[int] = (128, 256, 512),
                   ways: Sequence[Optional[int]] = (8, None),
                   machine: MachineModel = SANDY_BRIDGE,
                   sweeps: int = 2, seed: int = 0) -> List[SweepPoint]:
    """Cache-size x associativity sweep at fixed size (mechanisms off)."""
    specs = {}
    for kb in l2_kb:
        for w in ways:
            wlab = "full" if w is None else f"{w}way"
            specs[f"l2-{kb}k-{wlab}"] = HierarchySpec(
                l2_bytes=kb * 1024, ways=w)
    return run_sweep(log2ns=(log2n,), kinds=kinds, mechanisms=specs,
                     machine=machine, sweeps=sweeps, seed=seed)
