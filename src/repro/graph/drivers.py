"""Iterative graph-analytics drivers on compiled `SpmvPlan`s.

The paper motivates SpMV as "the core operation in many common network
and graph analytics" -- these drivers are those analytics, each one an
iterated semiring SpMV over a plan compiled ONCE:

    pagerank              plus_times  on the column-stochastic transpose
    bfs                   or_and      frontier propagation (hop depths)
    sssp                  min_plus    Bellman-Ford relaxation
    connected_components  min_plus    label propagation (zero weights)

Every analytic is factored into three pieces so both the blocking
drivers here and the `repro.serve_graph` engine can run it:

  * an **operand builder** (`analytic_operand`) -- host-side derivation
    of the matrix the iteration multiplies (stochastic transpose,
    pattern transpose, symmetrized zero-weight adjacency) plus any
    auxiliary vectors (PageRank's dangling mask);
  * a **stepper** (`make_stepper`) -- the per-iteration state machine:
    `frontier()` yields the (k, n) batch the next SpMV consumes,
    `advance(y)` folds the product back in, updates per-lane
    convergence, and returns the iteration's progress scalar;
  * the **SpMV itself**, which the *caller* owns: the drivers below loop
    `plan.execute` / `plan.execute_many`, while the serving engine
    coalesces frontiers from many concurrent requests over the same
    graph into one batched `execute_many` per step.

The per-iteration cost is therefore exactly the paper's object of study:
one SpMV's worth of memory traffic, nothing else -- which is what lets
`telemetry.sweep.graph_sweep` replay a whole analytic from the plan's
memoized address trace.

Each blocking driver call runs in a `graph.query` span (with the
analytic and a per-process query number), its host steps in child spans
(`repro.spans`): `graph.operand`, the plan cache's `plan_cache.get`, and
per iteration `graph.iteration` around `spmv.execute`, `graph.host_sync`
and `graph.advance`.  `recent_queries()` keeps the seconds of each,
summed over the query, for the last few queries.

Graph convention: the input is a square CSR adjacency with A[i, j] != 0
meaning an edge i -> j (weight = stored value).  SpMV computes
y[i] = ⊕_j A[i,j] ⊗ x[j] -- a *pull* along rows -- so push-style
traversals (BFS/SSSP from a source) run on the transpose, built once at
plan-compile time.  Undirected graphs should be stored symmetrically
(generators' FD/R-MAT matrices are fine as-is).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core.formats import CSR
from repro.spans import collect, span

from .semiring import MIN_PLUS, OR_AND, PLUS_TIMES, Semiring


@dataclasses.dataclass
class GraphResult:
    """Outcome of one analytic run.

    values    the analytic's vector: PageRank scores, hop depths,
              distances, or component labels ((k, n) for multi-source)
    n_iters   SpMV iterations executed
    converged True when the fixpoint/tolerance was reached before
              `max_iters`
    history   one scalar per iteration (residual / frontier size /
              labels changed) -- the convergence trajectory
    plan      the compiled `SpmvPlan` the iterations executed through
              (its memoized `address_trace` is what telemetry replays)
    """

    values: np.ndarray
    n_iters: int
    converged: bool
    history: List[float]
    plan: object

    def summary(self) -> str:
        tail = f"{self.history[-1]:.3g}" if self.history else "-"
        return (f"{self.plan.summary()} iters={self.n_iters} "
                f"converged={self.converged} last={tail}")


def transpose_csr(csr: CSR) -> CSR:
    """A^T as a canonically sorted CSR (host-side, plan-compile time)."""
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(indptr))
    return CSR.from_coo(np.asarray(csr.indices, dtype=np.int64), rows,
                        np.asarray(csr.data), csr.n_cols, csr.n_rows,
                        dtype=np.asarray(csr.data).dtype)


def _require_square(adj: CSR, who: str) -> int:
    if adj.n_rows != adj.n_cols:
        raise ValueError(f"{who} needs a square adjacency, "
                         f"got {adj.n_rows}x{adj.n_cols}")
    return adj.n_rows


def check_sources(source, n: int, who: str = "analytic") -> np.ndarray:
    """Validate and normalize a source spec to an int64 array.

    Empty and duplicate sources are well-defined (zero lanes / equal
    lanes); out-of-range indices are refused up front with a clear error
    instead of surfacing as an IndexError deep in the frontier setup.
    """
    sources = np.atleast_1d(np.asarray(source, dtype=np.int64))
    if sources.ndim != 1:
        raise ValueError(f"{who} sources must be a scalar or 1-D sequence, "
                         f"got shape {sources.shape}")
    if sources.size and (sources.min() < 0 or sources.max() >= n):
        bad = sources[(sources < 0) | (sources >= n)]
        raise ValueError(f"{who} sources out of range for n={n}: "
                         f"{bad.tolist()}")
    return sources


def _graph_plan(matrix: CSR, semiring, *, reorder, plan_cache, format=None,
                use_pallas=True, interpret=None):
    """Compile-once entry shared by every driver: plans land in the
    process-wide `plan.DEFAULT_CACHE` (or a caller-supplied `PlanCache`),
    so re-running an analytic -- or a different analytic over the same
    derived matrix -- recompiles nothing."""
    from repro import plan as _plan

    cache = plan_cache if plan_cache is not None else _plan.DEFAULT_CACHE
    return cache.get_or_compile(matrix, **plan_options(
        semiring, reorder=reorder, format=format, use_pallas=use_pallas,
        interpret=interpret))


def plan_options(semiring, *, reorder="none", predictor="none", format=None,
                 use_pallas=True, interpret=None) -> Dict:
    """The exact compile-option dict the drivers use -- shared with
    `serve_graph` admission so its warm-pool check (`PlanCache.key_for`)
    and its compiles produce the same cache keys the drivers would.

    `predictor` defaults to 'none' (no candidate scoring), preserving the
    historical cache keys; pass 'model'/'oracle' together with
    `reorder='auto'` when the engine should pick reorderings per graph.
    """
    name = semiring.name if isinstance(semiring, Semiring) else str(semiring)
    opts = dict(reorder=reorder, predictor=predictor, semiring=name,
                use_pallas=use_pallas, interpret=interpret, keep_csr=True)
    if format is not None:
        opts["format"] = format
    return opts


# ---------------------------------------------------------------------------
# Operand builders: adjacency -> the matrix the iteration multiplies
# ---------------------------------------------------------------------------

def pagerank_operand(adj: CSR) -> Tuple[CSR, Dict]:
    """Column-stochastic transpose P[j, i] = 1/out_deg[i] per edge i -> j,
    plus the dangling-vertex mask the iteration redistributes."""
    n = _require_square(adj, "pagerank")
    indptr = np.asarray(adj.indptr, dtype=np.int64)
    cols = np.asarray(adj.indices, dtype=np.int64)
    out_deg = np.diff(indptr).astype(np.float32)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    stoch = CSR.from_coo(cols, rows,
                         1.0 / np.maximum(out_deg[rows], 1.0), n, n)
    return stoch, {"dangling": (out_deg == 0).astype(np.float32)}


def bfs_operand(adj: CSR) -> Tuple[CSR, Dict]:
    """0/1 pattern of A^T: or_and propagation pulls each vertex's
    frontier membership from its in-neighbors along original edges."""
    n = _require_square(adj, "bfs")
    at = transpose_csr(adj)
    pattern = CSR(data=jnp.ones_like(at.data), indices=at.indices,
                  indptr=at.indptr, n_rows=n, n_cols=n)
    return pattern, {}


def sssp_operand(adj: CSR) -> Tuple[CSR, Dict]:
    _require_square(adj, "sssp")
    return transpose_csr(adj), {}


def cc_operand(adj: CSR) -> Tuple[CSR, Dict]:
    """Symmetrized zero-weight pattern: min_plus SpMV then computes each
    vertex's minimum neighbor label."""
    n = _require_square(adj, "connected_components")
    if n > (1 << 24):
        raise ValueError(
            f"connected_components labels are f32 vertex ids, which are "
            f"only injective up to 2^24; got n={n}")
    indptr = np.asarray(adj.indptr, dtype=np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    cols = np.asarray(adj.indices, dtype=np.int64)
    # deduplicate coordinates (an edge stored in both directions would
    # symmetrize to a doubled entry): the min_plus reduction is
    # unaffected, and canonical duplicate-free operands are what the
    # streaming lifecycle's csr_diff requires
    keys = np.unique(np.concatenate([rows * n + cols, cols * n + rows]))
    sym = CSR.from_coo(keys // n, keys % n,
                       np.zeros(keys.size, dtype=np.float32), n, n)
    return sym, {}


# ---------------------------------------------------------------------------
# Steppers: per-iteration state machines (frontier -> SpMV -> advance)
# ---------------------------------------------------------------------------

class PageRankStepper:
    """Power iteration on the stochastic transpose, k lanes.

    Without sources each lane teleports uniformly (classic PageRank, the
    historical driver semantics); a source lane teleports to its seed
    vertex instead -- personalized PageRank, which is what makes
    multi-source serving requests produce genuinely distinct lanes.
    """

    analytic = "pagerank"

    def __init__(self, plan, aux: Dict, sources=(), damping: float = 0.85,
                 tol: float = 1e-8, r0=None):
        n = plan.n_cols
        sources = check_sources(sources, n, "pagerank") if len(
            np.atleast_1d(sources)) else np.array([], dtype=np.int64)
        self.plan, self.damping, self.tol = plan, float(damping), float(tol)
        self.dangling = jnp.asarray(aux["dangling"])
        if sources.size:
            t = np.zeros((len(sources), n), np.float32)
            t[np.arange(len(sources)), sources] = 1.0
        else:
            t = np.full((1, n), 1.0 / max(n, 1), np.float32)
        self.teleport = jnp.asarray(t)
        if r0 is not None:
            r = jnp.asarray(r0, jnp.float32)
            r = r.reshape(1, n) if r.ndim == 1 else r
            if r.shape != self.teleport.shape:
                raise ValueError(
                    f"r0 shape {tuple(r.shape)} does not match the "
                    f"{tuple(self.teleport.shape)} lane layout")
            r = r / jnp.maximum(r.sum(axis=1, keepdims=True), 1e-30)
        else:
            r = self.teleport
        self.r = r
        self.k = int(r.shape[0])
        self.lane_done = np.zeros(self.k, bool)
        self.done = self.k == 0

    def frontier(self):
        return self.r

    def advance(self, y) -> float:
        y = jnp.asarray(y)
        leaked = self.r @ self.dangling                       # (k,)
        r_new = (self.damping * (y + leaked[:, None] * self.teleport)
                 + (1.0 - self.damping) * self.teleport)
        resid = np.asarray(jnp.abs(r_new - self.r).sum(axis=1))
        self.r = r_new
        self.lane_done = resid < self.tol
        self.done = bool(self.lane_done.all())
        return float(resid.max()) if resid.size else 0.0

    def values(self) -> np.ndarray:
        return np.asarray(self.r)


class BfsStepper:
    """or_and frontier propagation; `values()[l, v]` is v's hop depth
    from lane l's source (+inf if unreachable).  Duplicate sources are
    fine (equal lanes); zero sources is a zero-lane no-op run.

    No warm-start: depths are assigned level-synchronously (a vertex's
    depth is the global `level` counter the step its frontier bit first
    rises), so even an insert-only delta can LOWER finite depths --
    resuming from old depths would keep the stale values.  Any delta
    re-seeds BFS (`warm_start_params` returns None)."""

    analytic = "bfs"

    def __init__(self, plan, aux: Dict, sources=(), **_):
        n = plan.n_cols
        sources = check_sources(sources, n, "bfs")
        k = len(sources)
        self.plan, self.k, self.level = plan, k, 0
        self.depth = np.full((k, n), np.inf, dtype=np.float32)
        self.depth[np.arange(k), sources] = 0.0
        self.front = np.zeros((k, n), dtype=np.float32)
        self.front[np.arange(k), sources] = 1.0
        self.done = not self.front.any()
        self.lane_done = ~self.front.any(axis=1)

    def frontier(self):
        return self.front

    def advance(self, y) -> float:
        y = np.asarray(y)
        self.level += 1
        reached = (y > 0.0) & np.isinf(self.depth)
        self.depth[reached] = self.level
        self.front = reached.astype(np.float32)
        self.lane_done = ~self.front.any(axis=1)
        self.done = not self.front.any()
        return float(reached.sum())

    def values(self) -> np.ndarray:
        return self.depth


class SsspStepper:
    """min_plus Bellman-Ford relaxation, k source lanes.

    `d0` warm-starts from prior distances (shape (k, n) matching the
    sources): after insert-only edge deltas the old converged distances
    are valid upper bounds, so relaxation resumes from them and only
    re-settles the vertices the new edges improved.  Deletes can RAISE
    true distances, which monotone relaxation can never do -- callers
    must re-seed then (`warm_start_params` encodes the rule)."""

    analytic = "sssp"

    def __init__(self, plan, aux: Dict, sources=(), d0=None, **_):
        n = plan.n_cols
        sources = check_sources(sources, n, "sssp")
        k = len(sources)
        self.plan, self.k = plan, k
        self.dist = np.full((k, n), np.inf, dtype=np.float32)
        self.dist[np.arange(k), sources] = 0.0
        if d0 is not None:
            self.dist = np.minimum(
                np.asarray(d0, np.float32).reshape(k, n), self.dist)
        self.lane_done = np.zeros(k, bool)
        self.done = k == 0

    def frontier(self):
        return self.dist

    def advance(self, y) -> float:
        nd = np.minimum(self.dist, np.asarray(y))
        changed = (nd < self.dist).sum(axis=1)
        self.dist = nd
        self.lane_done = changed == 0
        self.done = bool(self.lane_done.all())
        return float(changed.sum())

    def values(self) -> np.ndarray:
        return self.dist


class CcStepper:
    """min-label propagation to the component-wise minimum vertex id.
    Always one lane; sources are ignored.

    `l0` warm-starts from prior labels: after insert-only deltas each
    vertex's old label (the min id of its old component) is a reachable
    upper bound in the new graph, so propagation resumes and only the
    merged components re-settle.  Edge deletes can split components --
    labels would have to rise -- so deletes force a re-seed
    (`warm_start_params`)."""

    analytic = "connected_components"

    def __init__(self, plan, aux: Dict, sources=(), l0=None, **_):
        n = plan.n_cols
        self.plan, self.k = plan, 1
        self.labels = np.arange(n, dtype=np.float32)[None]
        if l0 is not None:
            self.labels = np.minimum(
                np.asarray(l0, np.float32).reshape(1, n), self.labels)
        self.lane_done = np.zeros(1, bool)
        self.done = False

    def frontier(self):
        return self.labels

    def advance(self, y) -> float:
        nl = np.minimum(self.labels, np.asarray(y))
        changed = int((nl < self.labels).sum())
        self.labels = nl
        self.lane_done[:] = changed == 0
        self.done = changed == 0
        return float(changed)

    def values(self) -> np.ndarray:
        return self.labels


@dataclasses.dataclass(frozen=True)
class AnalyticDef:
    """One analytic, decomposed for engine-driven execution."""

    name: str
    semiring: Semiring
    operand: Callable[[CSR], Tuple[CSR, Dict]]
    stepper: Callable
    source_based: bool          # lanes = sources (vs one state vector)


ANALYTICS: Dict[str, AnalyticDef] = {
    "pagerank": AnalyticDef("pagerank", PLUS_TIMES, pagerank_operand,
                            PageRankStepper, source_based=False),
    "bfs": AnalyticDef("bfs", OR_AND, bfs_operand, BfsStepper,
                       source_based=True),
    "sssp": AnalyticDef("sssp", MIN_PLUS, sssp_operand, SsspStepper,
                        source_based=True),
    "connected_components": AnalyticDef(
        "connected_components", MIN_PLUS, cc_operand, CcStepper,
        source_based=False),
}


def analytic_operand(analytic: str, adj: CSR) -> Tuple[CSR, str, Dict]:
    """(operand matrix, semiring name, aux) for one analytic -- the
    host-side derivation `serve_graph` admission performs once per
    (graph, analytic) before consulting the plan cache; a
    `graph.operand` span."""
    d = ANALYTICS.get(analytic)
    if d is None:
        raise ValueError(f"unknown analytic {analytic!r}; "
                         f"have {sorted(ANALYTICS)}")
    with span("graph.operand"):
        matrix, aux = d.operand(adj)
    return matrix, d.semiring.name, aux


def make_stepper(analytic: str, plan, aux: Dict, sources=(), params=None):
    """Instantiate the per-iteration state machine for one request."""
    d = ANALYTICS.get(analytic)
    if d is None:
        raise ValueError(f"unknown analytic {analytic!r}; "
                         f"have {sorted(ANALYTICS)}")
    return d.stepper(plan, aux, sources=sources, **(params or {}))


#: Stepper kwarg each analytic consumes to resume from prior values.
WARM_START_PARAM = {"pagerank": "r0", "sssp": "d0",
                    "connected_components": "l0"}


def warm_start_params(analytic: str, values, delta=None) -> Optional[Dict]:
    """Stepper params resuming `analytic` from converged `values` after
    edge delta `delta`, or None when correctness demands a re-seed.

    The rules (each argued in the steppers' docstrings):

      pagerank   always warm -- power iteration converges to its unique
                 fixpoint from any start; old scores are just a better
                 start than teleport;
      sssp / cc  warm after insert-only deltas (old values are valid
                 upper bounds the monotone iteration drives down to the
                 new fixpoint); deletes can raise true values, which
                 min-reductions cannot, so they re-seed;
      bfs        never warm -- level-synchronous depth assignment goes
                 stale under any delta.

    `delta` may be the adjacency delta or the derived operand delta
    (inserts map to inserts either way); None means "unknown mutation",
    treated as delete-bearing.
    """
    kw = WARM_START_PARAM.get(analytic)
    if kw is None:
        return None
    if analytic != "pagerank" and (delta is None or delta.has_deletes):
        return None
    return {kw: np.asarray(values, dtype=np.float32)}


_QUERY_NUMBERS = itertools.count(1)
_RECENT: collections.deque = collections.deque(maxlen=64)


def recent_queries() -> List[Tuple[str, Dict[str, float]]]:
    """(analytic, seconds by span name) of this process's last 64
    blocking driver calls, oldest first."""
    return list(_RECENT)


@contextlib.contextmanager
def _query(analytic: str):
    """One driver call's `graph.query` span; the seconds of the spans
    inside it go to `recent_queries`."""
    with collect() as host_s:
        with span("graph.query", analytic=analytic,
                  query=next(_QUERY_NUMBERS)):
            yield
    _RECENT.append((analytic, host_s))


def _drive(stepper, plan, max_iters: int, multi: bool) -> GraphResult:
    """The blocking driver loop: pull `frontier()`, run the plan, feed
    `advance()` -- single-source stays on the 1-D Pallas `execute` path
    (bit-compatible with the historical drivers), multi-source batches
    through `execute_many`."""
    history: List[float] = []
    it = 0
    while it < max_iters and not stepper.done:
        it += 1
        with span("graph.iteration", it=it):
            F = stepper.frontier()
            if multi:
                y = plan.execute_many(jnp.asarray(F))
            else:
                y = plan.execute(jnp.asarray(F)[0])
            with span("graph.host_sync"):
                y = np.asarray(y)
            with span("graph.advance"):
                history.append(stepper.advance(y if multi else y[None]))
    vals = stepper.values()
    return GraphResult(values=vals if multi else vals[0], n_iters=it,
                       converged=bool(stepper.done), history=history,
                       plan=plan)


# ---------------------------------------------------------------------------
# Blocking drivers (compile one plan, iterate to convergence)
# ---------------------------------------------------------------------------

def pagerank(adj: CSR, damping: float = 0.85, tol: float = 1e-8,
             max_iters: int = 100, *, r0=None, reorder="none",
             format: Optional[str] = None, plan_cache=None,
             use_pallas: bool = True,
             interpret: Optional[bool] = None) -> GraphResult:
    """PageRank by power iteration on P = A^T D_out^{-1} (plus_times).

    Dangling vertices (zero out-degree) redistribute their mass
    uniformly, so r stays a probability distribution.  Converges when
    the L1 step residual drops below `tol`.  `r0` overrides the uniform
    start (it is normalized to sum 1) -- on near-regular graphs (FD
    grids) the uniform vector is already the fixpoint, so a perturbed
    start is what makes the iteration count meaningful there.
    """
    with _query("pagerank"):
        matrix, _, aux = analytic_operand("pagerank", adj)
        p = _graph_plan(matrix, PLUS_TIMES, reorder=reorder, format=format,
                        plan_cache=plan_cache, use_pallas=use_pallas,
                        interpret=interpret)
        st = PageRankStepper(p, aux, damping=damping, tol=tol, r0=r0)
        return _drive(st, p, max_iters, multi=False)


def bfs(adj: CSR, source: Union[int, Sequence[int]],
        max_iters: Optional[int] = None, *, reorder="none",
        format: Optional[str] = None, plan_cache=None,
        use_pallas: bool = True, interpret: Optional[bool] = None
        ) -> GraphResult:
    """Hop depths from `source` by or_and frontier propagation on A^T.

    `values[v]` is the BFS depth of v (0 at the source, +inf if
    unreachable).  A sequence of sources runs them all concurrently:
    single source iterates `plan.execute`, multi-source batches the
    frontiers through `plan.execute_many` (values then (k, n), one row
    per source -- duplicates produce equal rows, an empty sequence a
    (0, n) result).  The loop terminates on the first empty frontier --
    the normal end state, reached immediately on an edgeless (nnz=0)
    graph.
    """
    n = _require_square(adj, "bfs")
    multi = np.ndim(source) > 0
    with _query("bfs"):
        matrix, _, aux = analytic_operand("bfs", adj)
        p = _graph_plan(matrix, OR_AND, reorder=reorder, format=format,
                        plan_cache=plan_cache, use_pallas=use_pallas,
                        interpret=interpret)
        st = BfsStepper(p, aux, sources=np.atleast_1d(
            np.asarray(source, dtype=np.int64)))
        return _drive(st, p, n if max_iters is None else max_iters,
                      multi=multi)


def sssp(adj: CSR, source: int, max_iters: Optional[int] = None, *,
         d0=None, reorder="none", format: Optional[str] = None,
         plan_cache=None, use_pallas: bool = True,
         interpret: Optional[bool] = None) -> GraphResult:
    """Single-source shortest paths by Bellman-Ford relaxation:
    d' = d ⊕ (A^T (⊕=min, ⊗=+) d), iterated to fixpoint.

    Edge weights are the stored values (nonnegative for the shortest-path
    interpretation); unreachable vertices keep +inf.  Converges in at
    most n-1 relaxations; typically far fewer (`history` counts the
    distances lowered per iteration).  `d0` warm-starts from prior
    distances (valid after insert-only graph deltas; see `SsspStepper`).
    """
    n = _require_square(adj, "sssp")
    with _query("sssp"):
        matrix, _, aux = analytic_operand("sssp", adj)
        p = _graph_plan(matrix, MIN_PLUS, reorder=reorder, format=format,
                        plan_cache=plan_cache, use_pallas=use_pallas,
                        interpret=interpret)
        st = SsspStepper(p, aux, sources=[source], d0=d0)
        return _drive(st, p, n if max_iters is None else max_iters,
                      multi=False)


def connected_components(adj: CSR, max_iters: Optional[int] = None, *,
                         l0=None, reorder="none",
                         format: Optional[str] = None,
                         plan_cache=None, use_pallas: bool = True,
                         interpret: Optional[bool] = None) -> GraphResult:
    """Component labels by min-label propagation over the symmetrized
    pattern: with zero edge weights, min_plus SpMV computes each vertex's
    minimum neighbor label, and l' = l ⊕ (S (min,+) l) converges to the
    component-wise minimum vertex id.  `values[v]` is v's component label;
    isolated vertices keep their own id (empty rows reduce to +inf, which
    the ⊕ with the current label absorbs).

    Labels ride through the f32 kernels, so vertex ids must be exactly
    representable: graphs beyond 2^24 rows are refused rather than
    silently merging components whose seed ids collide in f32."""
    n = _require_square(adj, "connected_components")
    with _query("connected_components"):
        matrix, _, aux = analytic_operand("connected_components", adj)
        p = _graph_plan(matrix, MIN_PLUS, reorder=reorder, format=format,
                        plan_cache=plan_cache, use_pallas=use_pallas,
                        interpret=interpret)
        st = CcStepper(p, aux, l0=l0)
        return _drive(st, p, n if max_iters is None else max_iters,
                      multi=False)


DRIVERS = {"pagerank": pagerank, "bfs": bfs, "sssp": sssp,
           "connected_components": connected_components}

__all__ = ["GraphResult", "transpose_csr", "pagerank", "bfs", "sssp",
           "connected_components", "DRIVERS", "recent_queries",
           "AnalyticDef", "ANALYTICS", "analytic_operand", "make_stepper",
           "check_sources", "plan_options",
           "warm_start_params", "WARM_START_PARAM",
           "PageRankStepper", "BfsStepper", "SsspStepper", "CcStepper"]
