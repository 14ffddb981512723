"""repro.plan — compile-once SpMV: cached, serializable execution plans.

The paper's workloads call SpMV thousands of times on the *same* matrix
(graph analytics: eigensolvers, PageRank), so everything the per-call
stack decides — structure analysis, reordering, format conversion,
partitioning, Pallas layout padding — is pure overhead on the hot path.
This package freezes that decision chain once per matrix:

  fingerprint  content digests (a plan is valid while the bytes match)
  compiler     `compile(matrix, ...)` -> SpmvPlan: candidate reorderings
               scored by predicted contended-LLC throughput, winning
               format converted, kernel layout pre-padded; `semiring=`
               builds absorbing-padded plans for `repro.graph` analytics
  plan         SpmvPlan: execute / spmm (k vectors, features last) /
               execute_many (vectors as rows) / power_iteration /
               address_trace
  overlay      OverlaidPlan: a frozen plan + edge delta served warm
               (streaming matrices; staleness-budgeted re-plan)
  cache        PlanCache + the process-wide DEFAULT_CACHE behind the
               graph drivers and distributed.spmv
  costmodel    the learned candidate scorer (structural features ->
               predicted throughput) that replaces trace replay on the
               default compile path, plus its replay-labeled training
               pipeline (`python -m repro.plan.costmodel`)
  serial       save_plan / load_plan (and save_model / load_model)
               through repro.checkpoint

Quick use:

    from repro import plan
    p = plan.compile(csr, threads=8)       # slow: analyze+predict+convert
    y = p.execute(x)                       # fast: zero per-call prep
    Y = p.spmm(X)                          # SpMM: X (n_cols, k)
    Y = p.execute_many(X.T)                # the same, (k, n_cols)
    lam, v = p.power_iteration(x0)         # amortized iterative driver
    plan.save_plan(p, "ckpt/")             # survives restart
"""
from .cache import DEFAULT_CACHE, PlanCache, get_plan
from .compiler import (REPLAY_NNZ_MAX, auto_format, choose_format, compile,
                       convert, convert_chosen)
from .costmodel import (CostModel, default_model, fit_cost_model,
                        set_default_model)
from .fingerprint import (chain_fingerprint, delta_fingerprint,
                          fingerprint_arrays, is_concrete, matrix_fingerprint)
from .overlay import (DEFAULT_STALENESS_BUDGET, OverlaidPlan, overlay,
                      overlay_eligible)
from .plan import SpmvPlan
from .serial import (load_model, load_plan, model_from_state, model_state,
                     plan_from_state, plan_state, save_model, save_plan)

# alias for callers who prefer not to shadow the builtin
compile_plan = compile

__all__ = [
    "SpmvPlan", "compile", "compile_plan",
    "auto_format", "choose_format", "convert", "convert_chosen",
    "REPLAY_NNZ_MAX",
    "PlanCache", "DEFAULT_CACHE", "get_plan",
    "CostModel", "fit_cost_model", "default_model", "set_default_model",
    "OverlaidPlan", "overlay", "overlay_eligible",
    "DEFAULT_STALENESS_BUDGET",
    "matrix_fingerprint", "fingerprint_arrays", "is_concrete",
    "delta_fingerprint", "chain_fingerprint",
    "save_plan", "load_plan", "plan_state", "plan_from_state",
    "save_model", "load_model", "model_state", "model_from_state",
]
