"""Repeated PageRank queries through `graph.pagerank`.

Each query starts from the uniform vector and runs exactly `max_iters`
iterations (`tol` 0), so every query does the same work: it derives the
operand from the adjacency, finds its plan in the cell's plan cache, and
iterates.  Set-up warms the cache with one query.  Traffic parameters:

  damping, tol, max_iters   `graph.pagerank`'s arguments
  limits                    {"max_rel_err": limit} for the check

Every query of the window is checked against the float64 reference.
"""
from __future__ import annotations

import time

import jax

from bench import gen, reference, work

SPAN = "bench.query"


def _query(run):
    from repro import graph

    t = run.traffic
    return graph.pagerank(run.state["csr"], damping=t["damping"],
                          tol=t["tol"], max_iters=t["max_iters"],
                          plan_cache=run.state["cache"])


def setup(run) -> None:
    from repro import plan

    a = run.timed("generate_s", gen.generate, run.cfg, run.seed)
    csr = run.timed("to_device_s", lambda: jax.block_until_ready(
        gen.to_program(a)))
    run.state.update(matrix=a, csr=csr, cache=plan.PlanCache())
    res = run.timed("warm_query_s", _query, run)
    p = res.plan
    run.setup.update({f"plan.{k}": v for k, v in p.compile_stats.items()
                      if k.endswith("_s")})
    run.info.update(nnz=a.nnz, n_rows=a.n_rows, n_cols=a.n_cols,
                    format=p.format_name,
                    layout_bytes=work.layout_bytes(p.prep))


def window(run, seconds: float) -> None:
    answers, iters = [], 0
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(SPAN):
            res = _query(run)
        answers.append((res.n_iters, res.values))
        iters += res.n_iters
        t = time.perf_counter()
        if t - t0 >= seconds:
            break
    run.window_s = t - t0
    run.attempted = len(answers)
    run.counts.update(queries=len(answers), iterations=iters)
    run.e2e["pagerank_iters_per_s"] = iters / run.window_s
    run.state["answers"] = answers


def release(run) -> None:
    for k in ("csr", "cache"):
        run.state.pop(k, None)


def _reference(run, n_iters):
    return reference.pagerank(run.state["matrix"], n_iters,
                              run.traffic["damping"])


def check(run) -> dict:
    refs = {}
    err = 0.0
    for n_iters, values in run.state["answers"]:
        if n_iters != run.traffic["max_iters"]:
            err = float("inf")
            continue
        if n_iters not in refs:
            refs[n_iters] = _reference(run, n_iters)
        err = max(err, reference.rel_err(values, refs[n_iters]))
    return {"max_rel_err": (err, run.traffic["limits"]["max_rel_err"])}


def control(run) -> dict:
    n_iters = run.traffic["max_iters"]
    ctl = reference.pagerank_control(run.state["matrix"], n_iters,
                                     run.traffic["damping"])
    return {"max_rel_err": reference.rel_err(ctl, _reference(run,
                                                              n_iters))}
