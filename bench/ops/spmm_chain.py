"""A chain of k-vector multiplies through `SpmvPlan.spmm`.

X_{t+1} = Y_t / max|Y_t|, column by column, as stacked GCN aggregations,
block power or subspace iteration feed the kernel, so no multiply can be
skipped or cached; each ends in `block_until_ready` before the next is
issued.  The plan is compiled as the matrix comes (`reorder="none"`),
and `plan_s` runs from `plan.compile`'s start until the SpMM layout's
arrays are ready.  Traffic parameters:

  vectors   k, the columns of X
  sample    {"columns": m, "rows": r} the check reads of an answer
  limits    {"max_entry_err": limit} for the check

The window checks two of its answers, one drawn from the seed (a
reservoir of one over the window) and the last, against the float64
reference (`reference_spmm`) by their componentwise error: every row of
m seeded columns, and every column of r seeded rows.  It keeps only the
device slices the check reads (X's and Y's sampled columns, X's rows
that feed the sampled rows, and Y's sampled rows), never whole copies of
X and Y.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference_spmm, work, work_spmm

SPAN = "bench.multiply"


@jax.jit
def bench_normalize_columns(y):
    return y / jnp.max(jnp.abs(y), axis=0, keepdims=True)


@jax.jit
def bench_keep(x, y, cols, rows, feed):
    """The slices of one answer the check reads."""
    return (jnp.take(x, cols, axis=1), jnp.take(y, cols, axis=1),
            jnp.take(x, feed, axis=0), jnp.take(y, rows, axis=0))


def setup(run) -> None:
    from repro import plan

    if not hasattr(plan.SpmvPlan, "spmm"):
        raise RuntimeError("the program has no SpmvPlan.spmm, which this "
                           "cell multiplies through")
    a = run.timed("generate_s", gen.generate, run.cfg, run.seed)
    csr = run.timed("to_device_s", lambda: jax.block_until_ready(
        gen.to_program(a)))
    t = time.perf_counter()
    p = plan.compile(csr, reorder="none")
    jax.block_until_ready(work.device_arrays(p.prepare_spmm()))
    run.e2e["plan_s"] = run.setup["plan_s"] = time.perf_counter() - t
    run.setup.update({f"plan.{k}": v for k, v in p.compile_stats.items()
                      if k.endswith("_s")})
    k = int(run.traffic["vectors"])
    pick = np.random.default_rng(run.seed)
    sample = run.traffic["sample"]
    cols = np.sort(pick.choice(k, min(int(sample["columns"]), k),
                               replace=False))
    rows = np.sort(pick.choice(a.n_rows, min(int(sample["rows"]), a.n_rows),
                               replace=False))
    feed = reference_spmm.feeding(a, rows)
    where = tuple(jnp.asarray(v.astype(np.int32)) for v in (cols, rows, feed))
    x0 = jax.random.normal(gen._key(run.seed, 1), (a.n_cols, k), jnp.float32)
    t = time.perf_counter()
    y = p.spmm(x0).block_until_ready()
    jax.block_until_ready(bench_keep(x0, y, *where))
    bench_normalize_columns(y).block_until_ready()
    run.setup["warm_s"] = time.perf_counter() - t
    run.info.update(nnz=a.nnz, n_rows=a.n_rows, n_cols=a.n_cols, vectors=k,
                    format=p.format_name,
                    spmm_layout_bytes=work.layout_bytes(p.prepare_spmm()))
    run.state.update(matrix=a, plan=p, x0=x0, where=where,
                     sample=(cols, rows, feed))


def window(run, seconds: float) -> None:
    p, x, where = run.state["plan"], run.state.pop("x0"), run.state["where"]
    pick = np.random.default_rng(run.seed)
    count, drawn, drawn_at = 0, None, 0
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(SPAN):
            y = p.spmm(x)
            y.block_until_ready()
        count += 1
        if pick.random() * count < 1.0:
            drawn, drawn_at = bench_keep(x, y, *where), count
        t = time.perf_counter()
        if t - t0 >= seconds:
            break
        x = None                        # freed before its successor
        x = bench_normalize_columns(y)
        y = None
    run.window_s = t - t0
    run.attempted = count
    run.counts["multiplies"] = count
    run.e2e["spmv_gflops"] = (work_spmm.spmm_flops(run.info["nnz"],
                                                   run.info["vectors"])
                              * count / run.window_s / 1e9)
    run.state["answers"] = [drawn] if drawn_at == count else \
        [drawn, bench_keep(x, y, *where)]


def release(run) -> None:
    """Pull the answers' slices to the host and drop the program's
    state."""
    run.state["answers"] = [tuple(np.asarray(v) for v in ans)
                            for ans in run.state["answers"]]
    for k in ("plan", "x0", "where"):
        run.state.pop(k, None)


def _errs(a, sample, answer, product) -> float:
    """The larger componentwise error of an answer's two samples: of the
    program's product, or with `product="control"` of the bfloat16
    control's in its place."""
    _, rows, feed = sample
    xc, yc, xf, yr = answer
    ref_c, mag_c = reference_spmm.columns(a, xc)
    ref_r, mag_r = reference_spmm.rows(a, rows, feed, xf)
    if product == "control":
        yc = reference_spmm.columns_control(a, xc)
        yr = reference_spmm.rows_control(a, rows, feed, xf)
    return max(reference_spmm.entry_err(yc, ref_c, mag_c),
               reference_spmm.entry_err(yr, ref_r, mag_r))


def check(run) -> dict:
    a, sample = run.state["matrix"], run.state["sample"]
    err = max(_errs(a, sample, ans, "program")
              for ans in run.state["answers"])
    return {"max_entry_err": (err, run.traffic["limits"]["max_entry_err"])}


def control(run) -> dict:
    """The check's number with the bfloat16 control in the program's
    place, on the same inputs."""
    a, sample = run.state["matrix"], run.state["sample"]
    return {"max_entry_err": max(_errs(a, sample, ans, "control")
                                 for ans in run.state["answers"])}
