"""A chain of multiplies through `SpmvPlan.execute`.

x_{k+1} = y_k / max|y_k|, as a power or Krylov iteration feeds the
kernel; each multiply ends in `block_until_ready` before the next is
issued.  The plan is compiled as the matrix comes (`reorder="none"`) with
the format the plan chooses.  Traffic parameters:

  limits    {"max_row_err": limit} for the check

The window checks two of its answers against the float64 reference: one
drawn from the seed (a reservoir of one over the window) and the last,
each by its componentwise error (`reference.row_err`).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference, work

SPAN = "bench.multiply"


@jax.jit
def bench_normalize(y):
    return y / jnp.max(jnp.abs(y))


def setup(run) -> None:
    from repro import plan

    a = run.timed("generate_s", gen.generate, run.cfg, run.seed)
    csr = run.timed("to_device_s", lambda: jax.block_until_ready(
        gen.to_program(a)))
    t = time.perf_counter()
    p = plan.compile(csr, reorder="none")
    jax.block_until_ready(work.device_arrays(p.prep))
    run.e2e["plan_s"] = run.setup["plan_s"] = time.perf_counter() - t
    run.setup.update({f"plan.{k}": v for k, v in p.compile_stats.items()
                      if k.endswith("_s")})
    x0 = gen.start_vector(run.seed, a.n_cols)
    t = time.perf_counter()
    bench_normalize(p.execute(x0).block_until_ready()).block_until_ready()
    run.setup["warm_s"] = time.perf_counter() - t
    run.info.update(nnz=a.nnz, n_rows=a.n_rows, n_cols=a.n_cols,
                    format=p.format_name,
                    layout_bytes=work.layout_bytes(p.prep))
    run.state.update(matrix=a, plan=p, x0=x0)


def window(run, seconds: float) -> None:
    p, x = run.state["plan"], run.state["x0"]
    pick = np.random.default_rng(run.seed)
    count, kept = 0, None
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(SPAN):
            y = p.execute(x)
            y.block_until_ready()
        count += 1
        if pick.random() * count < 1.0:
            kept = (x, y)
        t = time.perf_counter()
        if t - t0 >= seconds:
            break
        x = bench_normalize(y)
    run.window_s = t - t0
    run.attempted = count
    run.counts["multiplies"] = count
    run.e2e["spmv_gflops"] = (work.spmv_flops(run.info["nnz"]) * count
                              / run.window_s / 1e9)
    run.state["answers"] = [kept, (x, y)] if kept[1] is not y else [(x, y)]


def release(run) -> None:
    """Pull the answers to the host and drop the program's state."""
    run.state["answers"] = [(np.asarray(x), np.asarray(y))
                            for x, y in run.state["answers"]]
    for k in ("plan", "x0"):
        run.state.pop(k, None)


def check(run) -> dict:
    a = run.state["matrix"]
    err = max(reference.row_err(y, a, x) for x, y in run.state["answers"])
    return {"max_row_err": (err, run.traffic["limits"]["max_row_err"])}


def control(run) -> dict:
    """The check's number with the bfloat16 control in the program's
    place, on the same inputs."""
    a = run.state["matrix"]
    err = max(reference.row_err(reference.spmv_control(a, x), a, x)
              for x, _ in run.state["answers"])
    return {"max_row_err": err}
