"""Chip smoke: the SpMV plan path end to end, in one process, on a TPU.

    python chip_smoke.py [--seed 0]     # one chip
    python chip_smoke.py --chips 4      # row-sharded path only

One chip: generates the paper's two structures from `--seed` at
2^22 rows -- FD (9-point stencil, 9 nnz/row) and R-MAT (Graph500
A/B/C quadrants, 8 nnz/row before dedup) -- and drives them through
`plan.compile(a, reorder="none")`: one `execute(x)` and one
`execute_many(X)` (k = 8) per matrix, each of the six formats forced
once on a matrix where it is legal, and `graph.pagerank`, `graph.bfs`
and `graph.sssp` on the R-MAT for a few iterations each.  BELL on FD is
the largest program: its 8x128 tiles take 12.9 GB of the chip's 16 GB.

`--chips 4`: only the row-sharded `shard_map` SpMV over a 4-device
`row_mesh()` on FD, compared with the one-chip plan on the same x, with
each shard's device printed.

Every result is checked against a float64 reference computed on the host
from the CSR arrays, and every plan's runner must compile to a Mosaic
kernel (`tpu_custom_call` in the compiled program).  No failure is
caught.  Each phase prints one JSON line: the format the plan chose, the
max error relative to the reference's largest magnitude, and host
set-up, compile and run seconds (runs end in `block_until_ready`;
compile seconds are the runner's first compile, served from the
persistent cache when an earlier run left it there).  The
last line is `{"ok": true, "device": {...}}` as JAX reports the device;
without a TPU the script exits 1 before doing any work.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

LOG2N = 22          # rows of both matrices: 2^22
TOL = 1e-4          # max |y - ref| / max |ref| for float32 against float64
K = 8               # vectors in the execute_many phase
ITERS = 3           # iterations of each graph analytic
FORMATS = ("dia", "bell", "ell", "csr", "csr-seg", "hyb")


def _now() -> float:
    return time.perf_counter()


def _emit(rec: dict) -> dict:
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------------------
# float64 host references, straight from the CSR arrays
# ---------------------------------------------------------------------------

def _coo(a):
    import numpy as np

    indptr = np.asarray(a.indptr, dtype=np.int64)
    rows = np.repeat(np.arange(a.n_rows, dtype=np.int64), np.diff(indptr))
    return (rows, np.asarray(a.indices, dtype=np.int64),
            np.asarray(a.data, dtype=np.float64))


def spmv_ref(a, x):
    """y = A x in float64."""
    import numpy as np

    rows, cols, vals = _coo(a)
    return np.bincount(rows, weights=vals * np.asarray(x, np.float64)[cols],
                       minlength=a.n_rows)


def pagerank_ref(a, n_iters: int, damping: float = 0.85):
    """Power iteration on A^T D_out^-1 from the uniform vector, dangling
    mass redistributed uniformly (the semantics of `graph.pagerank`)."""
    import numpy as np

    rows, cols, _ = _coo(a)
    n = a.n_rows
    out_deg = np.bincount(rows, minlength=n).astype(np.float64)
    tele = np.full(n, 1.0 / n)
    r = tele.copy()
    for _ in range(n_iters):
        y = np.bincount(cols, weights=r[rows] / out_deg[rows], minlength=n)
        leaked = r[out_deg == 0].sum()
        r = damping * (y + leaked * tele) + (1.0 - damping) * tele
    return r


def bfs_ref(a, source: int, n_iters: int):
    """Hop depths along edges i -> j (A[i, j] != 0), level-synchronous."""
    import numpy as np

    rows, cols, _ = _coo(a)
    depth = np.full(a.n_rows, np.inf)
    depth[source] = 0.0
    front = np.zeros(a.n_rows, dtype=bool)
    front[source] = True
    for level in range(1, n_iters + 1):
        hit = np.zeros(a.n_rows, dtype=bool)
        hit[cols[front[rows]]] = True
        front = hit & np.isinf(depth)
        depth[front] = level
    return depth


def sssp_ref(a, source: int, n_iters: int):
    """Bellman-Ford relaxations along edges i -> j weighted A[i, j]."""
    import numpy as np

    rows, cols, vals = _coo(a)
    dist = np.full(a.n_rows, np.inf)
    dist[source] = 0.0
    for _ in range(n_iters):
        cand = np.full(a.n_rows, np.inf)
        np.minimum.at(cand, cols, dist[rows] + vals)
        dist = np.minimum(dist, cand)
    return dist


def rel_err(y, ref) -> float:
    """max |y - ref| over the finite entries / max |ref| there; an entry
    finite in one and infinite in the other is an infinite error."""
    import numpy as np

    y = np.asarray(y, dtype=np.float64)
    fin = np.isfinite(ref)
    if not np.array_equal(fin, np.isfinite(y)):
        return float("inf")
    if not fin.any():
        return 0.0
    scale = max(float(np.abs(ref[fin]).max()), 1e-30)
    return float(np.abs(y[fin] - ref[fin]).max()) / scale


def _check(label: str, err: float) -> None:
    assert err <= TOL, f"{label}: max relative error {err:.3e} > {TOL:.0e}"


def _mosaic_compile(label: str, lowered, mosaic: bool) -> float:
    """Compile a lowered program; with `mosaic`, require a Mosaic kernel
    in it (an interpreted or reference path has no tpu_custom_call)."""
    t = _now()
    text = lowered.compile().as_text()
    if mosaic:
        assert "tpu_custom_call" in text, f"{label}: no Mosaic kernel"
    return _now() - t


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def spmv_phase(label: str, a, x, *, fmt=None, X=None, mosaic=True) -> dict:
    """Compile a plan (`fmt` forces the format), check its runner is a
    Mosaic kernel, run execute(x) (and execute_many(X) when given)."""
    from repro import plan

    t = _now()
    p = plan.compile(a, reorder="none", format=fmt)
    setup_s = _now() - t
    compile_s = _mosaic_compile(label, p.lower(x), mosaic)
    t = _now()
    y = p.execute(x).block_until_ready()
    first_s = _now() - t
    t = _now()
    y = p.execute(x).block_until_ready()
    run_s = _now() - t
    err = rel_err(y, spmv_ref(a, x))
    _check(label, err)
    rec = {"phase": label, "n": a.n_rows, "nnz": int(a.nnz),
           "format": p.format_name, "max_rel_err": err, "setup_s": setup_s,
           "compile_s": compile_s, "first_run_s": first_s, "run_s": run_s}
    if X is not None:
        t = _now()
        Y = p.execute_many(X).block_until_ready()
        rec["many_first_run_s"] = _now() - t
        t = _now()
        Y = p.execute_many(X).block_until_ready()
        rec["many_run_s"] = _now() - t
        rec["many_max_rel_err"] = max(rel_err(Y[i], spmv_ref(a, X[i]))
                                      for i in range(X.shape[0]))
        _check(label + " execute_many", rec["many_max_rel_err"])
    del p, y
    return _emit(rec)


def graph_phase(label: str, a, *, mosaic=True) -> dict:
    """Compile one analytic's plan into a cache of its own and compile
    its runner, then run the analytic's driver on that cache (which finds
    both) for ITERS iterations against the host reference."""
    import jax.numpy as jnp
    import numpy as np
    from repro import graph, plan
    from repro.graph.drivers import analytic_operand, plan_options

    source = int(np.argmax(np.diff(np.asarray(a.indptr))))   # the hub
    cache = plan.PlanCache()
    t = _now()
    matrix, semiring, _ = analytic_operand(label, a)
    p = cache.get_or_compile(matrix, **plan_options(semiring))
    setup_s = _now() - t
    del matrix
    x = jnp.zeros((p.n_cols,), jnp.float32)
    compile_s = _mosaic_compile(label, p.lower(x), mosaic)
    t = _now()
    if label == "pagerank":
        res = graph.pagerank(a, max_iters=ITERS, plan_cache=cache)
        ref = pagerank_ref(a, res.n_iters)
    elif label == "bfs":
        res = graph.bfs(a, source, max_iters=ITERS, plan_cache=cache)
        ref = bfs_ref(a, source, res.n_iters)
    else:
        res = graph.sssp(a, source, max_iters=ITERS, plan_cache=cache)
        ref = sssp_ref(a, source, res.n_iters)
    driver_s = _now() - t
    assert res.plan is p, f"{label}: the driver compiled another plan"
    err = rel_err(res.values, ref)
    _check(label, err)
    rec = {"phase": f"rmat/{label}", "n": a.n_rows, "format": p.format_name,
           "semiring": p.semiring, "iters": res.n_iters,
           "max_rel_err": err, "setup_s": setup_s, "compile_s": compile_s,
           "driver_s": driver_s}
    del res, p, cache
    return _emit(rec)


def _vectors(n: int, seed: int, k: int = 0):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed + 7)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    if not k:
        return x
    return x, jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))


def _generate(label: str, gen, n: int, seed: int):
    t = _now()
    a = gen(n, seed=seed)
    _emit({"phase": f"{label}/generate", "n": a.n_rows, "nnz": int(a.nnz),
           "setup_s": _now() - t})
    return a


def smoke(log2n: int, seed: int, mosaic: bool = True) -> list:
    """The one-chip phases; returns their records."""
    from repro.core.generators import fd_matrix, rmat_matrix

    recs = []
    n = 1 << log2n
    fd = _generate("fd", fd_matrix, n, seed)
    x, X = _vectors(n, seed, K)
    recs.append(spmv_phase("fd/auto", fd, x, X=X, mosaic=mosaic))
    auto = recs[-1]["format"]
    for fmt in ("csr", "dia", "ell", "bell"):   # the FD-legal formats
        if fmt != auto:
            recs.append(spmv_phase(f"fd/{fmt}", fd, x, fmt=fmt,
                                   mosaic=mosaic))
    del fd

    rmat = _generate("rmat", rmat_matrix, n, seed)
    recs.append(spmv_phase("rmat/auto", rmat, x, X=X, mosaic=mosaic))
    auto = recs[-1]["format"]
    for fmt in ("hyb", "csr-seg"):              # the nnz-balanced formats
        if fmt != auto:
            recs.append(spmv_phase(f"rmat/{fmt}", rmat, x, fmt=fmt,
                                   mosaic=mosaic))
    for analytic in ("pagerank", "bfs", "sssp"):
        recs.append(graph_phase(analytic, rmat, mosaic=mosaic))
    ran = {r["format"] for r in recs if "format" in r}
    missing = set(FORMATS) - ran
    assert not missing, f"formats that never ran: {sorted(missing)}"
    return recs


def smoke_sharded(log2n: int, seed: int, n_devices: int,
                  mosaic: bool = True) -> dict:
    """Row-sharded SpMV on a `n_devices` mesh over FD, against the
    one-chip plan's execute on the same x and the host reference."""
    import jax
    import numpy as np
    from repro import plan
    from repro.core.generators import fd_matrix
    from repro.distributed.spmv import pad_x, row_mesh, row_sharded_program

    n = 1 << log2n
    fd = _generate("fd", fd_matrix, n, seed)
    x = _vectors(n, seed)
    ref = spmv_ref(fd, x)
    one = plan.compile(fd, reorder="none")
    y1 = np.asarray(one.execute(x).block_until_ready())
    del one

    mesh = row_mesh(jax.devices()[:n_devices])
    t = _now()
    p = plan.compile(fd, reorder="none", mesh=mesh)
    setup_s = _now() - t
    prog = row_sharded_program(p.prep, mesh)
    args = (p.prep.data, p.prep.idx, pad_x(p.prep, x))
    compile_s = _mosaic_compile("sharded", prog.lower(*args), mosaic)
    slabs = prog(*args).block_until_ready()
    shards = sorted((s.index[0].start or 0, str(s.device))
                    for s in slabs.addressable_shards)
    print("# shards: " + ", ".join(f"part {i} -> {d}" for i, d in shards),
          flush=True)
    n_on = len({d for _, d in shards})
    assert n_on == n_devices, \
        f"sharded: y slabs sit on {n_on} devices, want {n_devices}"
    t = _now()
    y = p.execute(x).block_until_ready()
    first_s = _now() - t
    t = _now()
    y = p.execute(x).block_until_ready()
    run_s = _now() - t
    err = rel_err(y, ref)
    _check("sharded", err)
    vs_one = rel_err(y, y1.astype(np.float64))
    _check("sharded vs one chip", vs_one)
    return _emit({"phase": f"fd/ell-sharded x{n_devices}", "n": n,
                  "format": p.format_name, "max_rel_err": err,
                  "max_rel_diff_vs_one_chip": vs_one, "setup_s": setup_s,
                  "compile_s": compile_s, "first_run_s": first_s,
                  "run_s": run_s})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded path on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f"# {dev.device_kind} x{len(devices)}, 2^{LOG2N} rows, "
          f"seed {args.seed}, compile cache {cache_dir}", flush=True)
    t = _now()
    if args.chips == 4:
        smoke_sharded(LOG2N, args.seed, 4)
    else:
        smoke(LOG2N, args.seed)
    print(f"# total {_now() - t:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
