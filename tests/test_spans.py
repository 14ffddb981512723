"""Stage scopes in the runners' compiled HLO, and the host spans' timing.

Every op a runner program runs on the device with an op_name the program
wrote (`repro.spans.staged_ops`) lies in exactly one stage scope of
`repro.spans.SCOPES`, and every gather in `spmv.x_gather`.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from repro import graph, plan
from repro.core.generators import fd_matrix, rmat_matrix
from repro.spans import SCOPES, collect, scope_of, span, staged_ops

def gather_op_names(hlo_text: str) -> list:
    """op_names of every gather instruction, fused ones included."""
    return re.findall(r"\sgather\(.*?op_name=\"([^\"]*)\"", hlo_text)


def _assert_staged(texts: list, gathers: bool = True) -> set:
    seen = set()
    for text in texts:
        for instr, op_name in staged_ops(text).items():
            assert scope_of(op_name) is not None, (instr, op_name)
            seen.add(scope_of(op_name))
    all_gathers = [n for t in texts for n in gather_op_names(t)]
    if gathers:
        assert all_gathers
    assert all(scope_of(n) == "spmv.x_gather" for n in all_gathers), \
        all_gathers
    return seen


_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import jax.numpy as jnp
    from repro import plan
    from repro.core.generators import fd_matrix
    from repro.distributed import spmv as ds

    assert len(jax.devices()) == 4
    a = fd_matrix(1 << 8)
    p = plan.compile(a, reorder="none", mesh=ds.row_mesh(), interpret=True)
    x = jnp.ones(a.n_cols, jnp.float32)
    xp = ds.pad_x(p.prep, x)
    slabs = ds.row_sharded_program(p.prep, p.mesh, True)(
        p.prep.data, p.prep.idx, xp)
    rows = tuple(int(r) for r in p.prep.starts[1:] - p.prep.starts[:-1])
    texts = [
        ds._pad_to.lower(x, size=xp.shape[0]).compile().as_text(),
        ds.row_sharded_program(p.prep, p.mesh, True).lower(
            p.prep.data, p.prep.idx, xp).compile().as_text(),
        ds._stitch.lower(slabs, rows=rows,
                         n_rows=p.prep.n_rows).compile().as_text()]
    y = p.execute(x)
    ok = bool(jnp.allclose(y, plan.compile(a, reorder="none").execute(x),
                           rtol=1e-5))
    print(json.dumps({"texts": texts, "ok": ok}))
""")


def _sharded_texts() -> list:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"]
    return out["texts"]


CASES = [("dia", "fd", "plus_times"), ("bell", "fd", "plus_times"),
         ("ell", "fd", "plus_times"), ("csr", "fd", "plus_times"),
         ("csr-seg", "rmat", "plus_times"), ("hyb", "rmat", "plus_times"),
         ("hyb", "rmat", "min_plus"), ("ell-sharded", "fd", "plus_times")]


@pytest.mark.parametrize("fmt,matrix,semiring", CASES,
                         ids=[f"{f}-{s}" for f, _, s in CASES])
def test_every_runner_op_lies_in_one_stage_scope(fmt, matrix, semiring):
    if fmt == "ell-sharded":
        seen = _assert_staged(_sharded_texts())
        assert {"spmv.kernel", "spmv.stitch"} <= seen
        return
    a = fd_matrix(1 << 8) if matrix == "fd" else rmat_matrix(1 << 9, seed=1)
    p = plan.compile(a, reorder="none", format=fmt, interpret=True,
                     semiring=semiring, n_stripes=2 if fmt == "csr" else 1)
    text = p.lower(jnp.ones(a.n_cols, jnp.float32)).compile().as_text()
    seen = _assert_staged([text], gathers=fmt not in ("dia", "bell"))
    assert "spmv.kernel" in seen and seen <= set(SCOPES) - {"spmv.stitch"}


def test_span_times_into_stats_and_open_collectors():
    stats = {}
    with collect() as outer:
        with span("plan.compile", stats, "compile_s") as s:
            with collect() as inner, span("plan.reorder", stats,
                                          "reorder_s"):
                pass
        with span("plan.compile", stats, "compile_s"):
            pass
    assert s.seconds > 0 and stats["reorder_s"] <= s.seconds
    assert stats["compile_s"] >= s.seconds
    assert set(inner) == {"plan.reorder"}
    assert outer["plan.compile"] == pytest.approx(stats["compile_s"])
    assert outer["plan.reorder"] == pytest.approx(stats["reorder_s"])
    with span("plan.compile"):                    # nothing open: no record
        pass
    assert set(outer) == {"plan.compile", "plan.reorder"}


def test_compile_stats_and_cache_stats_keep_their_keys():
    a = rmat_matrix(1 << 9, seed=3)
    auto = plan.compile(a, reorder="none")
    assert set(auto.compile_stats) == {"reorder_s", "analyze_s", "scoring",
                                       "predict_s", "convert_s", "prepare_s",
                                       "xla_elements",
                                       "xla_elements_by_format"}
    forced = plan.compile(a, reorder="none", format="csr")
    assert set(forced.compile_stats) == {"reorder_s", "scoring",
                                         "predict_s", "convert_s",
                                         "prepare_s", "xla_elements"}
    assert all(v >= 0 for k, v in forced.compile_stats.items()
               if k.endswith("_s"))
    cache = plan.PlanCache()
    cache.get_or_compile(a, reorder="none")
    cache.get_or_compile(a, reorder="none")
    stats = cache.stats()
    assert set(stats) == {
        "plans", "hits", "misses", "evictions", "compiles", "compile_s",
        "predictor_compiles", "predictor_compile_s", "oracle_compiles",
        "oracle_compile_s", "overlays", "swaps", "delta_recompiles",
        "hit_rate"}
    assert (stats["hits"], stats["misses"], stats["compiles"]) == (1, 1, 1)
    assert stats["compile_s"] > 0


def test_a_query_times_its_host_steps():
    a = rmat_matrix(1 << 9, seed=4)
    cache = plan.PlanCache()
    cold = graph.pagerank(a, tol=0.0, max_iters=3, plan_cache=cache)
    warm = graph.pagerank(a, tol=0.0, max_iters=3, plan_cache=cache)
    (a1, cold_s), (a2, h) = graph.drivers.recent_queries()[-2:]
    assert a1 == a2 == "pagerank"
    assert "plan.compile" in cold_s and "plan.compile" not in h
    assert {"graph.query", "graph.operand", "plan_cache.get",
            "plan.fingerprint", "graph.iteration", "spmv.execute",
            "graph.host_sync", "graph.advance"} <= set(h)
    assert h["plan.fingerprint"] <= h["plan_cache.get"]
    assert h["graph.operand"] + h["plan_cache.get"] + h["graph.iteration"] \
        <= h["graph.query"]
    assert h["spmv.execute"] + h["graph.host_sync"] + h["graph.advance"] \
        <= h["graph.iteration"]
    graph.bfs(a, 0, plan_cache=cache)
    assert graph.drivers.recent_queries()[-1][0] == "bfs"
    assert jnp.allclose(cold.values, warm.values)
