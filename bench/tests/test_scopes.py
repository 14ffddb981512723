"""The program's stage scopes and host spans as `bench/scopes.py` reads
them: the grouping of op names, a traced query's spans on the CPU, and
the traces recorded on a TPU v5e (`record_trace.py data/scoped`)."""
from __future__ import annotations

import json
import math
import pathlib

import jax
import pytest

from bench import registry, scopes, trace

SCOPED = pathlib.Path(__file__).resolve().parent / "data" / "scoped"
SPEC = registry.load_spec()


def test_the_names_are_the_programs():
    from repro import spans

    assert scopes.STAGES == spans.SCOPES
    assert scopes.SPANS == spans.SPANS


@pytest.mark.parametrize("op_name,group", [
    ("jit(spmv_hyb_prepared)/jit(spmv_ell_prepared)/jit(spmv_ell_pallas)/"
     "spmv.x_gather/jit(_take)/gather:", "spmv.x_gather"),
    ("jit(spmv_csr_seg_prepared)/spmv.merge/broadcast_in_dim;"
     "jit(spmv_csr_seg_prepared)/spmv.merge/reshape", "spmv.merge"),
    ("jit(f)/spmv.kernel/reshape;jit(f)/spmv.merge/reshape", "spmv.other"),
    ("jit(spmv_hyb_prepared)/jit(spmv_csr_seg_prepared)/scatter-add:",
     "spmv.other"),
    ("prep.block_cols", "(compiler)"),
    (None, "(compiler)"),
])
def test_an_op_falls_in_one_group(op_name, group):
    assert scopes.group_of(op_name) == group


def test_a_traced_query_nests_its_spans(tmp_path):
    from repro import graph, plan
    from repro.core.generators import rmat_matrix

    a = rmat_matrix(1 << 9, seed=5)
    cache = plan.PlanCache()
    graph.pagerank(a, tol=0.0, max_iters=2, plan_cache=cache)
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.query"):
            graph.pagerank(a, tol=0.0, max_iters=3, plan_cache=cache)
    jax.profiler.stop_trace()
    path = trace.find_xplane(tmp_path)
    out = scopes.summarize(path)
    spans = out["host_spans"]
    assert spans["bench.query"]["count"] == 2
    for name in ("graph.query", "graph.operand", "plan_cache.get",
                 "plan.fingerprint"):
        assert spans[name]["count"] == 2, name
    for name in ("graph.iteration", "spmv.execute", "graph.host_sync",
                 "graph.advance"):
        assert spans[name]["count"] == 6, name
    assert "plan.compile" not in spans          # both queries hit the cache
    for rec in spans.values():
        assert 0 <= rec["self_s"] <= rec["total_s"]
    # every span lies inside its parent
    from jax.profiler import ProfileData

    host = scopes._host_spans(ProfileData.from_file(str(path)), 0,
                              float("inf"))
    parent = {"graph.query": "bench.query", "graph.operand": "graph.query",
              "plan_cache.get": "graph.query",
              "plan.fingerprint": "plan_cache.get",
              "graph.iteration": "graph.query",
              "spmv.execute": "graph.iteration",
              "graph.host_sync": "graph.iteration",
              "graph.advance": "graph.iteration"}
    for s, e, name, line in host:
        if name in parent:
            assert any(p[2] == parent[name] and p[3] == line
                       and p[0] <= s and e <= p[1] for p in host), name


def test_idle_falls_to_the_innermost_span_by_overlap():
    spans = [(0, 100, "bench.query", "t"), (10, 60, "graph.query", "t"),
             (20, 50, "graph.operand", "t")]
    idle = scopes._span_idle(spans, [(0, 30), (55, 120)])
    assert idle == pytest.approx({"bench.query": 50e-9,
                                  "graph.query": 15e-9,
                                  "graph.operand": 10e-9,
                                  "(no span)": 20e-9})
    times = scopes._span_times(spans)
    assert times["graph.query"]["self_s"] == pytest.approx(20e-9)
    assert times["bench.query"]["self_s"] == pytest.approx(50e-9)


def _recorded(name):
    result = json.loads((SCOPED / f"{name}.result.json").read_text())
    return scopes.summarize(SCOPED / f"{name}.xplane.pb", 1), result


def test_recorded_spmv_trace_splits_the_program_by_stage():
    out, result = _recorded("rmat.spmv")
    (dev, groups), = out["scopes"].items()
    program_s = out["program_s"][dev]
    assert groups.get("spmv.other", 0.0) == 0.0
    staged = sum(groups.get(g, 0.0) for g in scopes.STAGES)
    assert staged + groups.get("(compiler)", 0.0) == \
        pytest.approx(program_s, rel=0.01)
    assert all(groups[g] > 0 for g in ("spmv.x_gather", "spmv.kernel",
                                       "spmv.merge"))
    # the in-run readers (the HLO of the live executables) and the trace's
    # own op metadata give the same milliseconds, up to the trace's
    # durations read in whole ns there and in ps here
    n = result["counts"]["multiplies"]
    for metric, stage in (("gather_ms_per_spmv", "spmv.x_gather"),
                          ("merge_ms_per_spmv", "spmv.merge")):
        assert result["metrics"][metric]["value"] == \
            pytest.approx(groups[stage] / n * 1e3, rel=1e-4)


@pytest.mark.parametrize("name", ["rmat.spmv", "rmat.pagerank"])
def test_recorded_program_spans_lie_inside_the_bench_spans(name):
    from jax.profiler import ProfileData

    host = scopes._host_spans(
        ProfileData.from_file(str(SCOPED / f"{name}.xplane.pb")), 0,
        float("inf"))
    bench = [s for s in host if s[2] in trace.SPANS]
    program = [s for s in host if s[2] in scopes.SPANS]
    assert bench and program
    for s, e, span, line in program:
        assert any(b[0] <= s and e <= b[1] for b in bench), span


def test_recorded_pagerank_trace_times_each_host_step():
    out, result = _recorded("rmat.pagerank")
    spans = out["host_spans"]
    queries = result["counts"]["queries"]
    assert spans["graph.query"]["count"] == queries
    assert spans["graph.operand"]["count"] == queries
    assert spans["plan_cache.get"]["count"] == queries
    assert spans["graph.iteration"]["count"] == \
        result["counts"]["iterations"]
    idle = sum(out["span_idle"].values())
    assert idle > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for metric, span in (("operand_ms_per_query.pagerank", "graph.operand"),
                         ("plan_lookup_ms_per_query.pagerank",
                          "plan_cache.get")):
        assert math.isfinite(got[metric]) and got[metric] > 0
        assert got[metric] == pytest.approx(
            spans[span]["total_s"] / queries * 1e3, rel=0.05)


@pytest.mark.parametrize("name", ["rmat.spmv", "rmat.pagerank"])
def test_recorded_results_hold_every_metric_the_cell_reports(name):
    _, result = _recorded(name)
    want = {m["name"] for m in registry.metrics_for(SPEC, "per_layer", name)}
    assert set(result["metrics"]) == want
    assert all(math.isfinite(v["value"]) for v in result["metrics"].values())
