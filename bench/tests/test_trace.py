"""The trace reduction on small traces recorded on a TPU v5e
(`record_trace.py`), the peak table, and the roofline's floor."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import gen, registry, trace, work
from bench.tests.test_gen import FD, RMAT

DATA = pathlib.Path(__file__).resolve().parent / "data"
SPEC = registry.load_spec()
PEAK = work.peaks("TPU v5 lite")


def _recorded(name):
    summary = trace.reduce(DATA / f"{name}.xplane.pb", 1)
    result = json.loads((DATA / f"{name}.result.json").read_text())
    return summary, result


def _reading(summary, result):
    return trace.Reading(trace=summary, counts=result["counts"],
                         info=result["info"], chips=1, peak=PEAK)


def test_spmv_trace_splits_kernels_from_the_gather():
    s, result = _recorded("rmat.spmv")
    dev = s.busiest()
    assert s.spans == {"bench.multiply": result["attempted"]}
    assert s.spans["bench.multiply"] >= 2
    assert 0 < dev.program_s <= dev.busy_s <= s.window_s
    assert dev.mosaic_s > 0 and dev.xla_s > 0
    assert dev.bench_s > 0          # the chain's normalisation, kept apart
    assert dev.mosaic_s + dev.xla_s >= dev.program_s * 0.99
    ops = dict(s.breakdown()["device_ops"])
    assert len(ops) <= trace.TOP
    assert any("spmv_ell_pallas" in k for k in ops)
    assert any("spmv_csr_seg_pallas" in k for k in ops)
    gaps = s.breakdown()["idle_gaps"]
    assert gaps and all(sec > 0 for _, sec in gaps)


RECORDED = sorted(p.name.split(".xplane")[0] for p in DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("name", RECORDED)
def test_readers_reproduce_the_recorded_result(name):
    s, result = _recorded(name)
    r = _reading(s, result)
    got = {m["name"]: registry.load_module("metrics", m["name"]).read(r)
           for m in registry.metrics_for(SPEC, "per_layer", name)}
    want = {k: v["value"] for k, v in result["metrics"].items()}
    assert {k: v for k, v in got.items() if v is not None} == \
        pytest.approx(want, rel=1e-12)
    assert result["device"]["busy_s"] == pytest.approx(s.busy_s)


def test_pagerank_readers_split_the_window_into_host_and_device():
    dev = trace.DeviceTime("d", 30.0, 30.0, 1.0, 29.0, 0.0, {}, [])
    s = trace.TraceSummary(40.0, {"bench.query": 1}, [dev], [])
    r = trace.Reading(s, {"iterations": 5}, {}, 1, PEAK)
    read = {name: registry.load_module("metrics", name).read(r)
            for name in ("idle_share.pagerank", "host_ms_per_iter.pagerank",
                         "idle_share.spmv", "xla_ms_per_spmv")}
    assert read["idle_share.pagerank"] == pytest.approx(25.0)
    assert read["host_ms_per_iter.pagerank"] == pytest.approx(2000.0)
    assert read["idle_share.spmv"] is None      # no multiplies traced
    assert read["xla_ms_per_spmv"] is None


def test_peaks_refuse_an_unknown_device():
    assert PEAK["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")


@pytest.mark.parametrize("cfg,fmt", [(FD, f) for f in
                                     ("dia", "bell", "ell", "csr")]
                         + [(RMAT, f) for f in ("hyb", "csr-seg", "csr")],
                         ids=lambda v: v if isinstance(v, str) else
                         v["structure"])
def test_roofline_stays_under_100_for_every_layout(cfg, fmt):
    from repro import plan

    a = gen.generate(dict(cfg, log2_rows=8), seed=4)
    p = plan.compile(gen.to_program(a), reorder="none", format=fmt,
                     interpret=True)
    must_move = work.layout_bytes(p.prep) + 4 * (a.n_rows + a.n_cols)
    assert work.spmv_floor_bytes(a.nnz, a.n_rows, a.n_cols) <= must_move
    # a device that moved just that at peak reads at most 100%
    dev = trace.DeviceTime("d", 1.0, must_move / PEAK["hbm_bytes_per_s"],
                           0.0, 0.0, 0.0, {}, [])
    s = trace.TraceSummary(1.0, {"bench.multiply": 1}, [dev], [])
    r = trace.Reading(s, {}, {"nnz": a.nnz, "n_rows": a.n_rows,
                              "n_cols": a.n_cols}, 1, PEAK)
    assert 0 < registry.load_module("metrics", "spmv_roofline").read(r) <= 100
