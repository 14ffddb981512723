"""The SpMM cell (`products.spmm`) on the CPU at a tiny size: a sound run
is correct and reports its metrics, a run whose `SpmvPlan.spmm` is
broken underneath (or is the bfloat16 control) is not; the reference and
its control; the SpMM readers' grouping of HLO ops, and the trace
recorded on a TPU v5e (`record_spmm_trace.py`)."""
from __future__ import annotations

import io
import json
import math
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, harness, reference_spmm, registry, scopes, \
    spmm_scopes, trace, work, work_spmm
from bench.tests.tiny import tiny_spec

SEED = 2 ** 31 + 5
CELL = "products.spmm"
SPMM = pathlib.Path(__file__).resolve().parent / "data" / "spmm"


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny_spec(tmp_path_factory.mktemp("tiny"))


def _run(spec, trace_=False):
    wl = registry.workload(spec, CELL)
    return harness.execute(spec, wl, SEED, 0.05, trace_,
                           jax.devices()[: wl["chips"]],
                           time.perf_counter(), log=io.StringIO())


@pytest.mark.parametrize("trace_", [False, True], ids=["trace0", "trace1"])
def test_a_sound_run_is_correct(spec, trace_):
    res = _run(spec, trace_)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    if not trace_:
        assert set(res["metrics"]) == {"spmv_gflops", "plan_s", "setup_s"}
        assert all(v["value"] > 0 for v in res["metrics"].values())
    check = res["checks"]["max_entry_err"]
    assert check["value"] <= check["limit"]


def _unchanged(spmm):
    return lambda self, x, interpret=None: jnp.asarray(x)


def _half_left_out(spmm):
    def broken(self, x, interpret=None):
        y = spmm(self, x, interpret)
        return y.at[y.shape[0] // 2:].set(0.0)
    return broken


def _one_row_altered(spmm):
    def broken(self, x, interpret=None):
        y = spmm(self, x, interpret)
        return y.at[y.shape[0] // 3].add(1e-2 * jnp.max(jnp.abs(y)))
    return broken


def _control(spmm):
    def broken(self, x, interpret=None):
        a = self.csr
        host = gen.HostCSR(np.asarray(a.indptr), np.asarray(a.indices),
                           np.asarray(a.data), a.n_rows, a.n_cols)
        return jnp.asarray(reference_spmm.columns_control(host,
                                                          np.asarray(x)))
    return broken


FAULTS = {"unchanged": _unchanged, "half_left_out": _half_left_out,
          "one_row_altered": _one_row_altered, "bf16_control": _control}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(spec, fault, monkeypatch):
    from repro.plan.plan import SpmvPlan

    monkeypatch.setattr(SpmvPlan, "spmm", FAULTS[fault](SpmvPlan.spmm))
    res = _run(spec)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


def test_a_program_without_spmm_fails_before_set_up(spec, monkeypatch):
    from repro.plan.plan import SpmvPlan

    monkeypatch.delattr(SpmvPlan, "spmm")
    t = time.perf_counter()
    with pytest.raises(RuntimeError, match="spmm"):
        _run(spec)
    assert time.perf_counter() - t < 5.0


def test_the_reference_passes_the_program_and_fails_its_control():
    cfg = dict(registry.config(registry.load_spec(), "ogbn-products-kron"
                               ".2p21"), log2_rows=10)
    a = gen.generate(cfg, SEED)
    from repro import plan

    p = plan.compile(gen.to_program(a), reorder="none")
    X = np.asarray(jax.random.normal(jax.random.key(1), (a.n_cols, 32)))
    limit = registry.traffic("spmm_chain256")["limits"]["max_entry_err"]
    rows = np.arange(0, a.n_rows, 7)
    feed = reference_spmm.feeding(a, rows)
    for y, yr in ((np.asarray(p.spmm(jnp.asarray(X))), None),
                  (reference_spmm.columns_control(a, X),
                   reference_spmm.rows_control(a, rows, feed, X[feed]))):
        ref, mag = reference_spmm.columns(a, X)
        err = reference_spmm.entry_err(y, ref, mag)
        ref_r, mag_r = reference_spmm.rows(a, rows, feed, X[feed])
        err_r = reference_spmm.entry_err(y[rows] if yr is None else yr,
                                         ref_r, mag_r)
        if yr is None:
            assert max(err, err_r) < limit / 10
        else:
            assert min(err, err_r) > limit


def test_the_floor_counts_values_x_and_y_once():
    peak = work.peaks("TPU v5 lite")
    nnz, n, k = 108_000_000, 1 << 21, 256
    assert work_spmm.spmm_floor_bytes(nnz, n, n, k) == 4 * (nnz + 2 * n * k)
    assert work_spmm.spmm_floor_seconds(nnz, n, n, k, peak) == \
        pytest.approx(work_spmm.spmm_floor_bytes(nnz, n, n, k)
                      / peak["hbm_bytes_per_s"])
    assert work_spmm.spmm_flops(nnz, k) == 2 * nnz * k


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

HLO = """\
  %gather.1 = f32[8,256]{1,0} gather(%p, %i), metadata={op_name="jit(spmm_prepared)/while/body/closed_call/spmm.x_gather/jit(_take)/gather"}
  %k.2 = f32[64,256]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmm_prepared)/while/body/closed_call/jit(spmm_rows_pallas)/spmm.kernel/spmm_rows_pallas/pallas_call"}
  %while.3 = (s32[], f32[64,256]{1,0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(spmm_prepared)/while"}
  %slice.4 = f32[60,256]{1,0} slice(%w), slice={[0:60], [0:256]}, metadata={op_name="jit(spmm_prepared)/spmm.merge/slice"}
  %fusion.5 = f32[8]{0} fusion(%q), kind=kLoop, calls=%f, metadata={op_name="jit(spmv_csr_prepared)/spmv.x_gather/gather"}
"""


def test_hlo_ops_group_by_spmm_stage_and_leave_the_loop_out():
    ops = spmm_scopes.hlo_ops(HLO)
    assert ops == {"gather.1": ("spmm.x_gather", False),
                   "k.2": ("spmm.kernel", True),
                   "slice.4": ("spmm.merge", False),
                   "fusion.5": (None, False)}
    assert spmm_scopes.stage_of("jit(f)/spmm.kernel/a;jit(f)/spmm.merge/b") \
        is None


def _recorded():
    result = json.loads((SPMM / f"{CELL}.result.json").read_text())
    return SPMM / f"{CELL}.xplane.pb", result


def test_recorded_spmm_trace_splits_the_program_by_stage():
    """The in-run readers (the HLO of the live executables) against the
    trace: the kernel's Mosaic time; Mosaic and XLA together are the SpMM
    program's device time, the loop's own instruction left out; and the
    ops the trace's metadata puts in an SpMM stage hold nearly all of
    it."""
    path, result = _recorded()
    s = trace.reduce(path, 1)
    n = result["counts"]["multiplies"]
    assert s.spans == {"bench.multiply": n} and n >= 2
    from jax.profiler import ProfileData

    bench = [h for h in scopes._host_spans(ProfileData.from_file(str(path)),
                                           0, float("inf"))
             if h[2] in trace.SPANS]
    lo, hi = min(h[0] for h in bench), max(h[1] for h in bench)
    stages = {}
    for name, plane in scopes._planes(path.read_bytes()):
        if name != s.devices[0].name:
            continue
        for t0, t1, program, tf_op in scopes._device_ops(plane):
            t0, t1 = max(t0, lo), min(t1, hi)
            stage = spmm_scopes.stage_of(tf_op)
            if t1 > t0 and stage and \
                    not program.startswith(trace.BENCH_MODULE):
                stages[stage] = stages.get(stage, 0.0) + (t1 - t0) * 1e-9
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(got) == {m["name"] for m in registry.metrics_for(
        registry.load_spec(), "per_layer", CELL)}
    assert all(math.isfinite(v) and v > 0 for v in got.values())
    assert stages["spmm.x_gather"] > 0 and stages["spmm.kernel"] > 0
    dev = s.busiest()
    assert got["mosaic_ms_per_spmm"] == pytest.approx(
        dev.mosaic_s / n * 1e3, rel=1e-3)
    total = got["mosaic_ms_per_spmm"] + got["xla_ms_per_spmm"]
    assert total == pytest.approx(dev.program_s / n * 1e3, rel=1e-3)
    # the staged ops hold all but the compiler's own copies
    assert 0.95 * total < sum(stages.values()) / n * 1e3 <= total
    assert 0 < got["spmm_roofline"] < 100
    assert got["idle_share.spmm"] == pytest.approx(
        100 * (1 - s.busy_s / s.window_s), rel=1e-9)
