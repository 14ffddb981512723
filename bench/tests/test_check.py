"""Whole runs on the CPU at a tiny size (the look for a chip skipped):
sound runs are correct, and a run whose timed path is broken underneath,
or has the bfloat16 control in the program's place, is not."""
from __future__ import annotations

import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, harness, reference, registry
from bench.tests.tiny import tiny_spec

SEED = 2 ** 31 + 3
CELLS = ["rmat.spmv", "fd.spmv", "rmat.pagerank"]


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny_spec(tmp_path_factory.mktemp("tiny"))


def _run(spec, name, trace=False):
    wl = registry.workload(spec, name)
    return harness.execute(spec, wl, SEED, 0.05, trace,
                           jax.devices()[: wl["chips"]],
                           time.perf_counter(), log=io.StringIO())


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(spec, name, trace):
    res = _run(spec, name, trace)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    if not trace:
        want = {m["name"] for m in registry.metrics_for(
            spec, "end_to_end", name)}
        assert set(res["metrics"]) == want
        assert all(v["value"] > 0 for v in res["metrics"].values())
    else:
        assert set(res["device"]) >= {"busy_s", "window_s"}
    for check in res["checks"].values():
        assert check["value"] <= check["limit"]


def _unchanged(execute):
    return lambda self, x, interpret=None: jnp.asarray(x)


def _half_left_out(execute):
    def broken(self, x, interpret=None):
        y = execute(self, x, interpret)
        return y.at[y.shape[0] // 2:].set(0.0)
    return broken


def _answer_altered(execute):
    def broken(self, x, interpret=None):
        y = execute(self, x, interpret)
        return y.at[y.shape[0] // 3].add(1e-2 * jnp.max(jnp.abs(y)))
    return broken


def _control(execute):
    def broken(self, x, interpret=None):
        a = self.csr
        host = gen.HostCSR(np.asarray(a.indptr), np.asarray(a.indices),
                           np.asarray(a.data), a.n_rows, a.n_cols)
        return jnp.asarray(reference.spmv_control(host, np.asarray(x)))
    return broken


FAULTS = {"unchanged": _unchanged, "half_left_out": _half_left_out,
          "answer_altered": _answer_altered, "bf16_control": _control}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(spec, name, fault,
                                            monkeypatch):
    from repro.plan.plan import SpmvPlan

    monkeypatch.setattr(SpmvPlan, "execute", FAULTS[fault](SpmvPlan.execute))
    res = _run(spec, name)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


def test_a_pagerank_step_that_keeps_its_state_is_not_correct(spec,
                                                             monkeypatch):
    from repro.graph.drivers import PageRankStepper

    monkeypatch.setattr(PageRankStepper, "advance", lambda self, y: 1.0)
    assert _run(spec, "rmat.pagerank")["correct"] is False
