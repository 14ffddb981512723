"""The sharded, resumable sweep runner's contract, locked down.

`repro.telemetry.runner` promises bit-identical results no matter how a
sweep is executed: serial or sharded across worker processes, straight
through or killed-and-resumed from a checkpoint, cells listed once or
twice, axes enumerated in any order.  Everything here compares the
*canonical JSON payloads* (`encode_point`) byte-for-byte -- value-close
is not good enough for a resume contract.

Also here: the sorted-enumeration pin (checkpoint keys are an on-disk
format; reordering the cell sort silently orphans old checkpoints) and
the empty/single-cell report-helper regressions.
"""
import dataclasses

import pytest

from repro.parallel import ParallelSpec
from repro.telemetry import runner
from repro.telemetry.report import (gap_report, graph_gap_report,
                                    graph_report, partition_gap_report,
                                    plan_cache_report, scaling_gap_report,
                                    scaling_report, to_csv, to_markdown)
from repro.telemetry.runner import (SweepCell, SweepConfig, decode_point,
                                    encode_point, execute_cells, graph_cells,
                                    mech_cells, scaling_cells, sort_cells)

# Tiny scaled grid: big enough to shard, small enough to run in seconds.
SCALED = ParallelSpec(l2_bytes=16 * 1024, llc_bytes=64 * 1024)
CFG = SweepConfig(parallel_spec=SCALED, sweeps=1)
GRID = scaling_cells(log2ns=(7,), kinds=("fd", "rmat"),
                     threads_list=(1, 2), partition="balanced")


def _payloads(points):
    return [encode_point(p) for p in points]


# ---------------------------------------------------------------------------
# sorted, deduplicated, order-independent enumeration (pinned)
# ---------------------------------------------------------------------------


def test_enumeration_order_independent():
    a = mech_cells(log2ns=(8, 7), kinds=("rmat", "fd"),
                   mechanisms=("victim-cache", "baseline"),
                   threads_list=(2, 1))
    b = mech_cells(log2ns=(7, 8), kinds=("fd", "rmat"),
                   mechanisms=("baseline", "victim-cache"),
                   threads_list=(1, 2))
    assert a == b == sort_cells(a)
    assert len(a) == len(set(a)) == 2 * 2 * 2 * 2
    assert scaling_cells((7,), ("rmat", "fd", "rmat"), (2, 1, 2)) == \
        scaling_cells((7,), ("fd", "rmat"), (1, 2))


def test_cell_keys_pinned():
    """Checkpoint keys are an on-disk format: changing `SweepCell.key()`
    or the sort orphans every existing checkpoint.  Pin both."""
    assert [c.key() for c in GRID] == [
        "scaling|fd|7|none|-|1|balanced|-|-",
        "scaling|fd|7|none|-|2|balanced|-|-",
        "scaling|rmat|7|none|-|1|balanced|-|-",
        "scaling|rmat|7|none|-|2|balanced|-|-",
    ]
    g = graph_cells((6,), ("fd",), ("pagerank",))
    assert [c.key() for c in g] == ["graph|fd|6|none|-|1|-|-|pagerank"]
    m = mech_cells((7,), ("fd",), ("baseline",))
    assert [c.key() for c in m] == ["mech|fd|7|none|-|1|-|baseline|-"]


def test_keys_unique_across_sweeps():
    cells = (GRID + mech_cells((7,), ("fd", "rmat"), ("baseline",))
             + graph_cells((6,), ("fd",), ("bfs", "pagerank")))
    keys = [c.key() for c in cells]
    assert len(keys) == len(set(keys))


# ---------------------------------------------------------------------------
# payload round-trips (value-exact both directions)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", [
    SweepCell(sweep="mech", kind="rmat", log2n=7, mechanism="victim-cache"),
    SweepCell(sweep="scaling", kind="rmat", log2n=7, threads=2,
              partition="balanced"),
    SweepCell(sweep="graph", kind="fd", log2n=6, analytic="pagerank"),
    SweepCell(sweep="label", kind="banded", log2n=7, reorder="rcm",
              threads=2, mechanism="scaled"),
], ids=["mech", "scaling", "graph", "label"])
def test_encode_decode_roundtrip(cell):
    cfg = dataclasses.replace(CFG, max_iters=4)
    p = runner.run_cell(cell, cfg)
    blob = encode_point(p)
    q = decode_point(blob)
    assert q == p
    assert encode_point(q) == blob


def test_label_cells_ride_the_runner():
    """The cost-model labeler is a fourth sweep family: `run_cell`
    dispatches on sweep='label' (geometry label riding the `mechanism`
    field, seed from the config) and returns the exact row the direct
    entry point produces."""
    from repro.plan.costmodel import label_cells, run_label_cell

    cells = label_cells(kinds=("banded",), log2ns=(7,), threads_list=(2,),
                        reorders=("none",), specs=("scaled",))
    assert [c.key() for c in cells] == \
        ["label|banded|7|none|-|2|-|scaled|-"]
    cfg = SweepConfig(seed=5, sweeps=1)
    got = runner.run_cell(cells[0], cfg)
    want = run_label_cell("banded", 7, "none", 2, spec_label="scaled",
                          seed=5, sweeps=1)
    assert got == want and got.seed == 5


# ---------------------------------------------------------------------------
# execution equivalence: duplicates, interrupts, shards
# ---------------------------------------------------------------------------


def test_duplicate_cells_idempotent():
    once = _payloads(execute_cells(GRID, CFG))
    twice = _payloads(execute_cells(list(GRID) + list(GRID), CFG))
    assert twice == once
    assert len(once) == len(GRID)


def test_interrupt_and_resume_bit_identical(tmp_path):
    """Kill the runner after K cells; a resumed run must be
    byte-identical to one that never stopped."""
    straight = _payloads(execute_cells(GRID, CFG))

    ckpt = str(tmp_path / "ckpt")
    first = execute_cells(GRID, CFG, ckpt_dir=ckpt, checkpoint_every=1,
                          max_cells=2)
    assert len(first) == 2                      # the "killed" run
    resumed = execute_cells(GRID, CFG, ckpt_dir=ckpt)
    assert _payloads(resumed) == straight


def test_resume_skips_completed_cells(tmp_path, monkeypatch):
    """A complete checkpoint means zero recomputation on resume."""
    ckpt = str(tmp_path / "ckpt")
    want = _payloads(execute_cells(GRID, CFG, ckpt_dir=ckpt))

    def boom(cell, cfg):
        raise AssertionError(f"recomputed {cell.key()}")

    monkeypatch.setattr(runner, "run_cell", boom)
    again = execute_cells(GRID, CFG, ckpt_dir=ckpt)
    assert _payloads(again) == want


def test_no_resume_ignores_checkpoint(tmp_path, monkeypatch):
    ckpt = str(tmp_path / "ckpt")
    execute_cells(GRID[:1], CFG, ckpt_dir=ckpt)
    seen = []
    real = runner.run_cell
    monkeypatch.setattr(runner, "run_cell",
                        lambda cell, cfg: seen.append(cell) or real(cell, cfg))
    execute_cells(GRID[:1], CFG, ckpt_dir=ckpt, resume=False)
    assert seen == list(GRID[:1])


def test_checkpoint_only_returns_requested_cells(tmp_path):
    """A checkpoint holding extra cells does not leak them into the
    result -- only the requested grid comes back, in canonical order."""
    ckpt = str(tmp_path / "ckpt")
    execute_cells(GRID, CFG, ckpt_dir=ckpt)
    sub = [c for c in GRID if c.kind == "fd"]
    pts = execute_cells(sub, CFG, ckpt_dir=ckpt)
    assert [(p.kind, p.threads) for p in pts] == [("fd", 1), ("fd", 2)]


@pytest.mark.parametrize("workers", [2, 4])
def test_workers_bit_identical_to_serial(workers):
    serial = _payloads(execute_cells(GRID, CFG, workers=1))
    sharded = _payloads(execute_cells(GRID, CFG, workers=workers))
    assert sharded == serial


def test_spawn_workers_run_jax_on_the_cpu(monkeypatch):
    """Workers are pinned to the CPU before JAX starts a backend, so a
    parent holding an accelerator never shares it with them: even with
    an accelerator platform in the inherited environment, a worker's
    JAX comes up on the CPU."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    import jax

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with ProcessPoolExecutor(max_workers=1,
                             mp_context=multiprocessing.get_context("spawn"),
                             initializer=runner._cpu_worker) as pool:
        assert pool.submit(jax.default_backend).result(timeout=120) == "cpu"


def test_workers_resume_bit_identical(tmp_path):
    """Interrupt serially, finish sharded: still byte-identical."""
    straight = _payloads(execute_cells(GRID, CFG))
    ckpt = str(tmp_path / "ckpt")
    execute_cells(GRID, CFG, ckpt_dir=ckpt, checkpoint_every=1, max_cells=1)
    resumed = execute_cells(GRID, CFG, ckpt_dir=ckpt, workers=2)
    assert _payloads(resumed) == straight


def test_thin_clients_match_runner():
    """`scaling_sweep` is a thin client of the runner: same cells, same
    payloads."""
    from repro.telemetry.sweep import scaling_sweep

    pts = scaling_sweep(log2ns=(7,), threads_list=(1, 2), spec=SCALED,
                        partition="balanced", sweeps=1)
    assert _payloads(pts) == _payloads(execute_cells(GRID, CFG))


# ---------------------------------------------------------------------------
# report helpers on empty / single-cell results (regressions)
# ---------------------------------------------------------------------------


def test_reports_empty_inputs_well_formed():
    for fn in (to_csv, to_markdown, gap_report, scaling_report,
               scaling_gap_report, partition_gap_report, graph_report,
               graph_gap_report):
        out = fn([])
        assert isinstance(out, str) and out.strip()


def test_plan_cache_report_empty_stats():
    out = plan_cache_report({})
    assert "0" in out and len(out.splitlines()) >= 2
    # windowed view with a missing counter key must not KeyError either
    assert plan_cache_report({"hits": 3}, before={})


def test_reports_single_cell():
    pts = execute_cells(GRID[:1], CFG)
    assert len(pts) == 1
    assert str(pts[0].threads) in scaling_report(pts)
    assert scaling_gap_report(pts)          # one kind only: no gap rows
    assert partition_gap_report(pts)        # one partition only


def test_graph_point_empty_iters_row():
    from repro.telemetry.sweep import GraphPoint

    p = GraphPoint(kind="fd", log2n=6, nnz=0, analytic="bfs",
                   semiring="boolean", n_iters=0, converged=False,
                   format_name="csr", iters=())
    row = p.row()
    assert len(row) == len(GraphPoint.header())
    assert graph_report([p])
