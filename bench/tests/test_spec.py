"""`BENCHMARK.json` against the benchmark's contract, and every part it
names found by that name."""
from __future__ import annotations

import inspect
import json
import re

import pytest

from bench import registry, trace

SPEC = registry.load_spec()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((registry.ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_units_and_one_of_each():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[kind]]
        assert len(names) == len(set(names)), kind
        assert all(NAME.fullmatch(n) for n in names), kind
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_files_hold_what_the_entry_says(entry):
    assert entry["file"].startswith("bench/configs/")
    cfg = registry.config(SPEC, entry["name"])
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) <= set(cfg)
    registry.load_module("structures", cfg["structure"])
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_parts_and_reports_enough(wl):
    registry.config(SPEC, wl["config"])
    op = registry.load_module("ops", registry.traffic(wl["traffic"])["op"])
    for fn in ("setup", "window", "release", "check", "control"):
        assert callable(getattr(op, fn))
    assert wl["chips"] in (1, 4) and 0 < len(wl["why"]) <= 200
    e2e = [m["name"] for m in registry.metrics_for(SPEC, "end_to_end",
                                                   wl["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert registry.metrics_for(SPEC, "per_layer", wl["name"])
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert pairs.count((wl["config"], wl["traffic"])) == 1


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_readers_are_found_by_name(m):
    reader = registry.load_module("metrics", m["name"])
    assert list(inspect.signature(reader.read).parameters) == ["r"]
    assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    for cell in m["workloads"]:
        assert m["moves"] in [e["name"] for e in registry.metrics_for(
            SPEC, "end_to_end", cell)]
    # with nothing to read, a reader returns nothing, never 0
    empty = trace.Reading(trace=None, counts={}, info={}, chips=1,
                          peak=None)
    assert reader.read(empty) is None


def test_an_unknown_part_is_an_error():
    with pytest.raises(KeyError):
        registry.workload(SPEC, "no.such.cell")
    with pytest.raises(FileNotFoundError):
        registry.load_module("metrics", "no_such_metric")
    json.loads((registry.BENCH / "peaks.json").read_text())
