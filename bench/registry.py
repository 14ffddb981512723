"""Find the benchmark's parts by the names in `BENCHMARK.json`.

A configuration is `configs/<name>.json`, a traffic mix
`traffic/<name>.json`, the code that drives a mix `ops/<op>.py`, a
matrix structure `structures/<structure>.py` and a per-layer metric's
reader `metrics/<name>.py`.  Adding any of them takes new files and
entries only.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    return _named(spec["workloads"], name, "workload")


def config(spec: dict, name: str) -> dict:
    entry = _named(spec["configs"], name, "config")
    return json.loads((ROOT / entry["file"]).read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def metrics_for(spec: dict, kind: str, workload_name: str) -> list:
    """The `end_to_end` or `per_layer` entries a workload reports."""
    return [m for m in spec[kind]
            if workload_name in m.get("workloads", [workload_name])]


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module (names may hold dots)."""
    mod_name = f"bench.{kind}.{name}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
