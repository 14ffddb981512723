"""The benchmark's spec with every configuration cut to a size the CPU
runs in seconds (interpret-mode kernels), for the tests."""
from __future__ import annotations

import copy
import json

from bench import registry

TINY_LOG2_ROWS = {"fd9": 8, "rmat": 10}


def tiny_spec(tmp_path) -> dict:
    spec = copy.deepcopy(registry.load_spec())
    for entry in spec["configs"]:
        cfg = registry.config(spec, entry["name"])
        cfg["log2_rows"] = TINY_LOG2_ROWS[cfg["structure"]]
        path = tmp_path / f"{entry['name']}.json"
        path.write_text(json.dumps(cfg))
        entry["file"] = str(path)
    return spec
