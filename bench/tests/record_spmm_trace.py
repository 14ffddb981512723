"""Record the small chip trace the SpMM readers' tests read.

    python bench/tests/record_spmm_trace.py [OUT]   # on a TPU

writes into OUT, by default `bench/tests/data/spmm/`.

Runs `products.spmm` through the harness with `--trace 1` at 2^14
vertices (k = 256 as in the cell), and keeps the profile as
`products.spmm.xplane.pb` beside the result line it printed, with the
run's matrix facts and counts added under "info" and "counts".
"""
from __future__ import annotations

import copy
import io
import json
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
DATA = pathlib.Path(__file__).resolve().parent / "data" / "spmm"
NAME, LOG2_ROWS, SECONDS = "products.spmm", 14, 0.05


def main(argv=None) -> int:
    import jax

    from bench import harness, registry, trace

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_spmm_trace: needs a TPU")
    argv = sys.argv[1:] if argv is None else argv
    out_dir = pathlib.Path(argv[0]) if argv else DATA
    out_dir.mkdir(parents=True, exist_ok=True)
    spec = copy.deepcopy(registry.load_spec())
    wl = registry.workload(spec, NAME)
    cfg = registry.config(spec, wl["config"])
    cfg["log2_rows"] = LOG2_ROWS
    tmp = pathlib.Path(tempfile.mkdtemp())
    (tmp / "cfg.json").write_text(json.dumps(cfg))
    for entry in spec["configs"]:
        if entry["name"] == wl["config"]:
            entry["file"] = str(tmp / "cfg.json")
    out, log = tmp / "trace", io.StringIO()
    result = harness.execute(spec, wl, 1, SECONDS, True, jax.devices()[:1],
                             time.perf_counter(), log=log,
                             keep_trace=str(out))
    for line in log.getvalue().splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            result.update({k: rec[k] for k in ("info", "counts") if k in rec})
    shutil.copy(trace.find_xplane(out), out_dir / f"{NAME}.xplane.pb")
    (out_dir / f"{NAME}.result.json").write_text(
        json.dumps(result, indent=1) + "\n")
    print(NAME, json.dumps(result), flush=True)
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
