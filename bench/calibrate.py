"""Readings that set a cell's correctness limit, on the chip.

    python bench/calibrate.py --workload rmat.spmv --seeds 11-16 \
        --control-seeds 11,12,13 [--seconds 0]

For each seed, in one process: set-up and a window of `--seconds` as a
run makes them, then the check's number for the program (the lower
reading comes from these) and, for the control seeds, the same number
with the bfloat16 control in the program's place on the same inputs
(the upper reading).  One JSON line per seed, then a summary line with
the largest program reading and the smallest control reading beside the
limit in the cell's traffic file.
"""
from __future__ import annotations

import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, registry
    from bench.run import accelerators
    from repro.compile_cache import enable_compile_cache

    spec = registry.load_spec()
    wl = registry.workload(spec, args.workload)
    devices = accelerators(int(wl["chips"]))
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    controls = set(_seeds(args.control_seeds)) if args.control_seeds else set()
    program, control = {}, {}
    for seed in _seeds(args.seeds):
        t = time.perf_counter()
        run = harness.Run(spec, wl, seed, devices)
        run.op.setup(run)
        run.op.window(run, args.seconds)
        run.op.release(run)
        gc.collect()
        rec = {"seed": seed, "attempted": run.attempted,
               "program": {k: v for k, (v, _) in run.op.check(run).items()}}
        program[seed] = rec["program"]
        if seed in controls:
            rec["control"] = control[seed] = run.op.control(run)
        rec["s"] = time.perf_counter() - t
        print(json.dumps(rec), flush=True)
        del run
        gc.collect()
    limits = registry.traffic(wl["traffic"])["limits"]
    summary = {k: {"lower": max(p[k] for p in program.values()),
                   "upper": min((c[k] for c in control.values()),
                                default=None),
                   "limit": limit} for k, limit in limits.items()}
    print(json.dumps({"workload": args.workload, "summary": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
