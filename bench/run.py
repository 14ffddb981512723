"""Run one cell of the chip benchmark once.

    python bench/run.py --workload rmat.spmv --seed 7 --seconds 10 --trace 0

The cell (`BENCHMARK.json` `workloads`) names a configuration and a
traffic mix.  The run makes the matrix and the inputs from `--seed`,
warms every program the window uses (set-up), measures for `--seconds`
seconds, and checks the window's answers against a float64 reference.
With `--trace 0` the result holds the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics from a profiler trace of the window.
The last line of standard output is the result as one JSON object; the
set-up split and the window's counts go to standard error before it,
and the compared numbers with their limits are the last lines there.
Without a TPU, or with fewer chips than the cell asks for, it exits 1
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def accelerators(chips: int):
    """The first `chips` TPU devices; exits 1 without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX's first device is "
                 f"{devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees "
                 f"{len(devices)}")
    return devices[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, registry

    spec = registry.load_spec()
    wl = registry.workload(spec, args.workload)
    devices = accelerators(int(wl["chips"]))

    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.execute(spec, wl, args.seed, args.seconds,
                             bool(args.trace), devices, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
