"""Benchmark aggregator: `PYTHONPATH=src python -m benchmarks.run`.

Runs one bench per paper table/figure plus the TPU-side benches, printing
CSV blocks.  `--fast` trims the empirical sweep (CI); default reproduces
the full paper sweep via synthetic profiles to 2^26.  `--smoke` is the
benchmark smoke job: reorder + scaling + plan amortization + a
tiny-geometry graph-analytic case + the analytics serving bench
(hundreds of requests, ≥20 graphs, asserted warm hit rate) + the
streaming bench (asserted overlay-vs-recompile update latency and
warm-start savings), thread axis {1, 2} — just enough execution that
those benches (and the plan warm/cold ratio and serving hit-rate
assertions) cannot silently rot.
"""
from __future__ import annotations

import argparse
import sys
import time

ALL = ("paper,kernels,traffic,moe,serve,telemetry,reorder,scaling,plan,"
       "graph,serve_graph,stream")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="cap empirical matrices at 2^16 rows")
    ap.add_argument("--smoke", action="store_true",
                    help="reorder+scaling+plan only, tiny geometry, "
                         "threads {1,2}")
    ap.add_argument("--only", default=None, help=f"comma list: {ALL}")
    ap.add_argument("--workers", type=int, default=1,
                    help="shard runner-backed sweeps across N processes")
    ap.add_argument("--resume", default=None, metavar="CKPT_DIR",
                    help="checkpoint sweep cells under this directory and "
                         "resume from whatever is already committed there")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import common
    if args.fast:
        common.EMPIRICAL_MAX_LOG2 = 16
    if args.smoke:
        common.SMOKE = True
        common.EMPIRICAL_MAX_LOG2 = 12
    common.WORKERS = max(args.workers, 1)
    common.SWEEP_CKPT = args.resume

    default = ("reorder,scaling,plan,graph,serve_graph,stream"
               if args.smoke else ALL)
    want = set((args.only or default).split(","))
    t0 = time.time()

    if "paper" in want:
        from . import paper_metrics
        paper_metrics.main()
    if "kernels" in want:
        from . import kernel_bench
        kernel_bench.main()
    if "traffic" in want:
        from . import traffic_bench
        traffic_bench.main()
    if "moe" in want:
        from . import moe_dispatch_bench
        moe_dispatch_bench.main()
    if "serve" in want:
        from . import serve_bench
        serve_bench.main()
    if "telemetry" in want:
        from . import telemetry_bench
        telemetry_bench.main()
    if "reorder" in want:
        from . import reorder_bench
        reorder_bench.main()
    if "scaling" in want:
        from . import scaling_bench
        scaling_bench.main()
    if "plan" in want:
        from . import plan_bench
        plan_bench.main()
    if "graph" in want:
        from . import graph_bench
        graph_bench.main()
    if "serve_graph" in want:
        from . import serve_bench_graph
        serve_bench_graph.main()
    if "stream" in want:
        from . import stream_bench
        stream_bench.main()

    print(f"# benchmarks.run completed in {time.time()-t0:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
