"""Reordering demo: recover FD-like structure from an unstructured matrix.

    PYTHONPATH=src python examples/reorder_demo.py

1. Scramble a banded matrix and watch RCM recover the band (and with it,
   DIA eligibility in `auto_format`).
2. Apply every registered strategy to an R-MAT matrix and compare the
   structure metrics the paper ties to performance (before/after).
3. Replay the x-access traces through the telemetry hierarchy: how much
   of the FD-vs-R-MAT first-level miss gap does each permutation close,
   alone and on top of PR-1's stream buffers?
4. Correctness: reorder-then-multiply-then-inverse-scatter returns the
   same y as the unpermuted multiply.
"""
import numpy as np
import jax.numpy as jnp

from repro import reorder
from repro.core import analyze, banded_matrix, rmat_matrix, spmv
from repro.core.structure import analyze_reorder
from repro.plan import auto_format
from repro.telemetry.hierarchy import HierarchySpec
from repro.telemetry.report import reorder_gap_report
from repro.telemetry.sweep import reorder_sweep

N = 1 << 11

print("=== 1. RCM un-scrambles a banded matrix ===")
banded = banded_matrix(N, bandwidth=8, seed=0)
p = np.random.default_rng(0).permutation(N)
scrambled = reorder.Reordering(row_perm=p, col_perm=p,
                               strategy="scramble").apply(banded)
r = reorder.rcm(scrambled)
print(f"bandwidth: original {analyze(banded).bandwidth}, "
      f"scrambled {r.stats['bandwidth_before']}, "
      f"after RCM {r.stats['bandwidth_after']}")
print(f"auto_format: scrambled -> {type(auto_format(scrambled)).__name__}, "
      f"with RCM -> {type(auto_format(scrambled, reordering=r)).__name__}")

print("\n=== 2. structure before/after, R-MAT ===")
rm = rmat_matrix(N)
for name, strategy in reorder.STRATEGIES.items():
    if name == "none":
        continue
    print(analyze_reorder(rm, strategy(rm)).summary())

print("\n=== 3. miss-rate gap closed per strategy (trace-driven) ===")
scaled = dict(l2_bytes=32 * 1024, l3_bytes=256 * 1024)
points = reorder_sweep(
    log2ns=(11,),
    mechanisms={"baseline": HierarchySpec(**scaled),
                "stream-buffers": HierarchySpec(stream_buffers=8,
                                                stream_depth=4, **scaled)})
print(reorder_gap_report(points))

print("\n=== 4. correctness under reordering ===")
x = jnp.asarray(np.random.default_rng(1).normal(size=N).astype(np.float32))
y_ref = spmv(rm, x)
for name, strategy in reorder.STRATEGIES.items():
    rr = strategy(rm)
    y = spmv(rr.apply(rm), x, reordering=rr)
    err = float(jnp.abs(y - y_ref).max())
    print(f"{name:18}: max |y - y_ref| = {err:.2e}")

print("\nDone. Full sweep: PYTHONPATH=src python -m benchmarks.reorder_bench")
