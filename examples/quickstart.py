"""Quickstart: the paper in two minutes.

    PYTHONPATH=src python examples/quickstart.py

1. Generate the paper's two matrix families (FD 9-point stencil, R-MAT).
2. Measure their structure (the quantity the paper shows determines
   performance).
3. Reproduce the paper's five metrics at one size (Sandy Bridge model).
4. Show the TPU adaptation: traffic per placement policy, and the
   structure-aware dispatcher picking the right format + kernel.
"""
import numpy as np
import jax.numpy as jnp

from repro.core import analyze, fd_matrix, rmat_matrix, spmv, traffic
from repro.core.cache_model import analytic_metrics
from repro.core.formats import BELL
from repro.plan import auto_format

N = 1 << 14

print("=== 1. the paper's matrices ===")
fd = fd_matrix(N)
rm = rmat_matrix(N)
print(f"FD    : {fd.n_rows} rows, {fd.nnz} nnz ({fd.nnz/fd.n_rows:.1f}/row)")
print(f"R-MAT : {rm.n_rows} rows, {rm.nnz} nnz ({rm.nnz/rm.n_rows:.1f}/row)")

print("\n=== 2. structure is the variable ===")
for name, m in (("FD", fd), ("R-MAT", rm)):
    print(f"{name:6}: {analyze(m).summary()}")

print("\n=== 3. the paper's five metrics (Sandy Bridge model, 16 threads) ===")
for name, m in (("FD", fd), ("R-MAT", rm)):
    met = analytic_metrics(m, threads=16)
    print(f"{name:6}: L2={met.l2_miss_rate:6.2f}/kinst  "
          f"L3={met.l3_miss_rate:5.2f}/kinst  "
          f"pf={met.prefetch_miss_rate:5.2f}  "
          f"stall={met.l2_stall_frac:4.2f}  "
          f"GFLOPS={met.gflops:6.2f}")

print("\n=== 4. TPU adaptation: bytes moved per placement policy ===")
for name, m in (("FD", fd), ("R-MAT", rm)):
    rep = analyze(m)
    print(f"{name}:")
    print("  " + traffic.gather_policy(m).summary())
    print("  " + traffic.stream_policy(m, rep.bandwidth_p95).summary())
    print("  " + traffic.col_blocked_policy(m).summary())
    print("  " + traffic.bell_policy(BELL.from_csr(m).density(), m).summary())

print("\n=== 5. structure-aware dispatch (detect -> format -> kernel) ===")
x = jnp.asarray(np.random.default_rng(0).normal(size=N).astype(np.float32))
for name, m in (("FD", fd), ("R-MAT", rm)):
    fmt = auto_format(m)
    y = spmv(fmt, x)
    y_ref = spmv(m, x)
    err = float(jnp.abs(y - y_ref).max())
    print(f"{name:6}: dispatched to {type(fmt).__name__:5} "
          f"(max err vs CSR ref: {err:.2e})")

print("\nDone. Next: benchmarks (python -m benchmarks.run), training "
      "(python -m repro.launch.train --reduced), serving "
      "(python -m repro.launch.serve --reduced).")
