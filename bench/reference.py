"""Plain references for the answers a run checks, and their controls.

The references work in float64 on the host from the benchmark's own CSR
arrays (`gen.HostCSR`) and import nothing of the program.  The controls
are the same computations with their operands stored in bfloat16, the
precision below the configurations' float32, computed with jax on the
default device; put in the program's place, they must fail the check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def spmv(a, x) -> np.ndarray:
    """y = A x in float64."""
    x = np.asarray(x, dtype=np.float64)
    return np.bincount(a.rows(), weights=a.data.astype(np.float64)
                       * x[a.indices], minlength=a.n_rows)


def pagerank(a, n_iters: int, damping: float) -> np.ndarray:
    """`n_iters` power iterations of PageRank along the edges i -> j
    (A[i, j] != 0) from the uniform vector; the mass of vertices without
    out-edges is spread uniformly."""
    rows, cols, n = a.rows(), a.indices.astype(np.int64), a.n_rows
    out_deg = np.bincount(rows, minlength=n).astype(np.float64)
    share = 1.0 / np.maximum(out_deg, 1.0)
    dangling = out_deg == 0
    tele = np.full(n, 1.0 / n)
    r = tele.copy()
    for _ in range(n_iters):
        y = np.bincount(cols, weights=(r * share)[rows], minlength=n)
        r = damping * (y + r[dangling].sum() * tele) \
            + (1.0 - damping) * tele
    return r


def row_err(y, a, x) -> float:
    """The componentwise error of y as A x: the largest over rows of
    |y_i - (A x)_i| / (|A| |x|)_i, both in float64.  Float32 arithmetic
    keeps each row within a small multiple of its own magnitudes
    (|A| |x|)_i, whatever the row's length or the other rows' scale.  A
    row's magnitude is floored at 2^-80 of the largest, so a product that
    float32 flushes to zero is measured against the matrix's scale;
    infinite when y has a non-finite entry or the wrong shape."""
    y = np.asarray(y, dtype=np.float64)
    if y.shape != (a.n_rows,) or not np.isfinite(y).all():
        return float("inf")
    rows = a.rows()
    prod = a.data.astype(np.float64) * np.asarray(x, np.float64)[a.indices]
    ref = np.bincount(rows, weights=prod, minlength=a.n_rows)
    mag = np.bincount(rows, weights=np.abs(prod, out=prod),
                      minlength=a.n_rows)
    floor = max(float(mag.max(initial=0.0)) * 2.0 ** -80,
                np.finfo(np.float64).tiny)
    return float((np.abs(y - ref) / np.maximum(mag, floor)).max(initial=0.0))


def rel_err(y, ref) -> float:
    """max |y - ref| / max |ref|; infinite when y has a non-finite entry
    or the wrong shape."""
    y = np.asarray(y, dtype=np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return float("inf")
    scale = max(float(np.abs(ref).max(initial=0.0)), 1e-300)
    return float(np.abs(y - ref).max(initial=0.0)) / scale


def _bf16(v):
    """float32 rounded to the nearest bfloat16 (ties to even), in integer
    arithmetic: XLA may drop a float32 -> bfloat16 -> float32 round trip
    as excess precision, but not this."""
    bits = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


@jax.jit
def _spmv_bf16(rows, cols, vals, x):
    return jax.ops.segment_sum(_bf16(vals) * _bf16(x)[cols], rows,
                               num_segments=x.shape[0])


def spmv_control(a, x) -> np.ndarray:
    """y = A x with A and x stored in bfloat16, summed in float32."""
    if a.n_rows != a.n_cols:
        raise ValueError("the control multiplies square matrices")
    y = _spmv_bf16(jnp.asarray(a.rows().astype(np.int32)),
                   jnp.asarray(a.indices), jnp.asarray(a.data),
                   jnp.asarray(x, jnp.float32))
    return np.asarray(y)


def pagerank_control(a, n_iters: int, damping: float) -> np.ndarray:
    """`pagerank` with the transition weights and the scores stored in
    bfloat16 at each multiply, the rest in float32."""
    n = a.n_rows
    rows = jnp.asarray(a.rows().astype(np.int32))
    cols = jnp.asarray(a.indices)
    out_deg = jnp.bincount(rows, length=n).astype(jnp.float32)
    weight = (1.0 / jnp.maximum(out_deg, 1.0))[rows]
    dangling = out_deg == 0
    tele = jnp.full((n,), 1.0 / n, jnp.float32)
    r = tele
    for _ in range(n_iters):
        y = jax.ops.segment_sum(_bf16(weight) * _bf16(r)[rows], cols,
                                num_segments=n)
        leaked = jnp.sum(jnp.where(dangling, r, 0.0))
        r = damping * (y + leaked * tele) + (1.0 - damping) * tele
    return np.asarray(r)
