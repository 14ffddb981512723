"""2-D 9-point finite-difference stencil on a periodic grid.

Row r is grid point (r // w, r % w) of an h x w grid (h = 2^floor(k/2),
w = 2^ceil(k/2) for 2^k rows); its nine nonzeros are the point and its
eight periodic neighbours, so every row holds exactly nine, in three
bands of three adjacent columns.  Values are uniform in
[value_low, value_high).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen import HostCSR


def grid(cfg: dict) -> tuple[int, int]:
    k = int(cfg["log2_rows"])
    return 1 << (k // 2), 1 << (k - k // 2)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _stencil(key, h: int, w: int, lo: float, hi: float):
    node = jnp.arange(h * w, dtype=jnp.int32)
    gi, gj = node // w, node % w
    cols = jnp.stack([((gi + di) % h) * w + (gj + dj) % w
                      for di in (-1, 0, 1) for dj in (-1, 0, 1)], axis=1)
    cols = jnp.sort(cols, axis=1)
    vals = jax.random.uniform(key, cols.shape, jnp.float32, lo, hi)
    return cols.reshape(-1), vals.reshape(-1)


def generate(cfg: dict, key) -> HostCSR:
    h, w = grid(cfg)
    if min(h, w) < 3:
        raise ValueError(f"a {h}x{w} grid folds periodic neighbours "
                         "onto each other; the stencil needs sides >= 3")
    if cfg["nnz_per_row"] != 9:
        raise ValueError("fd9 makes exactly 9 nonzeros per row")
    cols, vals = _stencil(key, h, w, float(cfg["value_low"]),
                          float(cfg["value_high"]))
    n = h * w
    return HostCSR(indptr=np.arange(0, 9 * n + 1, 9, dtype=np.int32),
                   indices=np.asarray(cols), data=np.asarray(vals),
                   n_rows=n, n_cols=n)
