"""R-MAT (Kronecker) power-law graph, Graph500's generator.

Each of `edge_factor * 2^k` edges descends k levels of the adjacency
matrix, choosing a quadrant at each with probabilities a, b, c, d
(top-left, top-right, bottom-left, bottom-right).  The vertices are then
relabelled by one random permutation, applied to both endpoints of every
edge as Graph500 does, the edges sorted by (row, column), and repeated
edges merged into one nonzero holding the sum of their values; each
edge's value is uniform in [value_low, value_high).

The edges come from the configuration's `structure_seed`; the run's seed
draws the relabelling and the values.  So every seed gives the same
graph up to the names of its vertices (P A P^T): the same in- and
out-degree of each vertex, the same nnz, hence the same work, in
another order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen import HostCSR, csr_from_sorted


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def edges(key, levels: int, n_edges: int, a: float, b: float, c: float):
    """Unpermuted R-MAT (row, column) pairs, int32."""
    def level(i, rc):
        rows, cols = rc
        r = jax.random.uniform(jax.random.fold_in(key, i), (n_edges,))
        down = r >= a + b
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        return rows * 2 + down.astype(jnp.int32), \
            cols * 2 + right.astype(jnp.int32)

    zero = jnp.zeros((n_edges,), jnp.int32)
    return jax.lax.fori_loop(0, levels, level, (zero, zero))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def _graph(k_edges, key, levels, n_edges, a, b, c, permute, lo, hi):
    k_perm, k_vals = jax.random.split(key)
    rows, cols = edges(k_edges, levels, n_edges, a, b, c)
    if permute:
        label = jax.random.permutation(k_perm, 1 << levels).astype(jnp.int32)
        rows, cols = label[rows], label[cols]
    rows, cols = jax.lax.sort((rows, cols), num_keys=2)
    vals = jax.random.uniform(k_vals, (n_edges,), jnp.float32, lo, hi)
    return rows, cols, vals


def generate(cfg: dict, key) -> HostCSR:
    levels = int(cfg["log2_rows"])
    n = 1 << levels
    probs = [float(cfg[q]) for q in ("a", "b", "c", "d")]
    if abs(sum(probs) - 1.0) > 1e-9:
        raise ValueError(f"quadrant probabilities sum to {sum(probs)}")
    k_edges = jax.random.key(int(cfg["structure_seed"]))
    rows, cols, vals = _graph(k_edges, key, levels,
                              int(cfg["edge_factor"]) * n,
                              *probs[:3], bool(cfg["permute"]),
                              float(cfg["value_low"]),
                              float(cfg["value_high"]))
    return csr_from_sorted(np.asarray(rows), np.asarray(cols),
                           np.asarray(vals), n, n)
