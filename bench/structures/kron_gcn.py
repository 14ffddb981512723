"""A GCN's normalised adjacency over a symmetrised Kronecker graph.

Â = D̃^-1/2 (A + I) D̃^-1/2, the propagation matrix of a graph
convolutional network (Kipf and Welling), where A is the 0/1 adjacency of
an undirected graph and D̃ the degrees of A + I.  The graph stands in for
a co-purchase graph such as OGB's ogbn-products: Graph500's Kronecker
generator (`rmat.edges`, quadrant probabilities a, b, c, d) draws
`edge_factor * 2^k` edges, one random permutation relabels both
endpoints of every edge, each edge is stored in both directions, a
self-loop is added on every vertex, and repeated coordinates merge into
one nonzero.  Each stored entry (i, j) holds 1 / sqrt(d̃_i d̃_j) in
float32, d̃_i being the row's stored count.

The edges come from the configuration's `structure_seed`; the run's seed
draws the relabelling, so every seed gives the same graph up to the names
of its vertices and does the same work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen import HostCSR, csr_from_sorted
from bench.structures.rmat import edges


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _coordinates(k_edges, k_perm, levels, n_edges, a, b, c):
    """Both directions of every edge and one self-loop a vertex, with
    relabelled vertices, sorted by (row, column)."""
    rows, cols = edges(k_edges, levels, n_edges, a, b, c)
    label = jax.random.permutation(k_perm, 1 << levels).astype(jnp.int32)
    rows, cols = label[rows], label[cols]
    loops = jnp.arange(1 << levels, dtype=jnp.int32)
    return jax.lax.sort((jnp.concatenate([rows, cols, loops]),
                         jnp.concatenate([cols, rows, loops])), num_keys=2)


def normalise(a: HostCSR) -> HostCSR:
    """The same pattern holding 1 / sqrt(d_i d_j), d the stored count of
    each row (the matrix is symmetric, so also of each column)."""
    deg = np.diff(a.indptr.astype(np.int64)).astype(np.float64)
    vals = 1.0 / np.sqrt(deg[a.rows()] * deg[a.indices])
    return HostCSR(indptr=a.indptr, indices=a.indices,
                   data=vals.astype(np.float32), n_rows=a.n_rows,
                   n_cols=a.n_cols)


def generate(cfg: dict, key) -> HostCSR:
    levels = int(cfg["log2_rows"])
    n = 1 << levels
    probs = [float(cfg[q]) for q in ("a", "b", "c", "d")]
    if abs(sum(probs) - 1.0) > 1e-9:
        raise ValueError(f"quadrant probabilities sum to {sum(probs)}")
    rows, cols = _coordinates(jax.random.key(int(cfg["structure_seed"])),
                              key, levels, int(cfg["edge_factor"]) * n,
                              *probs[:3])
    rows, cols = np.asarray(rows), np.asarray(cols)
    pattern = csr_from_sorted(rows, cols, np.ones(rows.shape, np.float32),
                              n, n)
    return normalise(pattern)
