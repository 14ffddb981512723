"""Reordering strategies: turn unstructured (R-MAT) inputs FD-like.

The paper shows SpMV performance is set by the *structure* of the x-access
stream; PR 1 attacked the unstructured case from the hardware side (victim
caches, stream buffers).  These strategies are the software-side answer:
permute the matrix so the stream the kernel actually issues becomes
sequential/reused -- i.e. prefetchable -- and `plan.auto_format` can
re-decide the storage format afterwards (an RCM'd scrambled-banded
matrix becomes DIA-eligible again).

  rcm          reverse Cuthill-McKee bandwidth reduction (pure-numpy BFS)
  degree_sort  rows ordered by nnz (absorbs partition.sort_rows_by_nnz)
  cache_block  column tiling: pack each row-block's x working set
  chain        composable combinator over any of the above

Every strategy is a callable `CSR -> Reordering`; `STRATEGIES` maps names
to callables for sweeps and benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import numpy as np

from repro.core.formats import CSR

from .types import Reordering, identity_reordering, invert_permutation

Strategy = Callable[[CSR], Reordering]


# ---------------------------------------------------------------------------
# Reverse Cuthill-McKee
# ---------------------------------------------------------------------------

def _symmetric_adjacency(csr: CSR):
    """(indptr, indices) of the symmetrized pattern A | A^T, self-loops
    dropped, neighbours sorted by (degree, id) -- the CM visiting order."""
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    cols = np.asarray(csr.indices, dtype=np.int64)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(indptr))
    n = max(csr.n_rows, csr.n_cols)
    u = np.concatenate([rows, cols])
    v = np.concatenate([cols, rows])
    keep = u != v
    u, v = u[keep], v[keep]
    # dedup (u, v)
    key = u * n + v
    order = np.argsort(key, kind="stable")
    u, v, key = u[order], v[order], key[order]
    uniq = np.ones(key.size, dtype=bool)
    uniq[1:] = key[1:] != key[:-1]
    u, v = u[uniq], v[uniq]
    deg = np.bincount(u, minlength=n)
    # sort each node's neighbours by (degree, id): lexsort with u major
    order = np.lexsort((v, deg[v], u))
    v = v[order]
    adj_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=adj_ptr[1:])
    return adj_ptr, v, deg


def _pseudo_peripheral(start: int, adj_ptr, adj, deg) -> int:
    """George-Liu: repeat BFS from the farthest min-degree node until the
    eccentricity stops growing; returns a near-peripheral start node."""
    node = start
    last_ecc = -1
    for _ in range(8):                      # converges in 2-3 in practice
        level, ecc = _bfs_levels(node, adj_ptr, adj)
        if ecc <= last_ecc:
            break
        last_ecc = ecc
        frontier = np.flatnonzero(level == ecc)
        node = int(frontier[np.argmin(deg[frontier])])
    return node


def _bfs_levels(start: int, adj_ptr, adj):
    n = adj_ptr.size - 1
    level = np.full(n, -1, dtype=np.int64)
    level[start] = 0
    frontier = np.array([start], dtype=np.int64)
    ecc = 0
    while frontier.size:
        nbrs = np.concatenate([adj[adj_ptr[f]:adj_ptr[f + 1]]
                               for f in frontier]) if frontier.size else \
            np.zeros(0, np.int64)
        nbrs = np.unique(nbrs)
        nbrs = nbrs[level[nbrs] < 0]
        if nbrs.size == 0:
            break
        ecc += 1
        level[nbrs] = ecc
        frontier = nbrs
    return level, ecc


def rcm(csr: CSR) -> Reordering:
    """Reverse Cuthill-McKee: symmetric permutation minimizing bandwidth.

    Pure numpy + a Python BFS loop (no scipy).  Each connected component
    is traversed breadth-first from a pseudo-peripheral min-degree node,
    neighbours visited in increasing-degree order; the concatenated visit
    order is reversed (the "R" -- reversing halves the profile).  Rows and
    columns get the same permutation, so symmetric structure is preserved.
    """
    n = max(csr.n_rows, csr.n_cols)
    adj_ptr, adj, deg = _symmetric_adjacency(csr)
    visited = np.zeros(n, dtype=bool)
    order = np.empty(n, dtype=np.int64)
    pos = 0
    # component seeds in increasing-degree order (isolated nodes included)
    for seed in np.argsort(deg, kind="stable"):
        if visited[seed]:
            continue
        if deg[seed] > 0:
            seed = _pseudo_peripheral(int(seed), adj_ptr, adj, deg)
            if visited[seed]:
                continue
        visited[seed] = True
        order[pos] = seed
        head = pos
        pos += 1
        while head < pos:                   # queue-based BFS
            node = order[head]
            head += 1
            nbrs = adj[adj_ptr[node]:adj_ptr[node + 1]]
            nbrs = nbrs[~visited[nbrs]]     # already (degree, id)-sorted
            k = nbrs.size
            if k:
                visited[nbrs] = True
                order[pos:pos + k] = nbrs
                pos += k
    perm = order[::-1].copy()               # the reversal
    # non-square: restrict the node ordering to each side's id range
    row_perm = perm if csr.n_rows == n else perm[perm < csr.n_rows]
    col_perm = perm if csr.n_cols == n else perm[perm < csr.n_cols]
    r = Reordering(row_perm=row_perm, col_perm=col_perm, strategy="rcm")
    return dataclasses.replace(
        r, stats={"bandwidth_before": _bandwidth(csr),
                  "bandwidth_after": _bandwidth(csr, r)})


def _bandwidth(csr: CSR, reordering: Reordering | None = None) -> int:
    """max |col - row|, optionally under a reordering -- computed straight
    from the coordinate arrays (no permuted CSR is materialized)."""
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    cols = np.asarray(csr.indices, dtype=np.int64)
    if cols.size == 0:
        return 0
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(indptr))
    if reordering is not None:
        rows = reordering.inv_row_perm[rows]
        cols = reordering.inv_col_perm[cols]
    return int(np.abs(cols - rows).max())


# ---------------------------------------------------------------------------
# Degree / nnz row sorting (SELL-style)
# ---------------------------------------------------------------------------

def degree_sort(csr: CSR, descending: bool = True) -> Reordering:
    """Rows ordered by nnz (stable).  Groups similar-length rows so ELL
    padding within row blocks is minimal; generalizes (and now backs)
    `partition.sort_rows_by_nnz`.  Columns are untouched."""
    lengths = np.diff(np.asarray(csr.indptr, dtype=np.int64))
    key = -lengths if descending else lengths
    perm = np.argsort(key, kind="stable").astype(np.int64)
    return Reordering(
        row_perm=perm,
        col_perm=np.arange(csr.n_cols, dtype=np.int64),
        strategy="degree-sort",
        params={"descending": descending},
        stats={"max_nnz_row": int(lengths.max()) if lengths.size else 0},
    )


# ---------------------------------------------------------------------------
# Column / cache blocking of the x working set
# ---------------------------------------------------------------------------

def cache_block(csr: CSR, rows_per_block: int = 1024) -> Reordering:
    """Pack each row-block's x working set into contiguous columns.

    Columns are ordered by (row block that first touches them, access
    count descending, id): while the kernel sweeps one block of rows, its
    x gathers land in one contiguous (hot-first) column segment instead of
    being scattered over the whole vector -- the software analogue of the
    paper's P2/P3 column-blocked software cache, expressed as a pure
    permutation.  Rows are untouched."""
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    cols = np.asarray(csr.indices, dtype=np.int64)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(indptr))
    n_cols = csr.n_cols
    first_block = np.full(n_cols, csr.n_rows // rows_per_block + 1,
                          dtype=np.int64)
    np.minimum.at(first_block, cols, rows // rows_per_block)
    counts = np.bincount(cols, minlength=n_cols)
    col_perm = np.lexsort((np.arange(n_cols), -counts, first_block))
    touched = int((counts > 0).sum())
    return Reordering(
        row_perm=np.arange(csr.n_rows, dtype=np.int64),
        col_perm=col_perm.astype(np.int64),
        strategy="cache-block",
        params={"rows_per_block": rows_per_block},
        stats={"touched_cols": touched},
    )


# ---------------------------------------------------------------------------
# Composition
# ---------------------------------------------------------------------------

def chain(*strategies: Strategy) -> Strategy:
    """Compose strategies left-to-right into one: each runs on the matrix
    as permuted by its predecessors, and the returned `Reordering` is the
    single equivalent permutation pair (provenance lists every step)."""
    def run(csr: CSR) -> Reordering:
        combined = identity_reordering(csr.n_rows, csr.n_cols)
        cur = csr
        names = []
        for strat in strategies:
            step = strat(cur)
            step.validate()
            cur = step.apply(cur)
            names.append(step.strategy)
            combined = combined.then(step)
        return Reordering(
            row_perm=combined.row_perm, col_perm=combined.col_perm,
            strategy=f"chain({','.join(names)})" if names else "identity",
            params=combined.params, stats=combined.stats)
    return run


def identity(csr: CSR) -> Reordering:
    return identity_reordering(csr.n_rows, csr.n_cols)


# name -> strategy, what sweeps and benchmarks iterate over
STRATEGIES: Dict[str, Strategy] = {
    "none": identity,
    "rcm": rcm,
    "degree-sort": degree_sort,
    "cache-block": cache_block,
    "rcm+cache-block": chain(rcm, cache_block),
}

__all__ = ["Strategy", "STRATEGIES", "rcm", "degree_sort", "cache_block",
           "chain", "identity", "invert_permutation"]
