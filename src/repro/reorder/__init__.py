"""repro.reorder — permutations that turn R-MAT into FD-like structure.

The software-side counterpart of the telemetry subsystem's §V hardware
mechanisms: instead of adding victim caches / stream buffers to tolerate
an unstructured x-access stream, permute the matrix so the stream becomes
structured in the first place, then let `plan.auto_format` re-decide
the storage format on the reordered matrix.

  types        Reordering (row/col perms + inverses + provenance), compose
  strategies   rcm / degree_sort / cache_block / chain + STRATEGIES registry

Quick use:

    from repro import reorder
    r = reorder.rcm(csr)          # Reordering
    a2 = r.apply(csr)             # permuted CSR
    fmt = auto_format(a2)         # may now pick DIA/BELL
    y = spmv(fmt, x, reordering=r)   # == spmv(csr, x), original order
"""
from .strategies import (STRATEGIES, Strategy, cache_block, chain,
                         degree_sort, identity, rcm)
from .types import (Reordering, identity_reordering, invert_permutation,
                    is_permutation)

__all__ = [
    "Reordering", "Strategy", "STRATEGIES", "identity_reordering",
    "invert_permutation", "is_permutation", "rcm", "degree_sort",
    "cache_block", "chain", "identity",
]
