"""Seeded inputs: the matrix of a configuration and the start vector.

A configuration file names its `structure`; `structures/<structure>.py`
makes the matrix's coordinates and values on the device from a JAX key
(`generate(cfg, key) -> HostCSR`), in one jitted call, and this module
merges duplicates and builds the CSR arrays on the host.  The same seed
gives the same matrix on every backend (threefry keys, sorted order).
The program under test receives only the finished CSR arrays
(`to_program`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.registry import load_module


@dataclasses.dataclass(frozen=True)
class HostCSR:
    """A CSR matrix in host memory: int32 indices, float32 values."""
    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    n_rows: int
    n_cols: int

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    def rows(self) -> np.ndarray:
        """The row of each nonzero (int64)."""
        return np.repeat(np.arange(self.n_rows, dtype=np.int64),
                         np.diff(self.indptr.astype(np.int64)))


def _key(seed: int, stream: int):
    return jax.random.fold_in(jax.random.key(int(seed)), stream)


def generate(cfg: dict, seed: int) -> HostCSR:
    """The configuration's matrix for `seed`."""
    mod = load_module("structures", cfg["structure"])
    return mod.generate(cfg, _key(seed, 0))


def start_vector(seed: int, n: int) -> jax.Array:
    """x_0: standard normal float32 from `seed`, on the default device."""
    return jax.random.normal(_key(seed, 1), (n,), jnp.float32)


def csr_from_sorted(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                    n_rows: int, n_cols: int) -> HostCSR:
    """CSR from coordinates sorted by (row, column); repeated coordinates
    become one nonzero holding the sum of their values."""
    first = np.empty(rows.shape[0], dtype=bool)
    first[:1] = True
    np.not_equal(rows[1:], rows[:-1], out=first[1:])
    first[1:] |= cols[1:] != cols[:-1]
    starts = np.flatnonzero(first)
    if starts.size < rows.size:
        vals = np.add.reduceat(vals, starts)
        rows, cols = rows[starts], cols[starts]
    counts = np.bincount(rows, minlength=n_rows)
    indptr = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    if indptr[-1] < np.iinfo(np.int32).max:
        indptr = indptr.astype(np.int32)
    return HostCSR(indptr=indptr, indices=cols.astype(np.int32),
                   data=vals.astype(np.float32), n_rows=n_rows,
                   n_cols=n_cols)


def to_program(a: HostCSR):
    """The program's CSR container, its arrays on the default device as
    `CSR.from_coo` would leave them."""
    from repro.core.formats import CSR

    return CSR(data=jnp.asarray(a.data), indices=jnp.asarray(a.indices),
               indptr=jnp.asarray(a.indptr), n_rows=a.n_rows,
               n_cols=a.n_cols)
