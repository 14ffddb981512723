"""Row-parallel SpMV over a device mesh — the hardware side of the
`repro.parallel` simulation.

The simulated engine partitions rows across threads sharing an LLC; this
module executes the same `RowPartition` across real devices with
`shard_map`: every device runs the Pallas ELL kernel on its row slab
(x replicated, like the threads sharing one x working set), and y comes
back row-sharded.  On CPU the kernel runs in interpret mode, on TPU as
compiled Mosaic (`repro.kernels.resolve_interpret`).

Shard preparation is part of the matrix's execution plan:
`spmv_row_sharded` fetches a row-sharded `SpmvPlan` from
`repro.plan.DEFAULT_CACHE` (packing the ELL slabs only on first touch),
or build one yourself with `repro.plan.compile(csr, mesh=mesh)` to also
control reordering and serialize the planned shards.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core.formats import CSR
from repro.core.partition import RowPartition, rowblock_equal
from repro.kernels import resolve_interpret
from repro.kernels import spmv_ell as _ell
from repro.kernels._layout import (ShardedELL, round_up,           # noqa: F401
                                   prepare_ell_shards)  # re-exported for
                                                        # pre-plan callers
from repro.spans import KERNEL, STITCH, X_PREP

_AXIS = "shards"


def row_mesh(devices=None) -> Mesh:
    """A 1-D mesh over all (or the given) devices, axis name 'shards'."""
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (_AXIS,))


def default_row_partition(csr: CSR, mesh: Mesh) -> RowPartition:
    """`rowblock_equal` over the mesh's shard axis, padded with trailing
    empty parts when there are more devices than rows (`rowblock_equal`
    caps its part count, but `shard_map` needs exactly one slab per
    device)."""
    n_shards = mesh.shape[_AXIS]
    if n_shards <= csr.n_rows:
        return rowblock_equal(csr, n_shards)
    starts = np.minimum(np.arange(n_shards + 1, dtype=np.int64), csr.n_rows)
    indptr = np.asarray(csr.indptr, dtype=np.int64)
    return RowPartition(starts=starts,
                        nnz_per_part=indptr[starts[1:]] - indptr[starts[:-1]])


def spmv_row_sharded(csr: CSR, x: jax.Array, mesh: Optional[Mesh] = None,
                     partition: Optional[RowPartition] = None,
                     bm: int = 128, interpret: Optional[bool] = None,
                     reorder: str = "none", predictor: str = "auto"
                     ) -> jax.Array:
    """y = A @ x with rows sharded across the mesh's 'shards' axis.

    `partition` defaults to `default_row_partition`; a
    `rowblock_balanced` partition is accepted too (shards are padded to
    the largest part, so balance trades padding for equal work).  The
    packed shard slabs are cached in `repro.plan.DEFAULT_CACHE` keyed by
    matrix contents + partition, so repeated multiplies pay the ELL
    packing once.

    `reorder` defaults to 'none' (keeping historical cache keys);
    `reorder='auto'` lets the compiler's candidate scoring pick the
    shard-local ordering, scored by the learned cost model when one is
    shipped (`predictor='auto'`) -- a cheap decision even on the first
    touch of a large matrix.
    """
    from repro import plan as _plan

    mesh = mesh if mesh is not None else row_mesh()
    n_shards = mesh.shape[_AXIS]
    if partition is None:
        partition = default_row_partition(csr, mesh)
    if partition.n_parts != n_shards:
        raise ValueError(f"partition has {partition.n_parts} parts for "
                         f"{n_shards} devices on axis '{_AXIS}'")
    if reorder == "none":
        predictor = "none"     # nothing to score; keep historical keys
    p = _plan.DEFAULT_CACHE.get_or_compile(
        csr, mesh=mesh, partition=partition, bm=bm, reorder=reorder,
        predictor=predictor, keep_csr=False)
    return p.execute(x, interpret=interpret)


def row_sharded_program(prep: ShardedELL, mesh: Mesh,
                        interpret: Optional[bool] = None):
    """The jitted `shard_map` program behind `spmv_row_sharded_prepared`:
    `(prep.data, prep.idx, x_padded) -> (parts, rows_pad)` y slabs, one
    slab per device on the mesh's 'shards' axis."""
    interpret = resolve_interpret(interpret)
    bm = prep.bm
    _, rows_pad, w = prep.data.shape

    def one_shard(data, idx, xv):
        # data/idx arrive as this device's (1, rows_pad, w) slab
        b_dim = rows_pad // bm
        with jax.named_scope(KERNEL):
            data = data.reshape(b_dim, bm, w)
            idx = idx.reshape(b_dim, bm, w)
        y = _ell.spmv_ell_pallas(data, idx, xv, interpret=interpret)
        with jax.named_scope(KERNEL):
            return y.reshape(1, rows_pad)

    return jax.jit(jax.shard_map(
        one_shard, mesh=mesh,
        in_specs=(PartitionSpec(_AXIS, None, None),
                  PartitionSpec(_AXIS, None, None),
                  PartitionSpec()),
        out_specs=PartitionSpec(_AXIS, None),
        check_vma=False))


@functools.partial(jax.jit, static_argnames=("size",))
def _pad_to(x: jax.Array, size: int) -> jax.Array:
    with jax.named_scope(X_PREP):
        return jnp.pad(x, (0, size - x.shape[0]))


def pad_x(prep: ShardedELL, x: jax.Array) -> jax.Array:
    """x padded to the lane multiple every shard's column indices fit."""
    return _pad_to(x, round_up(prep.n_cols, 128))


@functools.partial(jax.jit, static_argnames=("rows", "n_rows"))
def _stitch(y_slabs: jax.Array, rows: tuple, n_rows: int) -> jax.Array:
    """y from the (parts, rows_pad) slabs: part p's first rows[p] rows,
    in order."""
    with jax.named_scope(STITCH):
        parts = [y_slabs[p, :r] for p, r in enumerate(rows)]
        return jnp.concatenate(parts)[:n_rows]


def spmv_row_sharded_prepared(prep: ShardedELL, x: jax.Array, mesh: Mesh,
                              interpret: Optional[bool] = None) -> jax.Array:
    y_slabs = row_sharded_program(prep, mesh, interpret)(
        prep.data, prep.idx, pad_x(prep, x))              # (parts, rows_pad)
    rows = tuple(int(r) for r in np.diff(prep.starts))
    return _stitch(y_slabs, rows, prep.n_rows)
