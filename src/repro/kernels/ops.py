"""Public jit'd wrappers for the Pallas kernels.

Two layers, split so repeated traffic never repeats host work:

  * `repro.kernels._layout` owns ALL matrix-side preparation (padding,
    stripe splitting, row blocking) as `prepare_*` functions returning
    `Prepared*` containers, plus `spmv_*_prepared` runners that do zero
    matrix-side work per call.  `repro.plan` calls `prepare_*` once at
    plan-compile time and replays `spmv_*_prepared` forever after.
  * THIS module keeps the per-call convenience wrappers (`spmv_dia`,
    `spmv_bell`, `spmv_ell`, `spmv_csr`): each is just
    `prepare_*` + `spmv_*_prepared` composed, for one-shot callers and
    oracle tests.  Repeated multiplies should go through a compiled
    `repro.plan.SpmvPlan` (or `core.spmv.spmv`, which caches plans).

The prepared-layout containers (`PaddedCSR`, `ShardedELL`, ...) are
re-exported here for backwards compatibility.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import BELL, CSR, DIA, ELL, HYB
from . import flash_attention as _fa
from ._layout import (PaddedCSR, PreparedBELL, PreparedDIA, PreparedELL,
                      PreparedHYB, PreparedSegCSR, ShardedELL, prepare_bell,
                      prepare_csr, prepare_csr_seg, prepare_dia, prepare_ell,
                      prepare_ell_shards, prepare_hyb, round_up,
                      spmv_bell_prepared, spmv_csr_prepared,
                      spmv_csr_seg_prepared, spmv_dia_prepared,
                      spmv_ell_prepared, spmv_hyb_prepared)

# Backwards-compatible alias; new code should use `_layout.round_up`.
_round_up = round_up


def _reordered(kernel_fn):
    """Give a `fn(matrix, x, ..)` wrapper an optional `reordering` kwarg:
    the matrix is the reordered operand, x/y stay in the original order
    (gather x through col_perm in, scatter y through inv_row_perm out) --
    same contract as `repro.core.spmv.spmv`."""
    @functools.wraps(kernel_fn)
    def run(matrix, x, *args, reordering=None, **kwargs):
        if reordering is None:
            return kernel_fn(matrix, x, *args, **kwargs)
        y = kernel_fn(matrix, reordering.permute_x(x), *args, **kwargs)
        return reordering.restore_y(y)
    return run


# ---------------------------------------------------------------------------
# Per-call SpMV wrappers: prepare + run (cache the prep via repro.plan for
# repeated multiplies)
# ---------------------------------------------------------------------------

@_reordered
def spmv_dia(dia: DIA, x: jax.Array, bn: int = 512,
             interpret: bool | None = None) -> jax.Array:
    return spmv_dia_prepared(prepare_dia(dia, bn=bn), x, interpret=interpret)


@_reordered
def spmv_bell(bell: BELL, x: jax.Array,
              interpret: bool | None = None) -> jax.Array:
    return spmv_bell_prepared(prepare_bell(bell), x, interpret=interpret)


def _check_ell_padding_absorbing(ell: ELL, semiring) -> None:
    """An ELL built with the default `fill=0.0` pads short rows with
    (value 0.0, col 0) slots.  Under a semiring whose absorbing element is
    not 0.0 (min-plus: +inf) those slots read as real weight-0 edges to
    vertex 0 and silently corrupt every short row — so refuse any
    container holding such ambiguous slots and point at the fix.  (The
    check is conservative: a genuine explicit-zero entry in column 0
    trips it too; store it as the CSR path does, or nudge it off 0.0.)"""
    if isinstance(ell.data, jax.core.Tracer) or \
            isinstance(ell.indices, jax.core.Tracer):
        return                         # can't inspect under tracing
    import numpy as np

    data, idx = np.asarray(ell.data), np.asarray(ell.indices)
    if data.size and bool(np.any((data == 0.0) & (idx == 0))):
        raise ValueError(
            f"ELL container has (value 0.0, col 0) slots, which the "
            f"{semiring.name!r} semiring (pad_value="
            f"{semiring.pad_value!r}) would treat as real edges; build it "
            f"with ELL.from_csr(csr, fill=semiring.pad_value) so padding "
            "is absorbing, or use spmv_csr(csr, x, semiring=...)")


@_reordered
def spmv_ell(ell: ELL, x: jax.Array, bm: int = 128,
             interpret: bool | None = None, semiring=None) -> jax.Array:
    """Row-block the (n_rows, max_nnz) ELL arrays to (B, bm, W) and run the
    Pallas kernel; padding rows index col 0 with the absorbing pad value
    (0 for the default plus-times `semiring`).

    Non-plus-times semirings require the CONTAINER's own short-row
    padding to be absorbing too: build it with
    `ELL.from_csr(csr, fill=semiring.pad_value)` (checked when the pad
    value is not 0.0)."""
    pad = 0.0 if semiring is None else semiring.pad_value
    if semiring is not None and semiring.pad_value != 0.0:
        _check_ell_padding_absorbing(ell, semiring)
    return spmv_ell_prepared(prepare_ell(ell, bm=bm, pad_value=pad), x,
                             interpret=interpret, semiring=semiring)


@_reordered
def spmv_csr(csr: CSR, x: jax.Array, n_stripes: int = 1,
             interpret: bool | None = None, semiring=None) -> jax.Array:
    """Convenience wrapper: preps layout per call (compile a
    `repro.plan.SpmvPlan` to cache the `PaddedCSR` for repeated
    multiplies)."""
    pad = 0.0 if semiring is None else semiring.pad_value
    return spmv_csr_prepared(
        prepare_csr(csr, n_stripes=n_stripes, pad_value=pad), x,
        interpret=interpret, semiring=semiring)


@_reordered
def spmv_csr_seg(csr: CSR, x: jax.Array, seg_len: int = 512,
                 interpret: bool | None = None, semiring=None) -> jax.Array:
    """nnz-balanced segmented (merge) CSR: equal-nonzero segments over a
    static grid with a carry-out merge across segment boundaries.
    Convenience wrapper; compile a `repro.plan.SpmvPlan` with
    `format="csr-seg"` to cache the `PreparedSegCSR` layout."""
    pad = 0.0 if semiring is None else semiring.pad_value
    return spmv_csr_seg_prepared(
        prepare_csr_seg(csr, seg_len=seg_len, pad_value=pad), x,
        interpret=interpret, semiring=semiring)


def _check_hyb_padding_absorbing(hyb: HYB, semiring) -> None:
    """Same contract as `_check_ell_padding_absorbing`, applied to the
    HYB light partition: `fill=0.0` padding reads as real weight-0 edges
    to vertex 0 under semirings whose absorbing element is not 0.0."""
    if isinstance(hyb.data, jax.core.Tracer) or \
            isinstance(hyb.indices, jax.core.Tracer):
        return                         # can't inspect under tracing
    import numpy as np

    data, idx = np.asarray(hyb.data), np.asarray(hyb.indices)
    if data.size and bool(np.any((data == 0.0) & (idx == 0))):
        raise ValueError(
            f"HYB light partition has (value 0.0, col 0) slots, which the "
            f"{semiring.name!r} semiring (pad_value="
            f"{semiring.pad_value!r}) would treat as real edges; build it "
            f"with HYB.from_csr(csr, fill=semiring.pad_value) so padding "
            "is absorbing")


@_reordered
def spmv_hyb(hyb: HYB, x: jax.Array, seg_len: int = 512,
             interpret: bool | None = None, semiring=None) -> jax.Array:
    """Hybrid row split: one ELL launch over the light rows, one
    segmented launch over the column-sorted heavy stream, joined by ⊕.
    Non-plus-times semirings require the container's light padding to be
    absorbing: build it with `HYB.from_csr(csr, fill=semiring.pad_value)`
    (checked when the pad value is not 0.0)."""
    pad = 0.0 if semiring is None else semiring.pad_value
    if semiring is not None and semiring.pad_value != 0.0:
        _check_hyb_padding_absorbing(hyb, semiring)
    return spmv_hyb_prepared(
        prepare_hyb(hyb, seg_len=seg_len, pad_value=pad), x,
        interpret=interpret, semiring=semiring)


# ---------------------------------------------------------------------------
# Paged attention (decode over block-table KV, GQA broadcast here)
# ---------------------------------------------------------------------------

def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, lengths: jax.Array,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, H, hd); pools: (n_blocks, block, KVH, hd) with KVH | H;
    tables: (B, max_blocks) int32; lengths: (B,) -> (B, H, hd)."""
    from . import paged_attention as _pa

    b, h, hd = q.shape
    kvh = k_pool.shape[2]
    if kvh != h:                      # GQA: broadcast KV heads to H
        g = h // kvh
        k_pool = jnp.repeat(k_pool, g, axis=2)
        v_pool = jnp.repeat(v_pool, g, axis=2)
    return _pa.paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                      interpret=interpret)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q/k/v: (batch, heads, seq, head_dim); GQA callers broadcast kv first."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, skv, d)
    vf = v.reshape(b * h, skv, d)
    of = _fa.flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                    interpret=interpret)
    return of.reshape(b, h, sq, d)


__all__ = [
    "spmv_dia", "spmv_bell", "spmv_ell", "spmv_csr", "spmv_csr_seg",
    "spmv_hyb",
    "paged_attention", "flash_attention",
    # prepared-layout API (lives in _layout; re-exported for compatibility)
    "PaddedCSR", "prepare_csr", "spmv_csr_prepared",
    "PreparedDIA", "prepare_dia", "spmv_dia_prepared",
    "PreparedBELL", "prepare_bell", "spmv_bell_prepared",
    "PreparedELL", "prepare_ell", "spmv_ell_prepared",
    "ShardedELL", "prepare_ell_shards",
    "PreparedSegCSR", "prepare_csr_seg", "spmv_csr_seg_prepared",
    "PreparedHYB", "prepare_hyb", "spmv_hyb_prepared",
]
