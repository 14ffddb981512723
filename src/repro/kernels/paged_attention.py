"""Paged-attention decode Pallas kernel -- the BELL pattern applied to KV.

Serving keeps the KV cache as a pool of fixed-size blocks; a sequence's
cache is the list of block ids in its block table (serve/kv_blocks.py).
Decode attention must therefore gather KV through a data-dependent block
indirection -- structurally identical to blocked-ELL SpMV: the block table
is the block-column index array, the pool is the gathered operand, and the
scalar-prefetched index_map (paper P3: the kernel directs placement) turns
each "random access" into a fully-useful lane-aligned tile DMA.

Layout (one query token per sequence, GQA folded by the wrapper):
  q        : (B, H, hd)
  k_pool   : (n_blocks, block, KVH, hd)   physical pool
  v_pool   : (n_blocks, block, KVH, hd)
  tables   : (B, max_blocks) int32        physical block id per logical blk
  lengths  : (B,) int32                   tokens in each sequence
  out      : (B, H, hd)

Grid = (B, max_blocks): for each sequence the kernel walks its logical
blocks; the BlockSpec index_map dereferences tables[b, j] so the DMA
engine prefetches exactly the needed pool block (never the whole pool).
Flash-style online softmax accumulates across blocks in VMEM scratch;
positions >= length are masked.  Interpret-mode validated vs ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

NEG_INF = -1e30


def _kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, block, n_blocks, scale):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    base = j * block
    # skip blocks entirely beyond the sequence (paper P1: never touch them)
    @pl.when(base < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # (H, hd)
        k = k_ref[0].astype(jnp.float32)               # (H, block, hd)
        v = v_ref[0].astype(jnp.float32)
        # per-head scores: batched dot over H -> (H, block)
        s = jax.lax.dot_general(
            q, k, (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        # mask positions past the sequence length
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                          # (H, block)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * corr + p.sum(axis=1, keepdims=True), l_scr.shape)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)         # (H, hd)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = jnp.where(l == 0.0, 0.0,
                             acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                           interpret: bool | None = None):
    """q: (B, H, hd); pools: (n_blocks, block, H, hd) (GQA pre-broadcast);
    tables: (B, max_blocks) int32; lengths: (B,) int32 -> (B, H, hd)."""
    bsz, h, hd = q.shape
    _, block, hp, _ = k_pool.shape
    assert hp == h, "wrapper must broadcast KV heads to query heads"
    max_blocks = tables.shape[1]
    scale = 1.0 / (hd ** 0.5)

    # pool laid out (n_blocks, H, block, hd) so the kernel sees (block..)
    kp = jnp.swapaxes(k_pool, 1, 2)     # (n_blocks, H, block, hd)
    vp = jnp.swapaxes(v_pool, 1, 2)

    grid = (bsz, max_blocks)

    out = pl.pallas_call(
        functools.partial(_kernel, block=block, n_blocks=max_blocks,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # tables, lengths
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, hd), lambda b, j, tbl, ln: (b, 0, 0)),
                # the BELL move: block index derefs the table (paper P3)
                pl.BlockSpec((1, h, block, hd),
                             lambda b, j, tbl, ln: (tbl[b, j], 0, 0, 0)),
                pl.BlockSpec((1, h, block, hd),
                             lambda b, j, tbl, ln: (tbl[b, j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, hd),
                                   lambda b, j, tbl, ln: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, h, hd), q.dtype),
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q, kp, vp)
    return out


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, lengths: jax.Array,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, H, hd); pools: (n_blocks, block, KVH, hd) with KVH | H;
    tables: (B, max_blocks) int32; lengths: (B,) -> (B, H, hd)."""
    b, h, hd = q.shape
    kvh = k_pool.shape[2]
    if kvh != h:                      # GQA: broadcast KV heads to H
        g = h // kvh
        k_pool = jnp.repeat(k_pool, g, axis=2)
        v_pool = jnp.repeat(v_pool, g, axis=2)
    return paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                  interpret=interpret)
