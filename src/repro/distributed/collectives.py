"""Collective helpers: overlap-friendly patterns on jax.lax primitives.

pjit/GSPMD schedules most collectives automatically; these helpers cover the
cases where an explicit schedule beats the default:

  * ring_allgather_matmul -- shard_map pattern that overlaps the per-step
    `ppermute` of weight shards with the partial matmul (the classic
    "all-gather overlap" used for FSDP prefetch; the dry-run HLO shows
    collective-permute interleaved with dots instead of one blocking
    all-gather);
  * lse_merge_attention   -- merges per-shard attention partials computed
    over a sequence-sharded KV cache (decode with 500k contexts) with one
    tiny psum instead of all-gathering KV;
  * crosspod_psum_compressed -- re-export of the int8 error-feedback
    all-reduce from optim.grad_compress.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.grad_compress import crosspod_allreduce_compressed  # noqa: F401


def ring_allgather_matmul(x: jax.Array, w_shard: jax.Array, axis_name: str
                          ) -> jax.Array:
    """Inside shard_map: y = x @ all_gather(w, axis) without a blocking
    all-gather.  w_shard: (d_in/n, d_out) local shard; x: (..., d_in).

    Each of the n steps multiplies the currently-held shard while
    ppermute-ing shards around the ring -- compute hides the permute
    latency (XLA overlaps independent ops).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    d_in = x.shape[-1]
    chunk = d_in // n

    def body(i, carry):
        acc, w_cur = carry
        src = (idx + i) % n
        x_chunk = jax.lax.dynamic_slice_in_dim(x, src * chunk, chunk, -1)
        acc = acc + x_chunk @ w_cur
        w_nxt = jax.lax.ppermute(
            w_cur, axis_name,
            perm=[(j, (j - 1) % n) for j in range(n)])
        return acc, w_nxt

    acc0 = jnp.zeros(x.shape[:-1] + (w_shard.shape[-1],),
                     jnp.promote_types(x.dtype, w_shard.dtype))
    acc, _ = jax.lax.fori_loop(0, n, body, (acc0, w_shard))
    return acc


def lse_merge_attention(q: jax.Array, k_shard: jax.Array, v_shard: jax.Array,
                        axis_name: str, positions_valid: jax.Array
                        ) -> jax.Array:
    """Decode attention over sequence-sharded KV without gathering KV.

    q: (B, H, 1, hd); k/v_shard: (B, S/n, KVH, hd) local slice;
    positions_valid: (B, S/n) bool mask for the local slice.
    Each shard computes its partial softmax numerator/denominator; the merge
    is a psum of (exp-shifted) partials -- O(B*H*hd) bytes on the wire
    instead of O(B*S*KVH*hd).
    """
    b, h, _, hd = q.shape
    kvh = k_shard.shape[2]
    g = h // kvh
    qf = q.reshape(b, kvh, g, hd).astype(jnp.float32)
    kf = k_shard.astype(jnp.float32)
    vf = v_shard.astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, kf) / (hd ** 0.5)
    s = jnp.where(positions_valid[:, None, None, :], s, -1e30)
    m_local = s.max(axis=-1, keepdims=True)
    m_global = jax.lax.pmax(m_local, axis_name)
    p = jnp.exp(s - m_global)
    num = jnp.einsum("bkgs,bskd->bkgd", p, vf)
    den = p.sum(axis=-1, keepdims=True)
    num = jax.lax.psum(num, axis_name)
    den = jax.lax.psum(den, axis_name)
    out = num / jnp.maximum(den, 1e-30)
    return out.reshape(b, h, 1, hd)


def reduce_scatter_grads(grads, axis_name: str):
    """ZeRO-2: each worker keeps 1/n of the (summed) gradient."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)

    def one(g):
        if g.shape and g.shape[0] % n == 0:
            scattered = jax.lax.psum_scatter(
                g, axis_name, scatter_dimension=0, tiled=True)
            return scattered
        return jax.lax.psum(g, axis_name)

    del idx
    return jax.tree.map(one, grads)
