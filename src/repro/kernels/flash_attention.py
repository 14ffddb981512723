"""Flash attention Pallas kernel with causal + sliding-window (banded) masks.

Paper tie-in: a sliding-window attention matrix IS a banded sparse matrix --
the FD structure applied to attention.  The same streaming property that
makes DIA SpMV roofline-friendly makes banded attention sub-quadratic: each
query block touches a contiguous KV window, so KV tiles stream HBM->VMEM
with no gathers and out-of-band blocks are skipped entirely.

Grid = (batch*heads, n_q_blocks, n_kv_blocks), online-softmax accumulators
in VMEM scratch, fp32 accumulation regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, bq, bk, nk):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = i * bq
    k_lo = j * bk
    # block-level skip: entire KV block out of the (causal, window) band
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant, k_lo <= q_lo + bq - 1)
    if window is not None:
        relevant = jnp.logical_and(relevant, k_lo + bk - 1 >= q_lo - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        q_idx = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_idx = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, q_idx >= k_idx)
        if window is not None:
            mask = jnp.logical_and(mask, q_idx - k_idx < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                          # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, d)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, :] = jnp.where(
            l == 0.0, 0.0, acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, window: int | None = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q: (bh, sq, d), k/v: (bh, skv, d) -> (bh, sq, d).

    `window`: sliding-window size (None = full attention).  fp32 accumulate.
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / (d ** 0.5)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v)
