"""Paged-attention decode kernel vs oracle: shape/dtype sweeps + pool
round-trip with the serve-layer allocator."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention
from repro.serve import BlockAllocator, PoolConfig


def _setup(bsz=3, h=4, hd=32, n_blocks=16, block=8, max_blocks=4, seed=0,
           dtype=np.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(bsz, h, hd)).astype(dtype))
    kp = jnp.asarray(rng.normal(size=(n_blocks, block, h, hd)).astype(dtype))
    vp = jnp.asarray(rng.normal(size=(n_blocks, block, h, hd)).astype(dtype))
    # distinct physical blocks per sequence
    perm = rng.permutation(n_blocks)[: bsz * max_blocks]
    tables = jnp.asarray(perm.reshape(bsz, max_blocks).astype(np.int32))
    lengths = jnp.asarray(rng.integers(1, max_blocks * block + 1, bsz)
                          .astype(np.int32))
    return q, kp, vp, tables, lengths


@pytest.mark.parametrize("bsz,h,hd,block", [
    (2, 4, 32, 8), (3, 8, 64, 16), (1, 2, 128, 8),
])
def test_paged_attention_sweep(bsz, h, hd, block):
    q, kp, vp, tables, lengths = _setup(bsz=bsz, h=h, hd=hd, block=block)
    got = paged_attention(q, kp, vp, tables, lengths)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_attention_gqa_broadcast():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(8, 8, 2, 32)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(8, 8, 2, 32)).astype(np.float32))
    tables = jnp.asarray(np.array([[0, 1], [2, 3]], np.int32))
    lengths = jnp.asarray(np.array([12, 9], np.int32))
    got = paged_attention(q, kp, vp, tables, lengths)
    kpb = jnp.repeat(kp, 4, axis=2)
    vpb = jnp.repeat(vp, 4, axis=2)
    want = ref.paged_attention_ref(q, kpb, vpb, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_attention_bf16():
    q, kp, vp, tables, lengths = _setup(seed=2)
    got = paged_attention(q.astype(jnp.bfloat16),
                              kp.astype(jnp.bfloat16),
                              vp.astype(jnp.bfloat16), tables, lengths)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=5e-2, atol=5e-2)


def test_paged_attention_respects_lengths():
    """Changing pool content beyond a sequence's length must not change
    its output (the kernel never reads unowned/overflow positions)."""
    q, kp, vp, tables, lengths = _setup(seed=3)
    lengths = jnp.asarray(np.array([5, 9, 17], np.int32))
    out1 = paged_attention(q, kp, vp, tables, lengths)
    # poison everything past each sequence's length within its blocks
    kp2 = np.asarray(kp).copy()
    block = kp2.shape[1]
    tb = np.asarray(tables)
    for b in range(3):
        ln = int(lengths[b])
        for j, blk in enumerate(tb[b]):
            lo = max(ln - j * block, 0)
            kp2[blk, lo:] = 1e3
    out2 = paged_attention(q, jnp.asarray(kp2), vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


def test_paged_attention_with_allocator_tables():
    """End-to-end with the serve-layer allocator's tables."""
    alloc = BlockAllocator(PoolConfig(n_blocks=16, block_size=8,
                                      max_blocks_per_seq=4))
    alloc.admit(0, 20)
    alloc.admit(1, 7)
    tables = jnp.asarray(np.stack([alloc.table_array(0),
                                   alloc.table_array(1)]))
    lengths = jnp.asarray(np.array([20, 7], np.int32))
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(2, 4, 32)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(16, 8, 4, 32)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(16, 8, 4, 32)).astype(np.float32))
    got = paged_attention(q, kp, vp, tables, lengths)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
