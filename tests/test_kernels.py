"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret=True).

Each Pallas kernel sweeps shapes and dtypes per the deliverable contract.
Sizes stay modest: interpret mode executes the grid in Python.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import BELL, CSR, DIA
from repro.core.generators import banded_matrix, fd_matrix, rmat_matrix
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.spmv_dia import spmv_dia_pallas


def _x(n, dtype=np.float32, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=n)
                       .astype(dtype))


@pytest.mark.parametrize("backend,asked,want", [
    ("cpu", None, True), ("cpu", True, True), ("cpu", False, False),
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, ValueError),
])
def test_backend_decides_interpret(monkeypatch, backend, asked, want):
    """None resolves to the backend's mode; interpreting on a TPU raises."""
    import jax
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is ValueError:
        with pytest.raises(ValueError):
            resolve_interpret(asked)
    else:
        assert resolve_interpret(asked) is want


# ---------------------------------------------------------------------------
# DIA kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bn", [(256, 128), (512, 128), (1024, 256)])
def test_dia_kernel_shapes(n, bn):
    csr = fd_matrix(n)
    dia = DIA.from_csr(csr)
    x = _x(n)
    got = ops.spmv_dia(dia, x, bn=bn)
    want = ref.spmv_dia_ref(dia.data, dia.offsets, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_dia_kernel_dtypes(dtype):
    csr = banded_matrix(256, 8, nnz_per_row=5)
    dia = DIA.from_csr(csr)
    band = dia.data.astype(dtype)
    x = _x(256).astype(dtype)
    got = spmv_dia_pallas(band, dia.offsets, x, bn=128)
    want = ref.spmv_dia_ref(band.astype(jnp.float32), dia.offsets,
                            x.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_dia_negative_and_positive_offsets():
    # explicit band with offsets [-2, 0, 3]
    n = 256
    band = jnp.asarray(np.random.default_rng(1)
                       .normal(size=(3, n)).astype(np.float32))
    offs = jnp.asarray(np.array([-2, 0, 3], np.int32))
    x = _x(n, seed=2)
    got = spmv_dia_pallas(band, offs, x, bn=128)
    want = ref.spmv_dia_ref(band, offs, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# BELL kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,seed", [(256, 0), (512, 1)])
def test_bell_kernel_vs_oracle(n, seed):
    csr = rmat_matrix(n, seed=seed)
    bell = BELL.from_csr(csr)
    x = _x(n, seed=seed)
    got = ops.spmv_bell(bell, x)
    want = np.asarray(csr.to_dense()) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_bell_table_past_smem_runs_in_calls(monkeypatch):
    """A block-column table larger than SMEM_TABLE_WORDS is cut into
    calls of whole block rows; the last call is ragged."""
    from repro.kernels import spmv_bell

    csr = rmat_matrix(512, seed=4)
    bell = BELL.from_csr(csr)
    nbr, bpr = bell.block_cols.shape
    monkeypatch.setattr(spmv_bell, "SMEM_TABLE_WORDS", 5 * bpr)
    assert nbr % 5                                 # 5 block rows per call
    x = _x(512, seed=4)
    got = spmv_bell.spmv_bell_pallas.__wrapped__(
        bell.data, bell.block_cols, x, interpret=True)
    want = np.asarray(csr.to_dense()) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_bell_bf16_inputs_fp32_accum():
    csr = rmat_matrix(256, seed=2)
    bell = BELL.from_csr(csr)
    data16 = bell.data.astype(jnp.bfloat16)
    import dataclasses
    bell16 = BELL(data=data16, block_cols=bell.block_cols,
                  n_rows=bell.n_rows, n_cols=bell.n_cols,
                  bm=bell.bm, bn=bell.bn, blocks_per_row=bell.blocks_per_row)
    x = _x(256, dtype=np.float32, seed=3).astype(jnp.bfloat16)
    got = ops.spmv_bell(bell16, x)
    want = ref.spmv_bell_ref(bell.data, bell.block_cols,
                             jnp.pad(x.astype(jnp.float32),
                                     (0, bell.bn * (-(-256 // bell.bn)) - 256)))
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want)[:256], rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# ELL kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,seed", [(256, 0), (512, 7)])
def test_ell_kernel_vs_dense(n, seed):
    from repro.core.formats import ELL
    csr = rmat_matrix(n, seed=seed)
    ell = ELL.from_csr(csr)
    x = _x(n, seed=seed)
    got = ops.spmv_ell(ell, x)
    want = np.asarray(csr.to_dense()) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_ell_kernel_banded_and_blocksizes():
    from repro.core.formats import ELL
    csr = banded_matrix(384, 16, nnz_per_row=5)
    ell = ELL.from_csr(csr)
    x = _x(384, seed=11)
    want = np.asarray(csr.to_dense()) @ np.asarray(x)
    for bm in (64, 128, 256):
        got = ops.spmv_ell(ell, x, bm=bm)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-4)


def test_ell_pallas_routed_from_dispatcher():
    """use_pallas=True must run the ELL kernel, not fall back to jnp."""
    from repro.core.formats import ELL
    from repro.core.spmv import spmv
    csr = rmat_matrix(256, seed=3)
    ell = ELL.from_csr(csr)
    x = _x(256, seed=4)
    got = spmv(ell, x, use_pallas=True)
    want = spmv(ell, x, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Column-blocked CSR kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_stripes", [1, 2, 4])
def test_csr_colblock_stripes(n_stripes):
    csr = rmat_matrix(512, seed=4)
    x = _x(512, seed=5)
    got = ops.spmv_csr(csr, x, n_stripes=n_stripes)
    want = np.asarray(csr.to_dense()) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_csr_prepared_reuse():
    csr = fd_matrix(256)
    prep = ops.prepare_csr(csr, n_stripes=2)
    for seed in range(3):
        x = _x(256, seed=seed)
        got = ops.spmv_csr_prepared(prep, x)
        want = np.asarray(csr.to_dense()) @ np.asarray(x)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-4)


def test_csr_padded_ref_matches_kernel_layout():
    csr = rmat_matrix(256, seed=6)
    prep = ops.prepare_csr(csr, n_stripes=2)
    xp = jnp.pad(_x(256, seed=7),
                 (0, 2 * prep.stripe_w - 256)).reshape(2, prep.stripe_w)
    want = ref.spmv_csr_padded_ref(prep.vals, prep.cols, prep.rowin, xp)
    got = ops.spmv_csr_prepared(prep, _x(256, seed=7))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want)[:256], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,skv,d,causal,window", [
    (128, 128, 64, True, None),
    (256, 256, 64, True, 64),
    (128, 256, 64, False, None),     # cross-attention shape
    (256, 256, 128, True, None),
])
def test_flash_attention_sweep(sq, skv, d, causal, window):
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(2, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, skv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, skv, d)).astype(np.float32))
    got = flash_attention_pallas(q, k, v, causal=causal, window=window)
    want = ref.mha_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(2, 128, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 128, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 128, 64))).astype(jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True)
    want = ref.mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                       v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=5e-2, atol=5e-2)


def test_flash_window_equals_banded_mask():
    """Sliding-window attention == attention through a banded mask: the
    paper's FD structure applied to the attention matrix."""
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(1, 256, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 256, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 256, 64)).astype(np.float32))
    got = flash_attention_pallas(q, k, v, causal=True, window=32)
    want = ref.mha_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# degenerate geometries + semiring inner loops
# ---------------------------------------------------------------------------

def _empty_csr(n=8):
    z = np.array([], dtype=np.int64)
    return CSR.from_coo(z, z, np.array([], dtype=np.float32), n, n)


def test_all_kernels_handle_empty_matrix():
    """nnz=0 regression: the DIA path used to crash on a zero-diagonal
    band (empty scalar-prefetch operand); every per-call wrapper must
    return exact zeros."""
    from repro.core.formats import ELL

    m = _empty_csr(8)
    x = _x(8)
    for name, got in [
        ("dia", ops.spmv_dia(DIA.from_csr(m), x)),
        ("bell", ops.spmv_bell(BELL.from_csr(m), x)),
        ("ell", ops.spmv_ell(ELL.from_csr(m), x)),
        ("csr", ops.spmv_csr(m, x)),
    ]:
        np.testing.assert_array_equal(np.asarray(got), np.zeros(8),
                                      err_msg=name)


def test_single_row_kernels_match_dense():
    from repro.core.formats import ELL

    m = CSR.from_coo([0, 0, 0], [0, 2, 5], [1.0, 2.0, 3.0], 1, 6)
    x = _x(6, seed=3)
    want = np.asarray(m.to_dense()) @ np.asarray(x)
    for got in (ops.spmv_csr(m, x), ops.spmv_ell(ELL.from_csr(m), x)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


@pytest.mark.parametrize("fmt", ["ell", "csr"])
def test_semiring_kernel_min_plus_matches_reference(fmt):
    """The generalized inner loop (⊗=+, ⊕=min) against a dense reference;
    padding slots must be absorbing (+inf), empty rows reduce to +inf."""
    from repro.core.formats import ELL
    from repro.graph.semiring import MIN_PLUS

    m = rmat_matrix(256, seed=6)
    x = jnp.asarray(np.abs(np.random.default_rng(0).normal(size=256))
                    .astype(np.float32))
    if fmt == "ell":
        container = ELL.from_csr(m, fill=MIN_PLUS.pad_value)
        got = ops.spmv_ell(container, x, semiring=MIN_PLUS)
    else:
        got = ops.spmv_csr(m, x, semiring=MIN_PLUS)

    dense = np.asarray(m.to_dense(), np.float64)
    nz = np.zeros(dense.shape, bool)
    ip, ci = np.asarray(m.indptr), np.asarray(m.indices)
    for r in range(256):
        nz[r, ci[ip[r]:ip[r + 1]]] = True
    want = np.where(nz, dense + np.asarray(x)[None, :], np.inf).min(axis=1)
    np.testing.assert_allclose(np.asarray(got), want.astype(np.float32),
                               rtol=1e-6)


def test_semiring_plus_times_arg_is_bit_identical():
    """Passing the plus_times semiring explicitly must take the exact
    historical kernel path (same bytes out)."""
    from repro.core.formats import ELL
    from repro.graph.semiring import PLUS_TIMES

    m = rmat_matrix(256, seed=8)
    ell = ELL.from_csr(m)
    x = _x(256, seed=9)
    np.testing.assert_array_equal(
        np.asarray(ops.spmv_ell(ell, x)),
        np.asarray(ops.spmv_ell(ell, x, semiring=PLUS_TIMES)))
    np.testing.assert_array_equal(
        np.asarray(ops.spmv_csr(m, x)),
        np.asarray(ops.spmv_csr(m, x, semiring=PLUS_TIMES)))
