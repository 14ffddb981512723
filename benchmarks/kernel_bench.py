"""Kernel microbenchmarks: each Pallas kernel vs its jnp oracle.

Wall-times on this container measure the *interpret-mode* kernel (Python
loop over grid cells) and the jit'd jnp oracle on CPU -- meaningful for
correctness and relative shape scaling, NOT for TPU throughput.  The TPU
throughput story is the traffic model (traffic_bench) + the dry-run
roofline; this bench additionally reports the model-predicted v5e GFLOP/s
per (kernel x matrix) from core.traffic.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import traffic
from repro.core.formats import BELL, CSR, DIA
from repro.core.generators import banded_matrix, fd_matrix, rmat_matrix
from repro.core.spmv import spmv_csr_jnp
from repro.kernels import _layout as kl
from repro.kernels.paged_attention import paged_attention

from .common import emit, time_fn


def _err(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def spmv_kernels(n: int = 1024) -> str:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    rows = []
    for name, gen in (("fd", fd_matrix), ("rmat", rmat_matrix),
                      ("banded32", lambda m: banded_matrix(m, 32, nnz_per_row=6))):
        csr = gen(n)
        y_ref = spmv_csr_jnp(csr, x)
        t_ref = time_fn(lambda: spmv_csr_jnp(csr, x))

        dia = DIA.from_csr(csr)
        if dia.n_diags <= 160:
            dia_prep = kl.prepare_dia(dia, bn=128)
            y = kl.spmv_dia_prepared(dia_prep, x)
            rows.append(["dia", name, n, dia.n_diags, _err(y, y_ref),
                         time_fn(lambda: kl.spmv_dia_prepared(dia_prep, x),
                                 iters=2),
                         t_ref,
                         traffic.stream_policy(
                             csr, int(np.abs(np.asarray(dia.offsets)).max())
                         ).roofline_gflops])

        bell = BELL.from_csr(csr)
        bell_prep = kl.prepare_bell(bell)
        y = kl.spmv_bell_prepared(bell_prep, x)
        rows.append(["bell", name, n, bell.blocks_per_row, _err(y, y_ref),
                     time_fn(lambda: kl.spmv_bell_prepared(bell_prep, x),
                             iters=2), t_ref,
                     traffic.bell_policy(bell.density(), csr)
                     .roofline_gflops])

        prep = kl.prepare_csr(csr, n_stripes=4)
        y = kl.spmv_csr_prepared(prep, x)
        rows.append(["csr_colblock", name, n, 4, _err(y, y_ref),
                     time_fn(lambda: kl.spmv_csr_prepared(prep, x), iters=2), t_ref,
                     traffic.col_blocked_policy(csr, 4).roofline_gflops])
    return emit(rows, ["kernel", "matrix", "n", "param", "max_err",
                       "t_interp_s", "t_jnp_s", "v5e_roofline_gflops"],
                "kernel_bench: Pallas kernels (interpret) vs jnp oracle + "
                "v5e traffic-model roofline")


def flash_attention_bench() -> str:
    rng = np.random.default_rng(1)
    rows = []
    from repro.kernels import ref as kref
    for (sq, window) in ((256, None), (256, 128)):
        q = jnp.asarray(rng.normal(size=(4, sq, 64)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(4, sq, 64)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(4, sq, 64)).astype(np.float32))
        from repro.kernels.flash_attention import flash_attention_pallas
        o = flash_attention_pallas(q, k, v, causal=True, window=window)
        o_ref = kref.mha_ref(q, k, v, causal=True, window=window)
        rows.append(["flash", sq, str(window), _err(o, o_ref),
                     time_fn(lambda: flash_attention_pallas(
                         q, k, v, causal=True, window=window)),
                     time_fn(lambda: kref.mha_ref(
                         q, k, v, causal=True, window=window))])
    return emit(rows, ["kernel", "seq", "window", "max_err", "t_interp_s",
                       "t_ref_s"],
                "flash_attention: banded (sliding-window) attention vs ref")


def paged_attention_bench() -> str:
    rng = np.random.default_rng(2)
    from repro.kernels import ref as kref
    rows = []
    for (bsz, h, hd, block, mb) in ((2, 4, 64, 16, 4), (4, 8, 128, 16, 8)):
        n_blocks = bsz * mb
        q = jnp.asarray(rng.normal(size=(bsz, h, hd)).astype(np.float32))
        kp = jnp.asarray(rng.normal(size=(n_blocks, block, h, hd))
                         .astype(np.float32))
        vp = jnp.asarray(rng.normal(size=(n_blocks, block, h, hd))
                         .astype(np.float32))
        tables = jnp.asarray(rng.permutation(n_blocks)
                             .reshape(bsz, mb).astype(np.int32))
        lengths = jnp.asarray(
            rng.integers(1, mb * block + 1, bsz).astype(np.int32))
        got = paged_attention(q, kp, vp, tables, lengths)
        want = kref.paged_attention_ref(q, kp, vp, tables, lengths)
        rows.append(["paged", bsz, h, block, mb, _err(got, want),
                     time_fn(lambda: paged_attention(
                         q, kp, vp, tables, lengths), iters=2),
                     time_fn(lambda: kref.paged_attention_ref(
                         q, kp, vp, tables, lengths))])
    return emit(rows, ["kernel", "batch", "heads", "block", "max_blocks",
                       "max_err", "t_interp_s", "t_ref_s"],
                "paged_attention: block-table decode kernel vs oracle")


def main() -> None:
    spmv_kernels()
    flash_attention_bench()
    paged_attention_bench()


if __name__ == "__main__":
    main()
