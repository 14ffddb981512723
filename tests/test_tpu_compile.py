"""Ahead-of-time compiles of the SpMV runners for a described TPU v5e.

Nothing runs: each test lowers a plan runner at 2^22-row shapes (and DIA
at the benchmark FD cell's 2^24 rows too, and the SpMM runner at the
products cell's 2^21 rows and k = 256) with
`interpret=False` and compiles it for a v5e chip that is described, not
attached, so what Mosaic or the device memory would refuse fails here.
Each compiled program must hold a Mosaic kernel (`tpu_custom_call`), put
each op it wrote in one stage scope (`repro.spans`), and fit one chip's
16 GB of HBM.  The topology is described in a fixture,
never at import, and the persistent compilation cache is off around the
compiles (a TPU entry written here could not be read back).
"""
from __future__ import annotations

import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from jax.sharding import (NamedSharding, PartitionSpec,       # noqa: E402
                          SingleDeviceSharding)

from repro.graph.semiring import MIN_PLUS, OR_AND             # noqa: E402
from repro.kernels import _layout as kl                       # noqa: E402
from repro.spans import SPMM_SCOPES, scope_of, staged_ops     # noqa: E402

N = 1 << 22            # rows of the chip smoke's FD and R-MAT matrices
N_FD = 1 << 24         # rows of the benchmark's FD stencil (4096 x 4096)
N_GCN = 1 << 21        # vertices of the benchmark's products graph
HBM_BYTES = 16e9       # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _layouts(sharding):
    """Prepared layouts at the 2^22-row shapes the chip smoke builds:
    FD (9 nnz/row) for DIA, BELL, ELL and CSR (BELL's 12.6 MB
    block-column table runs in SMEM-sized calls), R-MAT (~33M nnz, hub
    rows) for CSR-seg and HYB; and 'dia-2p24', the band the plan builds
    for the 2^24-row periodic stencil (9 interior and 12 wrap
    diagonals)."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32 = jnp.int32
    b = N // 128
    ell = kl.PreparedELL(data=s((b, 128, 128)), idx=s((b, 128, 128), i32),
                         n_rows=N, n_cols=N, x_pad=N)

    def seg(n_segs, rwin):
        return kl.PreparedSegCSR(
            vals=s((n_segs, 512)), cols=s((n_segs, 512), i32),
            rid=s((n_segs, 512), i32), row_ids=s((n_segs, rwin), i32),
            nonempty=s((N,), jnp.bool_), n_rows=N, n_cols=N, rwin=rwin,
            seg_len=512, x_pad=N)

    nbr = N // 8
    return {
        "dia": (kl.spmv_dia_prepared, kl.PreparedDIA(
            band=s((9, N)), offsets=s((9,), i32), n_rows=N, n_cols=N,
            bn=512), N),
        "dia-2p24": (kl.spmv_dia_prepared, kl.PreparedDIA(
            band=s((21, N_FD)), offsets=s((21,), i32), n_rows=N_FD,
            n_cols=N_FD, bn=512), N_FD),
        "bell": (kl.spmv_bell_prepared, kl.PreparedBELL(
            data=s((nbr, 6, 8, 128)), block_cols=s((nbr, 6), i32),
            n_rows=N, n_cols=N, x_pad=N), N),
        "ell": (kl.spmv_ell_prepared, ell, N),
        "csr": (kl.spmv_csr_prepared, kl.PaddedCSR(
            vals=s((1, b, 1152)), cols=s((1, b, 1152), i32),
            rowin=s((1, b, 1152), i32), n_rows=N, n_cols=N, stripe_w=N,
            bm=128), N),
        "csr-seg": (kl.spmv_csr_seg_prepared, seg(65536, 128), N),
        "hyb": (kl.spmv_hyb_prepared, kl.PreparedHYB(
            light=ell, heavy=seg(63488, 512), n_rows=N, n_cols=N), N),
    }


def _check(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # each op the program wrote lies in one stage scope
    for instr, op_name in staged_ops(text).items():
        assert scope_of(op_name) is not None, (instr, op_name)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one chip"


@pytest.mark.parametrize("fmt,semiring", [
    ("dia", None), ("dia-2p24", None), ("bell", None), ("ell", None), ("csr", None),
    ("csr-seg", None), ("hyb", None),
    ("ell", "min_plus"), ("csr-seg", "min_plus"), ("hyb", "or_and"),
])
def test_runner_compiles_to_mosaic(one_chip, fmt, semiring):
    runner, prep, n_cols = _layouts(one_chip)[fmt]
    x = jax.ShapeDtypeStruct((n_cols,), jnp.float32, sharding=one_chip)
    kwargs = {"interpret": False}
    if semiring is not None:
        kwargs["semiring"] = {"min_plus": MIN_PLUS, "or_and": OR_AND}[semiring]
    _check(runner.lower(prep, x, **kwargs).compile())


def test_row_sharded_ell_compiles_on_four_chips(topo):
    from repro.distributed.spmv import row_mesh, row_sharded_program

    mesh = row_mesh(topo.devices[:4])
    rows = N // 4
    slab = NamedSharding(mesh, PartitionSpec("shards", None, None))
    data = jax.ShapeDtypeStruct((4, rows, 128), jnp.float32, sharding=slab)
    idx = jax.ShapeDtypeStruct((4, rows, 128), jnp.int32, sharding=slab)
    x = jax.ShapeDtypeStruct((N,), jnp.float32,
                             sharding=NamedSharding(mesh, PartitionSpec()))
    prep = kl.ShardedELL(data=data, idx=idx, n_rows=N, n_cols=N,
                         starts=np.arange(5) * rows, bm=128)
    compiled = row_sharded_program(prep, mesh, interpret=False).lower(
        data, idx, x).compile()
    _check(compiled)


_LINE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([a-z][\w\-]*)\(")
_OP = re.compile(r'op_name="([^"]*)"')


def test_spmm_runner_compiles_to_mosaic_with_its_layout_as_parameters(
        one_chip):
    """The products cell's runner: the kernel is a Mosaic call, every
    array of the prepared layout (the 217,600 chunks of 512 slots that the
    cell's 107M nonzeros fill) and X are parameters of the program, no
    constant is larger than a scalar table, and each op the runner wrote lies in one SpMM stage
    (scalar index arithmetic and the loop's own control left out)."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, chunks, chunk, k = jnp.int32, 217600, 512, 256
    prep = kl.PreparedSpMM(
        cols=s((chunks, chunk), i32), vals=s((chunks, chunk)),
        rloc=s((chunks, chunk), i32), blk=s((chunks,), i32),
        first=s((chunks,), i32), n_rows=N_GCN, n_cols=N_GCN, bm=128,
        chunk=chunk, group=512)
    compiled = kl.spmm_prepared.lower(prep, s((N_GCN, k)),
                                      interpret=False).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    entry = text[text.index("\nENTRY "):]
    head = entry[: entry.index("{")]
    for shape in (f"s32[{chunks},{chunk}]", f"f32[{chunks},{chunk}]",
                  f"s32[{chunks}]", f"f32[{N_GCN},{k}]"):
        assert shape in head, shape
    staged = 0
    for line in text.splitlines():
        m = _LINE.match(line)
        if not m:
            continue
        shape, opcode = m.group(2), m.group(3)
        if opcode == "constant":
            dims = re.search(r"\[([\d,]*)\]", shape).group(1)
            assert np.prod([int(d) for d in dims.split(",") if d]) <= 1024
            continue
        op = _OP.search(line)
        if opcode in ("parameter", "while", "get-tuple-element", "tuple",
                      "bitcast", "copy", "copy-start", "copy-done") \
                or not op or "jit(" not in op.group(1) \
                or re.match(r"\w+\[\]", shape):
            continue
        assert scope_of(op.group(1), SPMM_SCOPES) is not None, line[:200]
        staged += 1
    assert staged >= 4
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one chip"
