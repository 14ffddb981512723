"""Pallas TPU kernels for the paper's compute hot spots.

  spmv_dia         banded SpMV (FD fast path): pure streaming, no gathers
  spmv_ell         fixed-width ELL: x gathered by XLA, streamed row blocks
  spmv_csr         column-blocked CSR: one-hot segment sum per row block
  spmv_csr_seg     nnz-balanced segmented CSR (merge-CSR)
  spmv_bell        blocked-ELL: data-dependent block-tile gathers (paper P3)
  _layout          shared host-side layout prep: `prepare_*` (run once,
                   at plan-compile time) + `spmv_*_prepared` (zero
                   matrix-side work per call)
  ops              per-call wrappers composing prepare + run, plus the
                   attention entry points
  flash_attention  causal + sliding-window (banded) attention
  paged_attention  decode over block-table KV (BELL pattern on the cache)

Every kernel takes `interpret=None` and resolves it through
`resolve_interpret`: compiled by Mosaic on a TPU backend, interpret mode
everywhere else (validated on CPU against the jnp oracles in ref.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether Pallas kernels run in interpret mode, decided by the backend.

    None means "what the backend supports": Mosaic on a TPU, the
    interpreter elsewhere.  Interpreting on a TPU would hide the device
    behind a Python emulation, so asking for it there raises."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: Pallas kernels "
                         "run compiled by Mosaic there; pass interpret=None")
    return bool(interpret)


def gather_x(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x[idx] as one XLA gather from HBM, for the kernels Mosaic cannot
    gather in (ELL, CSR, CSR-seg).  jnp.take's default bounds handling
    stays: an index out of range reads NaN, not stray memory."""
    return jnp.take(x, idx)
