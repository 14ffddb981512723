"""`SpmvPlan` — the frozen decision chain for one matrix.

A plan captures everything the per-call stack used to redo on every
`spmv()` invocation: the structure report, the chosen reordering, the
converted storage format, and the pre-padded Pallas kernel layout.
`execute()` is the amortized hot path: it performs zero structure
analysis, zero reordering, zero format conversion, and zero matrix-side
layout padding — only the x gather/scatter transport (when reordered),
the per-call x pad, and the kernel itself.

Repeated-traffic surfaces built on a plan:

  * `execute(x)`        one multiply, bit-identical to the per-call
                        `core.spmv.spmv(fmt, x, use_pallas=True)` path
                        (same prepared layout, same kernel);
  * `execute_many(X)`   batched multi-vector SpMV (SpMM): the vectorized
                        jnp format kernel vmapped over the leading axis
                        of X, jitted once per plan;
  * `power_iteration`   iterative driver (paper §I: repeated SpMV drives
                        eigensolvers) that amortizes one plan across all
                        iterations;
  * `address_trace`     the cached telemetry demand trace, so sweeps
                        replay one plan across the whole axis grid.

Plans serialize through `repro.plan.serial` (backed by
`repro.checkpoint`), so a planned matrix survives restart.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.formats import BELL, CSR, DIA, ELL, HYB
from repro.kernels import _layout as kl

# format name -> jitted `(prep, x, interpret=, [semiring=])` runner
RUNNERS = {
    "dia": kl.spmv_dia_prepared,
    "bell": kl.spmv_bell_prepared,
    "ell": kl.spmv_ell_prepared,
    "csr": kl.spmv_csr_prepared,
    "csr-seg": kl.spmv_csr_seg_prepared,
    "hyb": kl.spmv_hyb_prepared,
}


def _jnp_kernels():
    """Container type -> vectorized jnp reference kernel (late import:
    `core.spmv` is a thin client of this package)."""
    from repro.core.spmv import (spmv_bell_jnp, spmv_csr_jnp, spmv_dia_jnp,
                                 spmv_ell_jnp, spmv_hyb_jnp)

    return {CSR: spmv_csr_jnp, ELL: spmv_ell_jnp,
            BELL: spmv_bell_jnp, DIA: spmv_dia_jnp, HYB: spmv_hyb_jnp}


@dataclasses.dataclass
class SpmvPlan:
    """Compiled, reusable execution plan for one matrix.

    Obtain via `repro.plan.compile` (or `PlanCache.get_or_compile`); the
    constructor is an implementation detail shared with `serial.load_plan`.
    """

    fingerprint: str                 # digest of the ORIGINAL matrix
    format_name: str                 # 'dia'|'bell'|'ell'|'csr'|'csr-seg'|'hyb'|'ell-sharded'
    container: Any                   # converted format container (post-reorder)
    prep: Any                        # Prepared* / PaddedCSR / ShardedELL layout
    reordering: Any = None           # repro.reorder.Reordering or None
    report: Any = None               # StructureReport of the (permuted) matrix
    csr: Any = None                  # post-reorder CSR (trace / SpMM source)
    threads: int = 1
    use_pallas: bool = True
    interpret: Optional[bool] = None
    semiring: str = "plus_times"     # (⊕, ⊗) pair the kernels run under
    predicted: Dict[str, Dict] = dataclasses.field(default_factory=dict)
    chosen: str = "none"             # winning (reordering) candidate label
    compile_stats: Dict[str, float] = dataclasses.field(default_factory=dict)
    mesh: Any = None                 # sharded plans only; never serialized
    _many_fn: Any = dataclasses.field(default=None, repr=False, compare=False)
    _traces: Dict = dataclasses.field(default_factory=dict, repr=False,
                                      compare=False)

    # -- geometry -----------------------------------------------------------

    @property
    def n_rows(self) -> int:
        src = self.container if self.container is not None else self.prep
        return int(src.n_rows)

    @property
    def n_cols(self) -> int:
        src = self.container if self.container is not None else self.prep
        return int(src.n_cols)

    def _semiring(self):
        """Resolved `Semiring` object, or None for plus-times (the
        historical bit-exact kernel paths take the None branch)."""
        if self.semiring == "plus_times":
            return None
        from repro.graph.semiring import resolve
        return resolve(self.semiring)

    # -- execution ----------------------------------------------------------

    def execute(self, x: jax.Array, interpret: Optional[bool] = None
                ) -> jax.Array:
        """y = A @ x through the frozen plan (original row/col order)."""
        x = jnp.asarray(x)
        if self.reordering is not None:
            y = self._run(self.reordering.permute_x(x), interpret)
            return self.reordering.restore_y(y)
        return self._run(x, interpret)

    __call__ = execute

    def _run(self, x: jax.Array, interpret: Optional[bool]) -> jax.Array:
        if not self.use_pallas:
            return self._jnp_kernel()(x)
        if self.format_name == "ell-sharded":
            from repro.distributed.spmv import spmv_row_sharded_prepared
            if self._semiring() is not None:
                raise ValueError("sharded plans are plus-times only")
            if self.mesh is None:
                raise ValueError("sharded plan has no mesh bound; pass "
                                 "mesh= to load_plan or set plan.mesh")
            return spmv_row_sharded_prepared(
                self.prep, x, self.mesh,
                interpret=self.interpret if interpret is None else interpret)
        runner, kwargs = self._runner(interpret)
        return runner(self.prep, x, **kwargs)

    def _runner(self, interpret: Optional[bool]):
        """The jitted kernel runner for this plan and its static kwargs."""
        kwargs = {"interpret": self.interpret if interpret is None
                  else interpret}
        sr = self._semiring()
        if sr is not None:
            if self.format_name not in ("ell", "csr", "csr-seg", "hyb"):
                raise ValueError(
                    f"semiring {self.semiring!r} plans support "
                    f"ell/csr/csr-seg/hyb, not {self.format_name!r}")
            kwargs["semiring"] = sr
        return RUNNERS[self.format_name], kwargs

    def lower(self, x: jax.Array):
        """`jax.stages.Lowered` of the program `execute` runs (permuted
        order) for x's shape; `.compile().as_text()` shows whether the
        kernel is a Mosaic custom call (`tpu_custom_call`)."""
        if not self.use_pallas or self.format_name == "ell-sharded":
            raise ValueError(f"{self.format_name!r} plans with use_pallas="
                             f"{self.use_pallas} have no single runner")
        runner, kwargs = self._runner(None)
        return runner.lower(self.prep, x, **kwargs)

    def _source_container(self):
        container = self.container if self.container is not None else self.csr
        if container is None:
            raise ValueError(
                "plan retains no container or CSR (compiled with "
                "keep_csr=False); recompile with keep_csr=True to use the "
                "jnp/SpMM paths")
        return container

    def _jnp_kernel(self):
        container = self._source_container()
        sr = self._semiring()
        if sr is not None:
            from repro.graph.semiring import spmv_semiring_jnp
            return lambda xv: spmv_semiring_jnp(container, xv, sr)
        kern = _jnp_kernels()[type(container)]
        return lambda xv: kern(container, xv)

    # -- repeated-traffic surfaces ------------------------------------------

    def execute_many(self, X: jax.Array) -> jax.Array:
        """Batched multi-vector SpMV (SpMM path): Y[k] = A @ X[k].

        Uses the vectorized jnp format kernel vmapped over the leading
        axis (one fused SpMM, not a Python loop of Pallas launches),
        jitted once per plan and reused across calls.  Matches
        `execute` per vector up to float summation-order tolerance.
        """
        X = jnp.asarray(X)
        if X.ndim != 2:
            raise ValueError(f"execute_many expects (k, n_cols), got {X.shape}")
        if self._many_fn is None:
            self._many_fn = self._build_many()
        return self._many_fn(X)

    def _build_many(self):
        base = self._jnp_kernel()       # semiring-aware one-vector body
        if self.reordering is not None:
            cp = jnp.asarray(self.reordering.col_perm)
            irp = jnp.asarray(self.reordering.inv_row_perm)

            def one(xv):
                return jnp.take(base(jnp.take(xv, cp, axis=0)),
                                irp, axis=0)
        else:
            one = base
        return jax.jit(jax.vmap(one))

    def power_iteration(self, x0: jax.Array, n_iters: int = 16):
        """Dominant-eigenpair driver over the cached plan (paper §I's
        repeated-SpMV analytics).  Returns (eigenvalue estimate, vector)."""
        x = jnp.asarray(x0)
        lam = jnp.array(0.0, x.dtype)
        for _ in range(n_iters):
            y = self.execute(x)
            lam = jnp.linalg.norm(y)
            x = y / jnp.maximum(lam, 1e-30)
        return lam, x

    # -- telemetry ----------------------------------------------------------

    def address_trace(self, machine):
        """The SpMV demand-address trace of the planned (permuted) matrix,
        computed once per machine and cached — telemetry sweeps replay this
        one trace across the whole mechanism/thread grid.

        The trace is FORMAT-AWARE: a 'hyb' plan's trace interleaves the
        light row-major stream with the column-sorted heavy stream (the
        locality the hybrid split buys), a 'csr-seg' plan reuses the flat
        CSR stream (its win is thread balance, not stream shape)."""
        if self.csr is None:
            raise ValueError("plan was compiled with keep_csr=False; "
                             "no CSR retained for trace replay")
        if machine not in self._traces:
            from repro.telemetry.hierarchy import format_address_trace
            self._traces[machine] = format_address_trace(
                self.csr, self.format_name, machine,
                container=self.container)
        return self._traces[machine]

    # -- reporting ----------------------------------------------------------

    def summary(self) -> str:
        r = self.reordering.strategy if self.reordering is not None else "none"
        pred = self.predicted.get(self.chosen, {})
        gf = pred.get("gflops")
        gf_s = f" pred={gf:.2f}GF" if gf is not None else ""
        sr_s = "" if self.semiring == "plus_times" else f" sr={self.semiring}"
        return (f"SpmvPlan[{self.fingerprint[:8]}] fmt={self.format_name}"
                f"{sr_s} reorder={r} threads={self.threads}{gf_s}")
