"""`SpmvPlan` — the frozen decision chain for one matrix.

A plan captures everything the per-call stack used to redo on every
`spmv()` invocation: the structure report, the chosen reordering, the
converted storage format, and the pre-padded Pallas kernel layout.
`execute()` is the amortized hot path: it performs zero structure
analysis, zero reordering, zero format conversion, and zero matrix-side
layout padding — only the x gather/scatter transport (when reordered),
the per-call x pad, and the kernel itself.

Repeated-traffic surfaces built on a plan:

  * `execute(x)`        one multiply: the format's `_layout` runner over
                        the layout prepared at compile time;
  * `spmm(X)`           k vectors at once, features last: Y = A @ X for X
                        of shape (n_cols, k), through the row-gather
                        Pallas kernel over a layout built on the first
                        call (`prepare_spmm`); plus-times plans only;
  * `execute_many(X)`   the same product with the vectors as rows,
                        (k, n_cols): `spmm(X.T).T` on plus-times plans,
                        the semiring jnp kernel vmapped over the rows of
                        X on semiring plans;
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
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.formats import CSR
from repro.core.spmv import JNP_KERNELS
from repro.kernels import _layout as kl
from repro.spans import span

# format name -> jitted `(prep, x, interpret=, [semiring=])` runner
RUNNERS = {
    "dia": kl.spmv_dia_prepared,
    "bell": kl.spmv_bell_prepared,
    "ell": kl.spmv_ell_prepared,
    "csr": kl.spmv_csr_prepared,
    "csr-seg": kl.spmv_csr_seg_prepared,
    "hyb": kl.spmv_hyb_prepared,
}


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
    _spmm_prep: Any = dataclasses.field(default=None, repr=False,
                                        compare=False)
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
        """y = A @ x through the frozen plan (original row/col order),
        inside an `spmv.execute` span."""
        with span("spmv.execute", format=self.format_name):
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
        kern = JNP_KERNELS[type(container)]
        return lambda xv: kern(container, xv)

    # -- repeated-traffic surfaces ------------------------------------------

    def prepare_spmm(self):
        """The SpMM layout (`kernels._layout.PreparedSpMM`) of the plan's
        (permuted) CSR, built on the first call, inside a
        `plan.prepare_spmm` span that adds its seconds to
        `compile_stats["prepare_spmm_s"]`."""
        if self._spmm_prep is None:
            csr = self.csr if self.csr is not None else (
                self.container if isinstance(self.container, CSR) else None)
            if csr is None:
                raise ValueError(
                    "plan retains no CSR (compiled with keep_csr=False); "
                    "recompile with keep_csr=True to use the SpMM path")
            with span("plan.prepare_spmm", self.compile_stats,
                      "prepare_spmm_s"):
                self._spmm_prep = kl.prepare_spmm(csr)
        return self._spmm_prep

    def spmm(self, X: jax.Array, interpret: Optional[bool] = None
             ) -> jax.Array:
        """Y = A @ X for k vectors at once, features last: X is
        (n_cols, k) and Y is (n_rows, k), in the original row and column
        order, inside an `spmm.execute` span.  Plus-times plans only;
        the row-gather Pallas kernel runs whatever the plan's format."""
        with span("spmm.execute", format=self.format_name):
            if self._semiring() is not None:
                raise ValueError(f"spmm is plus-times only; this plan runs "
                                 f"{self.semiring!r} (use execute_many)")
            X = jnp.asarray(X)
            if X.ndim != 2 or X.shape[0] != self.n_cols:
                raise ValueError(f"spmm expects (n_cols, k) = "
                                 f"({self.n_cols}, k), got {X.shape}")
            prep = self.prepare_spmm()
            if X.shape[1] == 0:
                return jnp.zeros((self.n_rows, 0), prep.vals.dtype)
            if self.reordering is not None:
                X = self.reordering.permute_x(X)
            Y = kl.spmm_prepared(
                prep, X,
                interpret=self.interpret if interpret is None else interpret)
            if self.reordering is not None:
                return self.reordering.restore_y(Y)
            return Y

    def execute_many(self, X: jax.Array) -> jax.Array:
        """Batched multi-vector SpMV: Y[k] = A @ X[k] for X of shape
        (k, n_cols).  Plus-times plans run `spmm(X.T).T`; semiring plans
        run the semiring jnp kernel vmapped over the rows of X, jitted
        once per plan with the container's arrays as arguments."""
        X = jnp.asarray(X)
        if X.ndim != 2:
            raise ValueError(f"execute_many expects (k, n_cols), got {X.shape}")
        if self._semiring() is None:
            return self.spmm(X.T).T
        if self._many_fn is None:
            self._many_fn = self._build_many()
        return self._many_fn(X)

    def _build_many(self):
        container = self._source_container()
        sr = self._semiring()
        perm = None
        if self.reordering is not None:
            perm = (jnp.asarray(self.reordering.col_perm),
                    jnp.asarray(self.reordering.inv_row_perm))

        @jax.jit
        def many(container, perm, X):
            from repro.graph.semiring import spmv_semiring_jnp

            def one(xv):
                if perm is None:
                    return spmv_semiring_jnp(container, xv, sr)
                cp, irp = perm
                y = spmv_semiring_jnp(container, jnp.take(xv, cp, axis=0), sr)
                return jnp.take(y, irp, axis=0)
            return jax.vmap(one)(X)

        return functools.partial(many, container, perm)

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
