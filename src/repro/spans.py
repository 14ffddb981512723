"""Stable names for the plan path's stages, for profilers and timers.

Two kinds of name, both recorded by `jax.profiler` on one clock:

* **Stage scopes** (`SCOPES`) are `jax.named_scope`s inside the jitted
  SpMV runners.  They reach every HLO op's `op_name` metadata, which a
  device trace carries as the op's `tf_op`, so device time can be summed
  by stage whatever names XLA gives its fusions:

    spmv.x_prep    x's pad and reshape before the gather or the kernel
    spmv.x_gather  the XLA gather of x, with the index arithmetic feeding it
    spmv.kernel    the Pallas call, with its operand and result reshapes
    spmv.merge     what joins the kernel's output into y: the stripe
                   reduce, the segment-sum carry merge, HYB's join, the
                   final slices
    spmv.stitch    the row-sharded path's concatenation of the y slabs

  The multi-vector runner (`kernels._layout.spmm_prepared`) names its
  stages apart (`SPMM_SCOPES`), so that its device time is read apart
  from the one-vector runners':

    spmm.x_prep    X's cast and the output's allocation
    spmm.x_gather  the XLA row gather of X for one group of chunks
    spmm.kernel    the Pallas segment-sum call
    spmm.merge     the final row slice of Y

* **Host spans** (`SPANS`) are `jax.profiler.TraceAnnotation`s around
  host steps, opened with `span`.  A `span` also times itself: it adds
  its seconds to a `stats` dict when given one, and to every dict that a
  `collect()` around it has open.

    spmv.execute      `SpmvPlan.execute`: dispatch of one multiply
    graph.query       one blocking graph driver call (pagerank, bfs, ...)
    graph.operand     the analytic's operand, derived from the adjacency
    graph.iteration   one iteration: frontier, multiply, sync, advance
    graph.host_sync   the wait for the product's copy to the host
    graph.advance     the stepper's update from the product
    plan_cache.get    `PlanCache.get_or_compile`: key, lookup, any build
    plan.fingerprint  the content digest that keys the cache
    plan_cache.build  a plan cache miss: the builder's run
    plan.compile      `plan.compile`, with one span per phase below
    plan.reorder, plan.analyze, plan.predict, plan.convert, plan.prepare

  and for the multi-vector path (`SPMM_SPANS`):

    spmm.execute       `SpmvPlan.spmm`: dispatch of one multiply of k vectors
    plan.prepare_spmm  the SpMM layout's build, on a plan's first `spmm`

With no profiler running a span costs a few microseconds of host time;
the scopes are metadata and cost nothing on the device.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import re
import time
from typing import Callable, Dict, Iterator, Optional

import jax

X_PREP = "spmv.x_prep"
X_GATHER = "spmv.x_gather"
KERNEL = "spmv.kernel"
MERGE = "spmv.merge"
STITCH = "spmv.stitch"
SCOPES = (X_PREP, X_GATHER, KERNEL, MERGE, STITCH)

SPMM_X_PREP = "spmm.x_prep"
SPMM_X_GATHER = "spmm.x_gather"
SPMM_KERNEL = "spmm.kernel"
SPMM_MERGE = "spmm.merge"
SPMM_SCOPES = (SPMM_X_PREP, SPMM_X_GATHER, SPMM_KERNEL, SPMM_MERGE)

SPANS = ("spmv.execute", "graph.query", "graph.operand", "graph.iteration",
         "graph.host_sync", "graph.advance", "plan_cache.get",
         "plan.fingerprint", "plan_cache.build", "plan.compile",
         "plan.reorder", "plan.analyze", "plan.predict", "plan.convert",
         "plan.prepare")
SPMM_SPANS = ("spmm.execute", "plan.prepare_spmm")

_SINKS: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_span_sinks", default=())
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class span:
    """`with span(name, stats=None, key=None, **args):` a profiler
    annotation named `name` (with `args` shown beside it) that also
    times its body.  The seconds are kept in `.seconds`, added to
    `stats[key or name]` when `stats` is a dict, and to `collect()`'s
    open dicts under `name`."""

    __slots__ = ("name", "stats", "key", "seconds", "_annotation", "_t0")

    def __init__(self, name: str, stats: Optional[Dict] = None,
                 key: Optional[str] = None, **args):
        self.name, self.stats, self.key = name, stats, key
        self.seconds = 0.0
        self._annotation = jax.profiler.TraceAnnotation(name, **args)

    def set(self, **args) -> None:
        """Add `args` to the open annotation (for what the body found)."""
        self._annotation.set_metadata(**args)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self.stats is not None:
            key = self.key or self.name
            self.stats[key] = self.stats.get(key, 0.0) + self.seconds
        for sink in _SINKS.get():
            sink[self.name] = sink.get(self.name, 0.0) + self.seconds


def spanned(name: str) -> Callable:
    """Decorator: run each call of the function inside `span(name)`."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


@contextlib.contextmanager
def collect() -> Iterator[Dict[str, float]]:
    """Yield a dict that sums, by span name, the seconds of every span
    that closes inside the `with` (in this thread or task)."""
    sink: Dict[str, float] = {}
    token = _SINKS.set(_SINKS.get() + (sink,))
    try:
        yield sink
    finally:
        _SINKS.reset(token)


def scope_of(op_name: str, scopes: tuple = SCOPES) -> Optional[str]:
    """The stage scope of `scopes` an HLO op's `op_name` lies in, or None
    when it lies in none or in more than one.  (XLA joins the names of
    ops it merges with ';'.)"""
    found = {part for part in re.split(r"[/;]", op_name) if part in scopes}
    return found.pop() if len(found) == 1 else None


def staged_ops(hlo_text: str) -> Dict[str, str]:
    """instruction -> op_name of the ops a compiled program's entry
    computation runs (fused and called computations run inside them)
    with an op_name the program wrote.  Left out: parameters and
    constants, which run no code, and what XLA adds with no op_name or
    with only an argument's name (copies, bitcasts)."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    out = {}
    for line in entry[: entry.index("\n}")].splitlines()[1:]:
        m = _INSTRUCTION.match(line)
        op_name = _OP_NAME.search(line)
        if m and op_name and m.group(2) not in ("parameter", "constant") \
                and "jit(" in op_name.group(1):
            out[m.group(1)] = op_name.group(1)
    return out


__all__ = ["SCOPES", "SPANS", "X_PREP", "X_GATHER", "KERNEL", "MERGE",
           "STITCH", "SPMM_SCOPES", "SPMM_SPANS", "SPMM_X_PREP",
           "SPMM_X_GATHER", "SPMM_KERNEL", "SPMM_MERGE", "span", "spanned",
           "collect", "scope_of", "staged_ops"]
