"""`PlanCache` — content-addressed storage of compiled plans.

Keys are matrix fingerprints salted with the compile options that change
the produced plan, so the cache is self-invalidating: mutate one stored
value and the digest (hence the key) changes, and the stale plan simply
stops being found and ages out of the LRU.  A module-level
`DEFAULT_CACHE` backs the graph drivers' plans (`graph.drivers`) and
`distributed.spmv.spmv_row_sharded`, so repeated traffic on the same
matrix amortizes to one compile.
"""
from __future__ import annotations

import functools
import hashlib
import threading
from collections import OrderedDict
from typing import Callable, Dict

import numpy as np

from repro.spans import span

from .fingerprint import fingerprint_arrays, matrix_fingerprint


def _fn_token(v) -> str:
    """Distinguish callables beyond module+name: two lambdas (or closures
    over different constants) must not collide, or a sweep passing
    `lambda c: cache_block(c, 4)` and `lambda c: cache_block(c, 8)` would
    silently share one cached plan."""
    code = getattr(v, "__code__", None)
    if code is not None:
        h = hashlib.blake2b(digest_size=8)
        h.update(code.co_code)
        h.update(repr(code.co_consts).encode())
        for cell in (getattr(v, "__closure__", None) or ()):
            h.update(_opt_token(cell.cell_contents).encode())
        h.update(repr(getattr(v, "__defaults__", None)).encode())
        return (f"fn:{getattr(v, '__module__', '?')}."
                f"{getattr(v, '__qualname__', '?')}:{h.hexdigest()}")
    if isinstance(v, functools.partial):
        kw = sorted((v.keywords or {}).items())
        return f"partial:{_fn_token(v.func)}:{v.args!r}:{kw!r}"
    return f"callable:{type(v).__module__}.{type(v).__qualname__}:{v!r}"


def _opt_token(v) -> str:
    """Stable string for one compile option (participates in cache keys)."""
    from repro.reorder import Reordering

    if isinstance(v, Reordering):
        return f"Reordering:{v.strategy}:" + fingerprint_arrays(
            np.asarray(v.row_perm), np.asarray(v.col_perm))
    if callable(v):
        return _fn_token(v)
    if isinstance(v, np.ndarray):
        return "nd:" + fingerprint_arrays(v)
    if hasattr(v, "devices") and hasattr(v, "shape"):      # a jax Mesh
        return f"mesh:{v.shape}:{[getattr(d, 'id', d) for d in np.ravel(v.devices)]}"
    if hasattr(v, "starts"):                               # a RowPartition
        return "part:" + fingerprint_arrays(np.asarray(v.starts))
    return repr(v)


class PlanCache:
    """LRU cache of compiled `SpmvPlan`s keyed by matrix content + options."""

    def __init__(self, max_plans: int = 32):
        self.max_plans = max_plans
        self._plans: "OrderedDict[str, object]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compiles = 0
        self.compile_s = 0.0
        # compile counters split by the plan's resolved scoring mode
        # ('model' -> predictor_*; 'replay'/'analytic' -> oracle_*;
        # unscored compiles count only in the totals above), so serving
        # reports don't average microsecond model compiles into the
        # oracle's seconds
        self.predictor_compiles = 0
        self.predictor_compile_s = 0.0
        self.oracle_compiles = 0
        self.oracle_compile_s = 0.0
        # streaming plan lifecycle (repro.plan.overlay): overlaid plans
        # installed, atomic base swaps landed, re-plans forced by a
        # past-budget (or overlay-ineligible) delta
        self.overlays = 0
        self.swaps = 0
        self.delta_recompiles = 0

    def __len__(self) -> int:
        return len(self._plans)

    @staticmethod
    def key_for(matrix, **opts) -> str:
        salt = ";".join(f"{k}={_opt_token(v)}" for k, v in sorted(opts.items()))
        return f"{matrix_fingerprint(matrix)}|{salt}"

    def contains(self, key: str) -> bool:
        """Warm-pool probe: True iff `key` is resident.  Does NOT touch
        LRU order or hit/miss counters -- admission controllers call this
        every scheduling step, and a probe is not a serve."""
        with self._lock:
            return key in self._plans

    def peek(self, key: str):
        """The resident value for `key`, or None.  Like `contains`, a
        probe: no LRU promotion, no hit/miss accounting."""
        with self._lock:
            return self._plans.get(key)

    @staticmethod
    def chained_key(old_key: str, fingerprint: str) -> str:
        """Re-key an entry under a new (chained) fingerprint, preserving
        the option salt -- the streaming lifecycle's key derivation, with
        no matrix re-hash (`plan.fingerprint.chain_fingerprint` supplies
        the digest)."""
        _, salt = old_key.split("|", 1)
        return f"{fingerprint}|{salt}"

    def install_overlay(self, key: str, overlaid, supersedes: str | None = None
                        ) -> None:
        """Insert an `OverlaidPlan` under its chained key.  The
        superseded generation (previous overlay, or the base plan's key
        when the base should no longer be served directly) is dropped in
        the same critical section, so no scheduling step ever observes
        both generations as warm."""
        with self._lock:
            self._plans[key] = overlaid
            self._plans.move_to_end(key)
            self.overlays += 1
            if supersedes is not None and supersedes != key:
                self._plans.pop(supersedes, None)
            while len(self._plans) > self.max_plans:
                self._plans.popitem(last=False)
                self.evictions += 1

    def swap(self, key: str, builder: Callable[[], object],
             supersedes: str | None = None):
        """Atomic re-plan landing: build (or reuse) the plan for `key`
        and retire the superseded generation.  The drop and the counter
        bump share one critical section -- after `swap` returns, probes
        see exactly one generation."""
        value = self.get_or_build(key, builder)
        with self._lock:
            if supersedes is not None and supersedes != key:
                self._plans.pop(supersedes, None)
            self.swaps += 1
        return value

    def note_delta_recompile(self) -> None:
        """Count one delta-forced re-plan (past staleness budget, or an
        overlay-ineligible delete) -- bumped when the re-plan is
        *scheduled*, so reports show pressure even while the compile is
        still queued."""
        with self._lock:
            self.delta_recompiles += 1

    def get_or_build(self, key: str, builder: Callable[[], object]):
        """Low-level entry: return the cached value for `key` or build,
        insert (evicting LRU past `max_plans`), and return it."""
        with self._lock:
            if key in self._plans:
                self._plans.move_to_end(key)
                self.hits += 1
                return self._plans[key]
        with span("plan_cache.build") as build:
            value = builder()      # build outside the lock (can be slow)
        elapsed = build.seconds
        with self._lock:
            if key not in self._plans:
                self.misses += 1
                self.compiles += 1
                self.compile_s += elapsed
                scoring = (getattr(value, "compile_stats", None)
                           or {}).get("scoring")
                if scoring == "model":
                    self.predictor_compiles += 1
                    self.predictor_compile_s += elapsed
                elif scoring in ("replay", "analytic"):
                    self.oracle_compiles += 1
                    self.oracle_compile_s += elapsed
                self._plans[key] = value
                while len(self._plans) > self.max_plans:
                    self._plans.popitem(last=False)
                    self.evictions += 1
            else:
                self.hits += 1
            self._plans.move_to_end(key)
            return self._plans[key]

    def get_or_compile(self, matrix, **opts):
        """The main entry: `compile`d plan for (matrix contents, opts),
        cached.  Same signature as `repro.plan.compile`.  Runs in a
        `plan_cache.get` span, the key's digest in `plan.fingerprint`."""
        from .compiler import compile as _compile

        with span("plan_cache.get"):
            with span("plan.fingerprint"):
                key = self.key_for(matrix, **opts)
            return self.get_or_build(key, lambda: _compile(matrix, **opts))

    def invalidate(self, matrix_or_fingerprint) -> int:
        """Drop every plan for the given matrix (any options).  Returns the
        number of entries removed.

        Accepts a fingerprint string, or the container itself.  Passing
        the container is what makes invalidation after IN-PLACE mutation
        work: `matrix_fingerprint` memoizes its digest per object, so a
        mutated container would otherwise keep resolving to the
        pre-mutation digest (and the cache would keep serving the stale
        plan).  Here the memo entry is evicted first and plans under BOTH
        digests -- the stale memoized one and the re-hash of the current
        bytes -- are dropped.  Rarely needed for immutable containers,
        where content addressing invalidates implicitly.
        """
        if isinstance(matrix_or_fingerprint, str):
            fps = {matrix_or_fingerprint}
        else:
            from .fingerprint import forget_fingerprint
            stale_fp = forget_fingerprint(matrix_or_fingerprint)
            fps = {matrix_fingerprint(matrix_or_fingerprint)}
            if stale_fp is not None:
                fps.add(stale_fp)
        with self._lock:
            stale = [k for k in self._plans
                     if k.split("|", 1)[0] in fps]
            for k in stale:
                del self._plans[k]
            return len(stale)

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self.hits = 0
            self.misses = 0
            self.evictions = 0
            self.compiles = 0
            self.compile_s = 0.0
            self.predictor_compiles = 0
            self.predictor_compile_s = 0.0
            self.oracle_compiles = 0
            self.oracle_compile_s = 0.0
            self.overlays = 0
            self.swaps = 0
            self.delta_recompiles = 0

    def stats(self) -> Dict[str, float]:
        """Counter snapshot.  `hit_rate` is hits/(hits+misses) over the
        cache's lifetime (0.0 before any traffic); callers wanting a
        windowed rate diff two snapshots (`telemetry.plan_cache_report`
        does exactly that for the serving benchmark's measured phase)."""
        with self._lock:
            served = self.hits + self.misses
            return {"plans": len(self._plans), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions,
                    "compiles": self.compiles,
                    "compile_s": round(self.compile_s, 6),
                    "predictor_compiles": self.predictor_compiles,
                    "predictor_compile_s": round(self.predictor_compile_s, 6),
                    "oracle_compiles": self.oracle_compiles,
                    "oracle_compile_s": round(self.oracle_compile_s, 6),
                    "overlays": self.overlays,
                    "swaps": self.swaps,
                    "delta_recompiles": self.delta_recompiles,
                    "hit_rate": self.hits / served if served else 0.0}


DEFAULT_CACHE = PlanCache()


def get_plan(matrix, **opts):
    """`compile` through the process-wide `DEFAULT_CACHE`."""
    return DEFAULT_CACHE.get_or_compile(matrix, **opts)
