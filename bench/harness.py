"""One run of one cell: set-up, the measured window, the check.

`execute` does everything after the look for a chip, so tests can drive
a whole run on the CPU.  The cell's traffic file names the module that
drives it, `ops/<op>.py`, which provides `setup(run)`,
`window(run, seconds)`, `release(run)` and `check(run)` (and
`control(run)` for calibration).
The per-layer metrics are read by `metrics/<name>.py` readers from the
reduced trace of a `trace` run.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time

import jax

from bench import registry, trace as tracing, work


class Run:
    """What one run of a cell knows, filled in as it goes."""

    def __init__(self, spec: dict, workload: dict, seed: int, devices):
        self.spec, self.workload = spec, workload
        self.cfg = registry.config(spec, workload["config"])
        self.traffic = registry.traffic(workload["traffic"])
        self.op = registry.load_module("ops", self.traffic["op"])
        self.seed, self.devices = int(seed), list(devices)
        self.setup: dict = {}     # set-up split, seconds
        self.e2e: dict = {}       # end-to-end readings by metric name
        self.counts: dict = {}    # work done in the window
        self.info: dict = {}      # matrix and plan facts
        self.state: dict = {}     # the op module's objects
        self.window_s = 0.0
        self.attempted = 0

    def timed(self, name: str, fn, *args, **kwargs):
        t = time.perf_counter()
        out = fn(*args, **kwargs)
        self.setup[name] = time.perf_counter() - t
        return out


class _CompileCounter:
    """Counts the programs compiled, or loaded from the persistent cache,
    while it is open, and the seconds that took: none should be in the
    window."""

    def __init__(self):
        self.n, self.seconds, self.cache_hits = 0, 0.0, 0

    def _duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def split(self) -> dict:
        return {"programs": self.n, "cache_hits": self.cache_hits,
                "compile_s": self.seconds}


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def _per_layer(run, summary, peak) -> dict:
    out = {}
    reading = tracing.Reading(trace=summary, counts=run.counts,
                              info=run.info, chips=len(run.devices),
                              peak=peak)
    for m in registry.metrics_for(run.spec, "per_layer",
                                  run.workload["name"]):
        value = registry.load_module("metrics", m["name"]).read(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _end_to_end(run, setup_s: float) -> dict:
    run.e2e["setup_s"] = setup_s
    out = {}
    for m in registry.metrics_for(run.spec, "end_to_end",
                                  run.workload["name"]):
        if m["name"] not in run.e2e:
            raise KeyError(f"{run.workload['name']}: the op reported "
                           f"no {m['name']!r}")
        out[m["name"]] = {"value": run.e2e[m["name"]], "unit": m["unit"]}
    return out


def execute(spec: dict, workload: dict, seed: int, seconds: float,
            trace: bool, devices, t_start: float, log=sys.stderr,
            keep_trace: str | None = None) -> dict:
    """Run one cell on `devices`; returns the result object.  A traced
    run's profile is deleted once read, unless `keep_trace` names a
    directory to leave it in."""
    run = Run(spec, workload, seed, devices)
    dev = devices[0]
    peak = work.peaks(dev.device_kind) if dev.platform == "tpu" else None
    with _CompileCounter() as compiles:
        run.op.setup(run)
    setup_s = time.perf_counter() - t_start
    print(json.dumps({"setup_split": run.setup, "setup_s": setup_s,
                      "setup_compiles": compiles.split(), "info": run.info}),
          file=log, flush=True)

    trace_dir = keep_trace or (tempfile.mkdtemp(prefix="bench-trace-")
                               if trace else None)
    with _CompileCounter() as compiles:
        if trace:
            jax.profiler.start_trace(trace_dir)
        run.op.window(run, seconds)
        if trace:
            jax.profiler.stop_trace()
    memory_peak = _memory_peak(devices)
    print(json.dumps({"window_s": run.window_s, "counts": run.counts,
                      "window_compiles": compiles.split()}),
          file=log, flush=True)
    run.op.release(run)
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": run.attempted, "failed": 0}
    if trace:
        try:
            summary = tracing.reduce(tracing.find_xplane(trace_dir),
                                     len(devices))
        finally:
            if not keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = _per_layer(run, summary, peak)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = summary.breakdown()
    else:
        result["metrics"] = _end_to_end(run, setup_s)
        result["device"] = device

    checks = run.op.check(run)
    ok = all(math.isfinite(v) and v <= limit for v, limit in checks.values())
    result["correct"] = ok
    result["failed"] = 0 if ok else run.attempted
    result["checks"] = {k: {"value": v, "limit": limit}
                        for k, (v, limit) in checks.items()}
    for k, (v, limit) in checks.items():
        print(f"check {k} {v!r} limit {limit!r}", file=log, flush=True)
    return result
