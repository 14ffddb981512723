"""Reduce a profiler trace (`.xplane.pb`) to the numbers the per-layer
readers take.

The window is the stretch from the first to the last of the benchmark's
own host spans (`bench.multiply`, `bench.query`, one around each
multiply or query).  On each TPU device plane, the `XLA Ops` line holds
one event per operation run and the `XLA Modules` line one per program
run, on the host's clock.  An operation is a Mosaic kernel when its HLO
text calls `tpu_custom_call`; it belongs to the benchmark, not to the
program under test, when it runs inside a program named `jit_bench_*`
(the chain's normalisation).  Busy time is the union of the operations'
intervals inside the window; the idle gaps between them are named by the
innermost host span on the main Python thread that covers their middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import re

SPANS = ("bench.multiply", "bench.query")
MOSAIC = 'custom_call_target="tpu_custom_call"'
BENCH_MODULE = "jit_bench_"
TOP = 10
MIN_GAP_NS = 1000        # shorter gaps are the trace's own granularity

_INSTR = re.compile(r"^%?([\w.\-]+)\s*=")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class DeviceTime:
    """Seconds of one device inside the window."""
    name: str
    busy_s: float        # union of all operations
    program_s: float     # union of the program's operations
    mosaic_s: float      # Mosaic kernels
    xla_s: float         # the program's other operations
    bench_s: float       # the benchmark's own operations
    ops: dict            # "<program>/<instruction>" -> seconds
    idle: list           # [start_ns, end_ns] gaps between busy intervals


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    spans: dict                  # host span name -> count in the window
    devices: list                # DeviceTime per device plane
    gaps: list                   # [(host span, seconds)], longest first

    @property
    def busy_s(self) -> float:
        """Busy seconds, the mean over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def busiest(self) -> DeviceTime | None:
        return max(self.devices, key=lambda d: d.busy_s, default=None)

    def breakdown(self) -> dict:
        total = collections.Counter()
        for d in self.devices:
            total.update(d.ops)
        n = max(len(self.devices), 1)
        ops = [[k, v / n] for k, v in total.most_common(TOP)]
        return {"device_ops": ops,
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def _short_module(name: str) -> str:
    return _MODULE.match(name).group(1)


def _device(plane, lo: float, hi: float) -> DeviceTime:
    lines = {ln.name: ln for ln in plane.lines}
    modules = []
    if "XLA Modules" in lines:
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                          _short_module(e.name))
                         for e in lines["XLA Modules"].events)
    starts = [m[0] for m in modules]
    all_iv, prog_iv = [], []
    mosaic = xla = bench = 0.0
    ops = collections.Counter()
    for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
        s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
        if t <= s:
            continue
        i = bisect.bisect_right(starts, e.start_ns) - 1
        module = modules[i][2] if i >= 0 and e.start_ns <= modules[i][1] \
            else "?"
        m = _INSTR.match(e.name)
        ops[f"{module}/{m.group(1) if m else e.name[:40]}"] += (t - s) * 1e-9
        all_iv.append((s, t))
        if module.startswith(BENCH_MODULE):
            bench += t - s
            continue
        prog_iv.append((s, t))
        if MOSAIC in e.name:
            mosaic += t - s
        else:
            xla += t - s
    busy = _union(all_iv)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [[edges[k], edges[k + 1]] for k in range(0, len(edges), 2)
            if edges[k + 1] - edges[k] >= MIN_GAP_NS]
    return DeviceTime(
        name=plane.name, busy_s=sum(e - s for s, e in busy) * 1e-9,
        program_s=sum(e - s for s, e in _union(prog_iv)) * 1e-9,
        mosaic_s=mosaic * 1e-9, xla_s=xla * 1e-9, bench_s=bench * 1e-9,
        ops=dict(ops), idle=idle)


def _name_gaps(host_events, gaps) -> list:
    """(innermost host span around each gap's middle, gap seconds)."""
    out = []
    for s, e in gaps:
        mid, best = (s + e) / 2, None
        for start, end, name in host_events:
            if start > mid:
                break
            if end >= mid and (best is None or start >= best[0]):
                best = (start, name)
        out.append((best[1] if best else "(no host span)", (e - s) * 1e-9))
    return out


def reduce(path, n_devices: int | None = None) -> TraceSummary:
    """Summarise the trace at `path` over its window of bench spans; only
    the first `n_devices` TPU planes (by id) are counted."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                             ln.name) for e in ln.events)
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append(plane)
    spans = [h for h in host if h[2] in SPANS]
    if not spans:
        raise ValueError(f"{path}: no {SPANS} spans")
    lo, hi = min(h[0] for h in spans), max(h[1] for h in spans)
    counts = collections.Counter(h[2] for h in spans)
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    per_dev = [_device(p, lo, hi) for p in devices[:n_devices]]
    # the benchmark's spans and the Python tracer share the main thread's
    # line, named after the process
    lines = {h[3] for h in spans}
    py = sorted((s, e, n) for s, e, n, line in host if line in lines)
    gaps = sorted((g for d in per_dev for g in d.idle),
                  key=lambda g: g[0] - g[1])[:TOP]
    return TraceSummary(window_s=(hi - lo) * 1e-9, spans=dict(counts),
                        devices=per_dev, gaps=_name_gaps(py, gaps))


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets."""
    trace: TraceSummary | None
    counts: dict
    info: dict
    chips: int
    peak: dict | None

    def traced(self, span: str) -> tuple:
        """(busiest device, count of `span` in the window), or (None, 0)
        when the trace holds no such span or no device."""
        if self.trace is None or not self.trace.devices:
            return None, 0
        n = self.trace.spans.get(span, 0)
        return (self.trace.busiest(), n) if n else (None, 0)
