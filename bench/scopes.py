"""The program's stage scopes and host spans, read from a profile.

The runners name their stages with `jax.named_scope`s (`STAGES`), which
reach each HLO op's `op_name`; a device trace carries it as the op's
`tf_op`.  Each operation falls in one group:

  a stage    its op_name lies under that stage scope
  spmv.other its op_name lies under none (an op the program wrote outside
             every stage, or a program that names no stages)
  (compiler) it has no op_name, or only an argument's name: copies,
             prefetches and slices XLA added itself

The driver's host steps are `jax.profiler.TraceAnnotation`s (`SPANS`).

Two ways to the groups.  From a kept `.xplane.pb` (`summarize`), the
XPlane protobuf is decoded here (jax's `ProfileData` does not expose the
ops' metadata): device seconds per group, count and seconds of each span
with its self time, and the device's idle seconds under each innermost
span.  Inside the run that made the trace, where the harness has
already deleted it (`live_groups`), the op_names come from the HLO of
the executables the process still holds, matched to the trace's
`<program>/<instruction>` names.

    python3 -m bench.scopes --trace DIR
    python3 -m bench.scopes --workload rmat.pagerank --seed 7 --seconds 10

(from the checkout's root) summarise a trace directory, or run one
traced run of a cell on a TPU and summarise it; either prints one JSON
object.
"""
from __future__ import annotations

import collections
import re

from bench import trace

STAGES = ("spmv.x_prep", "spmv.x_gather", "spmv.kernel", "spmv.merge",
          "spmv.stitch")
OTHER, COMPILER = "spmv.other", "(compiler)"
SPANS = ("spmv.execute", "graph.query", "graph.operand", "graph.iteration",
         "graph.host_sync", "graph.advance", "plan_cache.get",
         "plan.fingerprint", "plan_cache.build", "plan.compile",
         "plan.reorder", "plan.analyze", "plan.predict", "plan.convert",
         "plan.prepare")
NO_SPAN = "(no span)"

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def group_of(op_name: str | None) -> str:
    """The group of an op by its op_name (or `tf_op`, "name:type")."""
    if not op_name or "jit(" not in op_name:
        return COMPILER
    found = {p for p in re.split(r"[/;:]", op_name) if p in STAGES}
    return found.pop() if len(found) == 1 else OTHER


# -- the trace file ---------------------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints
    and fixed-width fields, memoryviews for length-delimited ones."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _device_ops(plane):
    """(start_ns, end_ns, program name, tf_op) of each XLA op of one
    `XPlane` (tsl/profiler/protobuf/xplane.proto: plane 3 lines,
    4 event_metadata, 5 stat_metadata; line 2 name, 3 timestamp_ns,
    4 events; event 1 metadata_id, 2 offset_ps, 3 duration_ps; metadata
    2 name, 5 stats; stat 1 metadata_id, 3 uint64, 5 str, 7 ref).  An
    op's program is the `XLA Modules` event, "<name>(<program id>)",
    whose id its `program_id` stat holds."""
    stat_names, meta, lines = {}, {}, {}
    for f, v in _fields(plane):
        if f in (4, 5):
            entry = dict(_fields(v))
            if f == 5:
                stat_names[entry.get(1, 0)] = _text(
                    dict(_fields(entry[2])).get(2, b""))
            else:
                meta[entry.get(1, 0)] = entry.get(2, b"")
        elif f == 3:
            line = dict(_fields(v))
            lines.setdefault(_text(line.get(2, b"")), []).append(v)

    def events(name):
        for raw in lines.get(name, ()):
            t0 = next((v for f, v in _fields(raw) if f == 3), 0)
            for f, v in _fields(raw):
                if f == 4:
                    ev = dict(_fields(v))
                    start = t0 + ev.get(2, 0) / 1e3
                    yield start, start + ev.get(3, 0) / 1e3, ev.get(1, 0)

    def stats(mid):
        out = {}
        for f, v in _fields(meta.get(mid, b"")):
            if f == 5:
                st = dict(_fields(v))
                name = stat_names.get(st.get(1))
                out[name] = (st[3] if 3 in st else _text(st[5]) if 5 in st
                             else stat_names.get(st.get(7), ""))
        return out

    programs = {}
    for _, _, mid in events("XLA Modules"):
        name = _text(dict(_fields(meta.get(mid, b""))).get(2, b""))
        m = _PROGRAM_ID.search(name)
        if m:
            programs[int(m.group(1))] = name[:m.start()]
    ops, info = [], {}
    for s, e, mid in events("XLA Ops"):
        if mid not in info:
            st = stats(mid)
            info[mid] = (programs.get(st.get("program_id"), ""),
                         st.get("tf_op"))
        ops.append((s, e) + info[mid])
    return ops


def _planes(data: bytes):
    """(name, raw XPlane) of an XSpace (field 1: planes)."""
    for f, v in _fields(memoryview(data)):
        if f == 1:
            name = next((_text(pv) for pf, pv in _fields(v) if pf == 2), "")
            yield name, v


def _host_spans(data, lo: float, hi: float) -> list:
    """(start_ns, end_ns, name, line) of the bench's and the program's
    spans that start inside the window."""
    names = set(SPANS) | set(trace.SPANS)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                            ln.name) for e in ln.events
                           if e.name in names and lo <= e.start_ns <= hi)
    return sorted(out, key=lambda s: (s[3], s[0], -s[1]))


def _span_times(spans) -> dict:
    """name -> {count, total_s, self_s}; self time is the total less the
    time of the spans directly inside it on the same thread."""
    out = collections.defaultdict(lambda: {"count": 0, "total_s": 0.0,
                                           "self_s": 0.0})
    stack = []
    for s, e, name, line in spans:
        while stack and (stack[-1][3] != line or stack[-1][1] <= s):
            stack.pop()
        rec = out[name]
        rec["count"] += 1
        rec["total_s"] += (e - s) * 1e-9
        rec["self_s"] += (e - s) * 1e-9
        if stack:
            out[stack[-1][2]]["self_s"] -= (e - s) * 1e-9
        stack.append((s, e, name, line))
    return {k: dict(v) for k, v in out.items()}


def _span_idle(spans, gaps) -> dict:
    """Idle seconds under each innermost span, split by overlap."""
    out = collections.Counter()
    for g0, g1 in gaps:
        inside = [s for s in spans if s[0] < g1 and s[1] > g0]
        cuts = sorted({g0, g1} | {t for s in inside for t in s[:2]
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            cover = [s for s in inside if s[0] <= a and s[1] >= b]
            name = max(cover, key=lambda s: (s[0], -s[1]))[2] if cover \
                else NO_SPAN
            out[name] += (b - a) * 1e-9
    return dict(out)


def summarize(path, n_devices: int | None = None) -> dict:
    """Device seconds per group for each device, the host spans, and the
    idle seconds under each innermost span, over the trace's window of
    bench spans (`trace.reduce`)."""
    from jax.profiler import ProfileData

    summary = trace.reduce(path, n_devices)
    raw = open(path, "rb").read()
    data = ProfileData.from_file(str(path))
    spans = [s for s in _host_spans(data, 0, float("inf"))
             if s[2] in trace.SPANS]
    lo, hi = min(s[0] for s in spans), max(s[1] for s in spans)
    wanted = {d.name for d in summary.devices}
    groups = {}
    for name, plane in _planes(raw):
        if name not in wanted:
            continue
        sums = collections.Counter()
        for s, e, program, tf_op in _device_ops(plane):
            s, e = max(s, lo), min(e, hi)
            if e > s and not program.startswith(trace.BENCH_MODULE):
                sums[group_of(tf_op)] += (e - s) * 1e-9
        groups[name] = dict(sums)
    host = _host_spans(data, lo, hi)
    idle = [g for d in summary.devices for g in d.idle]
    return {"window_s": summary.window_s,
            "program_s": {d.name: d.program_s for d in summary.devices},
            "scopes": groups, "host_spans": _span_times(host),
            "span_idle": _span_idle(host, idle)}


# -- inside the run that made the trace -------------------------------------

def _hlo_groups(text: str) -> dict:
    """instruction -> group for every instruction of an HLO module (names
    are unique in a module; the entry's, and a loop body's, run as
    device events)."""
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(m.group(2))
            out[m.group(1)] = group_of(op.group(1) if op else None)
    return out


def live_groups() -> dict:
    """program name -> {instruction -> group} from the HLO of every
    executable this process holds on its TPU; an instruction that two
    programs of one name group differently is left out.  Empty off a
    TPU: the executables there are not the ones a TPU trace timed."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    out, clash = {}, set()
    for exe in jax.devices()[0].client.live_executables():
        try:
            modules = exe.hlo_modules()
        except Exception:         # an executable that keeps no HLO
            continue
        for module in modules:
            seen = out.setdefault(module.name, {})
            for instr, group in _hlo_groups(module.to_string()).items():
                if seen.setdefault(instr, group) != group:
                    clash.add((module.name, instr))
    for name, instr in clash:
        out[name].pop(instr, None)
    return out


def device_groups(dev) -> dict | None:
    """Seconds per group of a `trace.DeviceTime`'s program operations,
    from `live_groups`; None when an operation cannot be found there, or
    when none lies in a stage (a program that names no stages)."""
    groups = live_groups()
    sums = collections.Counter()
    for key, seconds in dev.ops.items():
        module, instr = key.split("/", 1)
        if module.startswith(trace.BENCH_MODULE):
            continue
        group = groups.get(module, {}).get(instr)
        if group is None:
            return None
        sums[group] += seconds
    if not any(g in sums for g in STAGES):
        return None
    return {g: sums.get(g, 0.0) for g in STAGES + (OTHER, COMPILER)}


def stage_ms_per_spmv(r, stage: str) -> float | None:
    """A reader's value: device milliseconds per multiply of one stage,
    on the busiest chip."""
    dev, n = r.traced("bench.multiply")
    if dev is None:
        return None
    groups = device_groups(dev)
    return None if groups is None else groups[stage] / n * 1e3


def recent_queries(analytic: str, n: int) -> list | None:
    """The host-step seconds of the program's last `n` queries, when
    they are all `analytic` queries; None where the program keeps none."""
    try:
        from repro.graph import drivers
    except ImportError:
        return None
    recent = getattr(drivers, "recent_queries", lambda: [])()
    if n <= 0 or len(recent) < n:
        return None
    last = recent[-n:]
    if any(a != analytic for a, _ in last):
        return None
    return [host_s for _, host_s in last]


def main(argv=None) -> int:
    import argparse
    import json
    import pathlib
    import shutil
    import sys
    import tempfile
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", help="a trace directory to summarise")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    if args.trace:
        print(json.dumps(summarize(trace.find_xplane(args.trace))))
        return 0

    t_start = time.perf_counter()
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "src"))
    import jax

    from bench import harness, registry
    from repro.compile_cache import enable_compile_cache

    spec = registry.load_spec()
    wl = registry.workload(spec, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("scopes: needs a TPU")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    keep = pathlib.Path(tempfile.mkdtemp(prefix="bench-scopes-"))
    try:
        result = harness.execute(spec, wl, args.seed, args.seconds, True,
                                 devices[:int(wl["chips"])], t_start,
                                 keep_trace=str(keep))
        out = summarize(trace.find_xplane(keep), int(wl["chips"]))
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    print(json.dumps({"result": result, "summary": out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
