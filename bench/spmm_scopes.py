"""The SpMM runner's stage scopes (`repro.spans.SPMM_SCOPES`), read from
the executables of the run that made a trace.

The runner (`kernels._layout.spmm_prepared`) names its stages apart from
the one-vector runners', so `scopes.group_of` puts its ops in
`spmv.other`; this module groups them by the SpMM stages instead.  The
runner loops over groups of chunks, so its ops run inside a `while`
body: the loop's own instruction, which only runs other ops, is left
out of every sum.  Only programs that hold an op in an SpMM stage count;
where the process holds none (a program without the SpMM path, or no
TPU), the readers read nothing.
"""
from __future__ import annotations

import re

from bench import scopes, trace

STAGES = ("spmm.x_prep", "spmm.x_gather", "spmm.kernel", "spmm.merge")
CONTROL = ("while", "conditional", "call")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def stage_of(op_name: str | None) -> str | None:
    """The SpMM stage an op's op_name lies in, or None (in none, or in
    more than one)."""
    found = {p for p in re.split(r"[/;:]", op_name or "") if p in STAGES}
    return found.pop() if len(found) == 1 else None


def hlo_ops(text: str) -> dict:
    """instruction -> (stage, is Mosaic) for each instruction of an HLO
    module that runs code of its own (control flow left out)."""
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(2) in CONTROL:
            continue
        op = scopes._OP_NAME.search(line)
        out[m.group(1)] = (stage_of(op.group(1) if op else None),
                           trace.MOSAIC in line)
    return out


def live_ops() -> dict:
    """program name -> `hlo_ops` for the executables this process holds
    on its TPU that hold an op in an SpMM stage; empty off a TPU."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    out = {}
    for exe in jax.devices()[0].client.live_executables():
        try:
            modules = exe.hlo_modules()
        except Exception:         # an executable that keeps no HLO
            continue
        for module in modules:
            ops = hlo_ops(module.to_string())
            if any(stage for stage, _ in ops.values()):
                out.setdefault(module.name, {}).update(ops)
    return out


def ms_per_call(r, keep) -> float | None:
    """Device milliseconds per multiply, on the busiest chip, of the SpMM
    programs' ops for which keep(stage, is_mosaic) holds; None without a
    traced window or an SpMM program."""
    dev, n = r.traced("bench.multiply")
    if dev is None:
        return None
    live = live_ops()
    if not live:
        return None
    total = 0.0
    for key, seconds in dev.ops.items():
        module, instr = key.split("/", 1)
        found = live.get(module, {}).get(instr)
        if found is not None and keep(*found):
            total += seconds
    return total / n * 1e3
