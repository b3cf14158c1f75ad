"""Device-op time of a traced window grouped by the program's name scopes.

The trace's `XLA Ops` events are named by the whole HLO instruction
(`%fusion.50 = f32[...] fusion(...)`) and carry no `op_name`; the compiled
program's text does: every instruction line ends in
`metadata={op_name="jit(train_step)/.../block1/moe/moe.experts/..." ...}`.
`op_names` reads that text into {instruction: op_name}, and `scope_seconds`
sums the window's event time under each scope (a `/`-separated component of
the op_name, wrapped or not by `jvp(...)`, `transpose(...)` and the like)
and under each kernel: a Pallas call is the instruction `%<kernel>.<n>`, and
only that one counts (the operations that read its result name it among
their operands). A fusion takes the op_name XLA left on it, so time at a
scope's edge can land on its neighbour.
"""
from __future__ import annotations

import re

from . import trace_reduce

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict:
    """{instruction name: the op_name of its line's metadata, "" if none}."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            found = _OP_NAME.search(line)
            out[m.group(1)] = found.group(1) if found else ""
    return out


def _instruction(event_name: str) -> str:
    return event_name.partition(" = ")[0].strip().lstrip("%")


def in_scope(op_name: str, scope: str) -> bool:
    return re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)",
                     op_name) is not None


def is_kernel(instruction: str, kernel: str) -> bool:
    """`flash_fwd.12` (or a bare `flash_fwd`) is a launch of `flash_fwd`."""
    return re.fullmatch(re.escape(kernel) + r"(\.\d+)?", instruction) \
        is not None


def scope_seconds(planes: dict, window_ns, names: dict, scopes: list,
                  kernels: list) -> dict:
    """{"scopes": {scope: seconds}, "kernels": {kernel: seconds},
    "matched": share of device-op seconds whose instruction was found in
    `names`}; seconds are means over the device planes, inside the window
    (None = the whole trace)."""
    devs = trace_reduce._device_planes(planes)
    if not devs:
        return {}
    by_scope = {s: 0.0 for s in scopes}
    by_kernel = {k: 0.0 for k in kernels}
    total = found = 0.0
    for d in devs:
        for name, start, dur in planes[d].get(trace_reduce.OPS_LINE, []):
            a, b = start, start + dur
            if window_ns is not None:
                a, b = max(a, window_ns[0]), min(b, window_ns[1])
            if b <= a:
                continue
            sec = (b - a) / 1e9
            total += sec
            instr = _instruction(name)
            for k in kernels:
                if is_kernel(instr, k):
                    by_kernel[k] += sec
            op = names.get(instr)
            if op is None:
                continue
            found += sec
            for s in scopes:
                if in_scope(op, s):
                    by_scope[s] += sec
    n = len(devs)
    return {"scopes": {s: v / n for s, v in by_scope.items()},
            "kernels": {k: v / n for k, v in by_kernel.items()},
            "matched": found / total if total else 0.0}


def step_share(ctx: dict, scope: str):
    """Device time under `scope` as a share (%) of the train step's module
    time, from a job's `ctx`; None where either is missing."""
    seconds = ((ctx.get("scope_seconds") or {}).get("scopes") or {}).get(scope)
    red = ctx.get("reduced")
    name = ctx.get("trace_modules", {}).get("step")
    if ctx.get("job") != "train" or not red or not name or not seconds:
        return None
    mod = trace_reduce.find_module(red, name)
    if not mod or not mod["seconds"]:
        return None
    return 100.0 * seconds / mod["seconds"]
