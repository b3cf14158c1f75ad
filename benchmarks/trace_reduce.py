"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle
time, device time per XLA module, the longest device operations, and the
idle gaps laid to what the host was doing. Reads the file with
`jax.profiler.ProfileData` alone, which loads no accelerator library.

A device plane is one whose name starts with `/device:`. On it the line
`XLA Ops` holds one event per operation run (the busy time is the union of
their intervals) and the line `XLA Modules` one event per program launched
(named `jit_<function>(<fingerprint>)`). Host planes hold one line per
thread.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

_FINGERPRINT = re.compile(r"\(\d+\)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, float(ev.start_ns),
                               float(ev.duration_ns)))
    return out


def union(intervals) -> list:
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def module_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def short_op(event_name: str) -> str:
    """`fusion.50 f32[250112,768]` from the whole HLO instruction that this
    runtime gives as an operation's name."""
    left, _, right = event_name.partition(" = ")
    shape = right.split("{")[0].lstrip("(") if right else ""
    return f"{left.lstrip('%')} {shape}".strip()[:80]


def find_module(reduced: dict, name: str):
    """The entry of `reduced["modules"]` whose name, without its
    fingerprint, is `name`. Several programs can share such a name (every
    jitted lambda is `jit__lambda`): then the one launched most often."""
    hits = [m for full, m in reduced["modules"].items()
            if module_name(full) == name]
    return max(hits, key=lambda m: m["launches"]) if hits else None


def _device_planes(planes: dict) -> list:
    return sorted(n for n in planes if n.startswith("/device:")
                  and (OPS_LINE in planes[n] or MODULES_LINE in planes[n]))


def _host_events(planes: dict) -> list:
    out = []
    for name, lines in planes.items():
        if name.startswith("/host:"):
            for evs in lines.values():
                out.extend(e for e in evs if e[2] > 0)
    return out


def reduce(planes: dict, window_ns: tuple | None = None, top: int = 10,
           min_gap_ns: float = 20_000.0) -> dict:
    """Numbers of one trace. `window_ns` = (start, end) on the trace's clock
    restricts everything to that window; None = from the first device event
    to the last.

    Returns {"devices", "window_s", "busy_s" (mean over devices),
    "idle_share", "modules": {name with fingerprint: {"seconds",
    "launches"}} (mean over devices; see `find_module`), "device_ops": [[name, s]], "idle_gaps": [[host activity, s]]}.
    """
    devs = _device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device plane with XLA events")
    ops = {d: planes[d].get(OPS_LINE) or [
        e for ln, evs in planes[d].items() if ln != MODULES_LINE
        for e in evs] for d in devs}
    if window_ns is None:
        every = [e for d in devs for e in ops[d]]
        window_ns = (min(e[1] for e in every),
                     max(e[1] + e[2] for e in every))
    w0, w1 = window_ns

    def clip(evs):
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                yield name, a, b

    busy, op_time, modules, gaps = [], {}, {}, []
    for d in devs:
        ivals = []
        for name, a, b in clip(ops[d]):
            ivals.append((a, b))
            op = short_op(name)
            op_time[op] = op_time.get(op, 0.0) + (b - a)
        merged = union(ivals)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] - edges[i] >= min_gap_ns]
        for name, a, b in clip(planes[d].get(MODULES_LINE, [])):
            m = modules.setdefault(name, {"seconds": 0.0, "launches": 0})
            m["seconds"] += (b - a) / 1e9
            m["launches"] += 1
    n = len(devs)
    for m in modules.values():
        m["seconds"] /= n
        m["launches"] /= n
    # lay each gap to the innermost host event that covers half of it or
    # more (the shortest such event); failing that, to the one that covers
    # most of it
    host = sorted(_host_events(planes), key=lambda e: e[1])
    starts = [e[1] for e in host]
    long_events = [e for e in host if e[2] >= 10e6]
    laid: dict = {}
    for g0, g1 in gaps:
        hi = bisect.bisect_right(starts, g1)
        near = host[max(0, hi - 400):hi]
        inner, inner_dur = None, float("inf")
        most, most_cover = "(no host event)", 0.0
        for name, s, dur in near + long_events:
            cover = min(s + dur, g1) - max(s, g0)
            if cover <= 0:
                continue
            if cover >= 0.5 * (g1 - g0) and dur < inner_dur:
                inner, inner_dur = name, dur
            if cover > most_cover:
                most, most_cover = name, cover
        name = inner if inner is not None else most
        laid[name] = laid.get(name, 0.0) + (g1 - g0)
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / n / 1e9
    rank = lambda d: [[k, v / 1e9 / n] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"devices": n, "window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "modules": modules, "device_ops": rank(op_time),
            "idle_gaps": rank(laid)}


def reduce_dir(trace_dir: str, **kw) -> dict:
    return reduce(load(find_xplane(trace_dir)), **kw)
