"""What every job shares: finding a cell's files by the names in
`BENCHMARK.json`, the device gate, JAX's compile cache at a fixed path in
the checkout, the traced window, per-layer metric readers found by name, and
the one result line.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import tempfile
import time

from . import flops, trace_reduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")     # vocabulary, store
JAX_CACHE = os.path.join(ROOT, ".jax_cache")       # fixed: part of the key
TRACE_SECONDS = 6       # a traced run measures at most this long
WINDOW_SPAN = "bench_window"


class Cell:
    """One entry of `workloads`, with its files: `workloads/<name>.json`
    (job and job settings), `configs/<config>.json`, and
    `traffic/<traffic>.json`."""

    def __init__(self, name: str, root: str = ROOT):
        self.name = name
        bench = os.path.join(root, "benchmarks")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        entry = [w for w in self.manifest["workloads"] if w["name"] == name]
        if not entry:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.entry = entry[0]
        self.chips = int(self.entry["chips"])
        cfg = [c for c in self.manifest["configs"]
               if c["name"] == self.entry["config"]][0]
        with open(os.path.join(root, cfg["file"])) as f:
            self.config = json.load(f)
        with open(os.path.join(bench, "workloads", name + ".json")) as f:
            self.workload = json.load(f)
        with open(os.path.join(bench, "traffic",
                               self.entry["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.job = self.workload["job"]

    def metrics(self, group: str) -> list:
        """The manifest's metrics of `group` that this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


COMPILES = {"hits": 0, "misses": 0, "programs": 0, "seconds": 0.0}
_LISTENING = []


def _on_event(event: str, **_) -> None:
    if event.endswith("compilation_cache/cache_hits"):
        COMPILES["hits"] += 1
    elif event.endswith("compilation_cache/cache_misses"):
        COMPILES["misses"] += 1


def _on_duration(event: str, seconds: float, **_) -> None:
    if event.endswith("backend_compile_duration"):
        COMPILES["programs"] += 1
        COMPILES["seconds"] += seconds


def note_compiles(when: str) -> None:
    """One line on standard error: programs built so far (each is either
    compiled or read from the persistent cache), and the cache's count."""
    c = COMPILES
    print(f"compiles {when}: {c['programs']} programs in {c['seconds']:.1f} s"
          f", persistent cache {c['hits']} hits {c['misses']} misses",
          file=sys.stderr)


def setup_jax() -> None:
    """Persistent compile cache: where JAX_COMPILATION_CACHE_DIR says, else
    at a fixed path in the checkout; every program cached, however small.
    Programs built are counted, so that a run can show that none was built
    inside its window."""
    import jax
    if not _LISTENING:
        _LISTENING.append(True)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: a cap under the sum of a cell's programs (mt5's are about
    # 200 MB) makes every run of that cell compile them all again
    jax.config.update("jax_compilation_cache_max_size", -1)


def require_chips(chips: int) -> None:
    """Exit non-zero unless JAX's default backend is an accelerator whose
    kind has a row of peaks, with exactly the chips the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        print(f"benchmark: no accelerator (platform {devs[0].platform!r}); "
              "nothing was measured", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"benchmark: {len(devs)} chip(s) visible, the cell asks for "
              f"{chips}", file=sys.stderr)
        raise SystemExit(3)
    flops.peaks_for(devs[0].device_kind)     # unknown kind raises


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def note_memory(when: str) -> None:
    """One line on standard error: the first device's bytes in use and peak."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory {when}: bytes_in_use={stats.get('bytes_in_use')} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", file=sys.stderr)


_LAST = [time.perf_counter()]


def note_time(label: str) -> None:
    """One line on standard error: seconds since the previous note."""
    now = time.perf_counter()
    print(f"time {label}: {now - _LAST[0]:.2f} s", file=sys.stderr)
    _LAST[0] = now


def scratch_dir(prefix: str):
    """A per-run directory under TMPDIR, removed on the way out."""
    return tempfile.TemporaryDirectory(prefix=prefix,
                                       ignore_cleanup_errors=True)


def window_seconds(seconds: float, trace: bool) -> float:
    return min(seconds, TRACE_SECONDS) if trace else seconds


class Window:
    """The measured window. With tracing on it runs under the profiler and
    is marked by a host span, so that the trace can be cut to it."""

    def __init__(self, seconds: float, trace: bool, scratch: str):
        self.trace = trace
        self.seconds = window_seconds(seconds, trace)
        self._dir = os.path.join(scratch, "trace")
        self.reduced = None
        self.t0 = self.t1 = None

    def __enter__(self):
        import jax
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
        self._programs0 = COMPILES["programs"]
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + self.seconds
        return self

    def close(self) -> float:
        """Call after the last barrier; returns the window's length."""
        self.t1 = time.perf_counter()
        self.programs_built = COMPILES["programs"] - self._programs0
        return self.t1 - self.t0

    def __exit__(self, *exc):
        import jax
        if self.t1 is None:
            self.close()
        if self.trace:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            if exc[0] is None:
                planes = trace_reduce.load(trace_reduce.find_xplane(self._dir))
                self.reduced = trace_reduce.reduce(
                    planes, window_ns=_span_window(planes))
                for name, m in sorted(self.reduced["modules"].items(),
                                      key=lambda kv: -kv[1]["seconds"])[:8]:
                    print(f"trace module {name}: {m['seconds']:.6f} s in "
                          f"{m['launches']:.0f} launches", file=sys.stderr)
        return False


def _span_window(planes: dict):
    for name, lines in planes.items():
        if name.startswith("/host:"):
            for evs in lines.values():
                for ev, start, dur in evs:
                    if ev == WINDOW_SPAN:
                        return (start, start + dur)
    return None


def read_metric(name: str, ctx: dict):
    """Load `metrics/<name>.py` and call its `read(ctx)`; None = nothing to
    read, and the metric is left out of the line."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list (q in [0, 100])."""
    vals = sorted(values)
    rank = max(1, int(-(-q * len(vals) // 100)))
    return vals[min(rank, len(vals)) - 1]


def result_line(cell: Cell, trace: bool, out: dict) -> dict:
    """The last line of standard output, from a job's `out`:
    {"correct", "attempted", "failed", "end_to_end": {name: value},
     "ctx": {...for the per-layer readers}, "compared": {name: {"value",
     "limit"}}, "device": {...}, "reduced": trace numbers or None}."""
    metrics = {}
    device = dict(out["device"])
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"]}
    if trace:
        red = out["reduced"]
        for m in cell.metrics("per_layer"):
            val = read_metric(m["name"], out["ctx"])
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    else:
        for m in cell.metrics("end_to_end"):
            if m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["workload"] = cell.name
    line["compared"] = out["compared"]          # comes last
    return line


def print_compared(compared: dict) -> None:
    """Each number compared beside its limit, as the last lines of stderr."""
    for name, c in compared.items():
        verdict = "ok" if c["ok"] else "OVER"
        print(f"compared {name}: {c['value']:.6g} limit {c['limit']:.6g} "
              f"{verdict}", file=sys.stderr)
    sys.stderr.flush()
