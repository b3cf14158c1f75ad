"""Tracing/profiling hooks (SURVEY.md §5.1): jax.profiler traces around the
train/embed hot loops, TensorBoard-readable, behind a --profile CLI flag —
plus PipelineProfiler, the per-STAGE wall-time accounting the traces can't
give cheaply: where an end-to-end pages/sec number hides which stage binds
(host production vs H2D vs compute vs D2H vs store writeback), the stage
breakdown says it in one metrics line.
"""
from __future__ import annotations

import contextlib
import gc
import os
import threading
import time
import weakref
from typing import Dict, Optional

import jax
from jax.profiler import TraceAnnotation


class PipelineProfiler:
    """Cumulative per-stage wall time for the host<->device pipelines.

    Stages are free-form names; the bulk-embed and train loops use:
      produce_wait  consumer blocked waiting for a host batch (prefetch gap)
      read          corpus record reads inside tokenizer workers
      tokenize      encode_batch inside tokenizer workers
      h2d           device_put / make_array_from_process_local_data
      compute       jitted dispatch (async under JAX — small when pipelined)
      d2h           materializing device results to numpy
      write         shard concat + write_shard on the writer thread
      write_wait    device loop blocked on the bounded writeback budget

    The serving path (infer/serve.py) uses:
      batcher_idle  dispatcher thread blocked on an empty request queue
      batch_window  dispatcher thread waiting out the coalescing window
      dispatch      dispatcher thread answering one coalesced batch: the
                    parent of tokenize/encode/topk/merge/format there, so
                    dispatch minus those is host time no stage names
      queue_wait    request sat in the micro-batcher queue before dispatch
      tokenize      encode_batch over the coalesced cache-miss queries
      encode        compiled query-tower dispatch (+ host materialize)
      topk          per-shard sharded_topk dispatches (or the streaming
                    sweep on a non-resident store)
      encode_launch inside encode: the put of the ids and the launch of
                    the query tower, host only
      encode_wait   inside encode: the pull of the vectors, i.e. the host
                    blocked on the tower, plus their copy
      merge         the one packed transfer of the carried top-k
      format        page-id mapping + snippet assembly
      gc            Python's cyclic collector, one pass a call, on
                    whichever thread collected (watch_gc); gc_gen2 the
                    second-generation passes among them

    Seconds are CUMULATIVE ACROSS THREADS — a pool of N tokenizer workers
    adds each worker's time, so `read`/`tokenize` can exceed wall clock.
    That is the point: the ratios between stages (and the consumer-side
    `produce_wait`) say which stage binds, not how long the job took.
    Thread-safe: producers, tokenizer workers, and the writer thread all
    add into one instance.

    Every `stage()` is ALSO an event in the jax profiler's trace
    (docs/OBSERVABILITY.md "The combined trace"): a `TraceAnnotation` over
    the interval the stage times, on the thread that runs it, so a
    `--profile` run shows the program's stages on the device ops' clock.
    The event is named `prefix + stage`; the owner that builds the profiler
    picks the prefix (`serve.`, `train.`, `embed.`; none = the bare stage
    name) and the dictionary keys never carry it. Names keep to letters,
    digits, `_` and `.`. With no profiler session the annotation is inert.
    `add()` alone (a duration measured elsewhere, e.g. the per-request
    `queue_wait`) records no event.
    """

    def __init__(self, prefix: str = "") -> None:
        self._prefix = prefix
        self._lock = threading.Lock()
        self._sec: Dict[str, float] = {}
        self._n: Dict[str, int] = {}
        self._bytes: Dict[str, int] = {}
        self._gc: Optional[_GcWatch] = None
        self._gc_finalizer: Optional[weakref.finalize] = None

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._sec[name] = self._sec.get(name, 0.0) + seconds
            self._n[name] = self._n.get(name, 0) + 1

    def add_bytes(self, name: str, nbytes: int) -> None:
        """Byte volume moved by a stage (h2d/d2h transfers): with the
        stage's cumulative seconds this makes the achieved MB/s of a
        transfer stage computable from one metrics line —
        `embed_d2h_mbytes_per_sec` in the bulk-embed log."""
        with self._lock:
            self._bytes[name] = self._bytes.get(name, 0) + int(nbytes)

    def stage_bytes(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._bytes)

    @contextlib.contextmanager
    def stage(self, name: str):
        with TraceAnnotation(self._prefix + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    # -- Python's collector ------------------------------------------------
    def watch_gc(self) -> None:
        """Time every pass of Python's cyclic collector as stage `gc` (and
        `gc_gen2` for the second generation) on whichever thread collects;
        a second-generation pass is also a `<prefix>gc` event in the trace
        (the younger ones, hundreds a second under the tracer's own
        allocations, are left out of it). A pass holds the GIL, so it
        stops every other thread's Python too. Idempotent; `unwatch_gc()`
        removes the hook, and a profiler that is freed removes it by
        itself. The hook holds no reference to the profiler
        and takes no lock (a pass can start inside `add()`, under it)."""
        if self._gc is None:
            self._gc = _GcWatch(self._prefix + "gc")
            gc.callbacks.append(self._gc)
            self._gc_finalizer = weakref.finalize(
                self, gc.callbacks.remove, self._gc)

    def unwatch_gc(self) -> None:
        if self._gc is not None:
            self._gc_finalizer()
            self._gc = None

    def gc_seconds(self) -> float:
        """Collector seconds since watch_gc(), never reset: read around an
        interval, the difference is what of it the collector took."""
        w = self._gc
        return w.total_s if w is not None else 0.0

    def reset(self) -> None:
        with self._lock:
            self._sec.clear()
            self._n.clear()
            self._bytes.clear()
            if self._gc is not None:
                self._gc.reset()

    def _gc_sums(self):
        """[(key, seconds, passes)] of the collector, or none unwatched:
        plain reads of what only the hook writes."""
        w = self._gc
        if w is None:
            return []
        return [("gc", w.sec, w.n), ("gc_gen2", w.sec2, w.n2)]

    def stages(self) -> Dict[str, float]:
        """{stage: cumulative seconds} snapshot."""
        with self._lock:
            out = dict(self._sec)
        out.update((k, s) for k, s, _ in self._gc_sums())
        return out

    def counts(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._n)
        out.update((k, n) for k, _, n in self._gc_sums())
        return out

    def summary(self, prefix: str = "stage_") -> Dict[str, float]:
        """Flat metrics-ready dict: {f'{prefix}{stage}_s': seconds,
        f'{prefix}{stage}_n': calls}. Stable key shape so dashboards/tests
        can pin on e.g. stage_produce_wait_s — and the per-stage call count
        next to the cumulative seconds makes mean-per-call computable from
        ONE metrics line."""
        sec, n = self.stages(), self.counts()
        with self._lock:
            nbytes = dict(self._bytes)
        out: Dict[str, float] = {}
        for k in sorted(sec):
            out[f"{prefix}{k}_s"] = round(sec[k], 4)
            out[f"{prefix}{k}_n"] = n.get(k, 0)
            if k in nbytes:
                out[f"{prefix}{k}_bytes"] = nbytes[k]
        return out


class _GcWatch:
    """The `gc.callbacks` hook of one PipelineProfiler. Only this hook
    writes its sums and it takes no lock: CPython runs one collection at a
    time, and a lock here could be the one the collecting thread already
    holds. Readers fold the plain attributes in (a read may see a pass's
    seconds a moment before its count)."""

    __slots__ = ("_name", "_ann", "_t0", "sec", "n", "sec2", "n2",
                 "total_s")

    def __init__(self, name: str) -> None:
        self._name = name
        self._ann = None
        self._t0 = None
        self.total_s = 0.0
        self.reset()

    def reset(self) -> None:
        self.sec = self.sec2 = 0.0
        self.n = self.n2 = 0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if info.get("generation") == 2:
                self._ann = TraceAnnotation(self._name)
                self._ann.__enter__()
            self._t0 = time.perf_counter()
            return
        if self._t0 is None:
            # hooked during a pass: its finalizers run Python, and so may
            # another thread's watch_gc()
            return
        dt = time.perf_counter() - self._t0
        self._t0 = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.sec += dt
        self.n += 1
        self.total_s += dt
        if info.get("generation") == 2:
            self.sec2 += dt
            self.n2 += 1


class LatencyStats:
    """Per-request latency samples -> distribution numbers (count, mean,
    p50/p99). PipelineProfiler answers "which stage binds" with cumulative
    seconds; this answers the serving question it can't — what one caller
    experiences under load, where the tail (p99) matters more than the
    mean. Thread-safe: concurrent search() callers add into one instance.

    Memory is BOUNDED: samples land in a seeded reservoir
    (utils/telemetry.Reservoir, Algorithm R) of `cap` slots instead of an
    ever-growing list, so a long-lived service neither leaks nor re-sorts
    an unbounded buffer per percentile call. Below `cap` samples the
    reservoir holds every observation, so count/mean AND the nearest-rank
    percentiles are exactly what the unbounded version reported (pinned by
    tests/test_profiling.py); past `cap`, count and mean stay exact and
    percentiles are estimated from a uniform sample.
    """

    def __init__(self, cap: int = 4096, seed: int = 0) -> None:
        from dnn_page_vectors_tpu.utils.telemetry import Reservoir
        self._res = Reservoir(cap=cap, seed=seed)

    def add(self, seconds: float) -> None:
        self._res.add(float(seconds))

    @contextlib.contextmanager
    def timed(self):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(time.perf_counter() - t0)

    def __len__(self) -> int:
        return self._res.count

    def percentile_ms(self, q: float) -> float:
        """Nearest-rank percentile (q in [0, 100]) in milliseconds; 0.0
        with no samples. p50 of an even count is the lower middle sample —
        a latency the service actually delivered, not an interpolation."""
        return self._res.percentile(q) * 1000.0

    def summary(self, prefix: str = "lat_") -> Dict[str, float]:
        return {f"{prefix}count": self._res.count,
                f"{prefix}mean_ms": round(self._res.mean * 1000.0, 3),
                f"{prefix}p50_ms": round(self.percentile_ms(50), 3),
                f"{prefix}p99_ms": round(self.percentile_ms(99), 3)}


@contextlib.contextmanager
def maybe_profile(enabled: bool, workdir: str):
    if not enabled:
        yield
        return
    trace_dir = os.path.join(workdir, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        yield
