"""Query-time retrieval service (the serving half of call stack §4.3).

`cli.py search` originally rebuilt the corpus, tokenizer, and model per
invocation — fine as a demo, not a serving path (VERDICT r3 Weak #6).
SearchService is the serving path: everything is loaded ONCE (params on
device, store shards optionally pre-staged in HBM), so per-query cost is
one tokenize + one compiled encode + MXU top-k over resident vectors.

Throughput layer (docs/SERVING.md): the compiled encode/top-k programs are
BATCH-shaped (`query_batch` rows), so one-query-at-a-time serving wastes
most of every dispatch on padding. Three mechanisms recover that width:

  * `search_many(queries, k)` — vectorized multi-query search: one
    encode_batch over up to `query_batch` real queries, one scan per
    resident shard that folds the shard into a running top-k, one packed
    transfer, results split per query (the running top-k is ONE packed
    int32 [B, 2k] array, scores' bits then row ids, ops/topk.py:pack_topk,
    donated to each launch); larger lists tile over full buckets (one
    compiled shape throughout).
  * a dynamic micro-batcher (`serve.batch_window_ms` / `serve.max_batch`,
    start_batcher()): concurrent search() callers enqueue onto a bounded
    queue, a dispatcher thread coalesces whatever arrived within the window
    into one search_many dispatch, and per-request futures carry results
    (or exactly the failing request's exception) back to the callers. A
    lone caller pays at most one window of extra latency; under load the
    bucket fills and aggregate QPS scales toward bucket width.
  * an LRU query-embedding cache (`serve.query_cache_size`, keyed on
    whitespace-normalized query text + the store's model step): repeat
    queries skip tokenize+encode entirely; a store re-stamp
    (ensure_model_step / model reload) changes the key and invalidates
    every entry. Hit/miss counters surface through metrics().

ANN routing (docs/ANN.md): with `serve.index = "ivf"` queries route
through the inverted-file index (index/ivf.py) — centroid scan +
top-`serve.nprobe` posting-list gather + exact on-device re-rank, cost
~nprobe/nlist of the exact sweep — with automatic PER-REQUEST fallback to
the exact path when the index is missing, stale against the store's model
step, or quarantined. `ann_lists_scanned` / `ann_candidates_reranked` /
`ann_fallbacks` and the active index config surface through metrics().
The default `serve.index = "exact"` keeps the pre-index paths below
byte-identical. On a PQ index (built with `cli index --pq`, docs/ANN.md)
the candidate gather moves m-byte codes with on-device ADC scoring and
an exact re-rank, and `serve.hot_postings_gb` stages the hot posting
set's codes to device at view build time — resident lists answer with
zero per-request host gather (`ann_gather_bytes` measures what moves).

Partitioned + replicated serving (docs/SCALING.md "Partitioned serving"):
`serve.partitions` > 1 splits the shard table into P contiguous
partitions — each owning its shard range, its slice of the IVF posting
lists, and its cut of the hot-posting HBM budget — host-simulated as
per-partition worker threads each owning an independent `_ServeView`
(infer/partition.py). search_many becomes a scatter-gather: the coalesced
bucket's query matrix broadcasts once, every partition answers its local
top-k over only its rows (per-query scan bytes drop ~1/P, partitions run
concurrently), and results fold through the ops/topk.py partition merge
tree (`merge_topk_host` as the final host fold). `serve.replicas` adds R
copies of each partition with health-based routing: a replica mid-restage,
degraded to the streaming path, or past `serve.replica_shed_queue` sheds
to its siblings (`replica_shed` event); a partition whose replicas are
ALL degraded serves degraded locally (`partition_degraded`) — never an
empty result slice. refresh() restages partition by partition (one
partition's restage — or maintenance swapping in compaction/rebuild
results — never blocks the others) and publishes the finished view table
with one atomic reference assignment, so a scatter never mixes store
generations across partitions. P = R = 1 (the default) keeps the
single-view paths below byte-identical.

HBM pre-staging: when the store fits the configured budget, every shard is
device_put once (row-sharded over the mesh 'data' axis, padded to one
static shape so a single compiled top-k program serves all shards) and
page vectors never touch disk. Oversized stores transparently fall back to
the streaming path (ops/topk.py:topk_over_store) — same results, per-query
disk reads double-buffered behind a reader thread.

Live updates (docs/UPDATES.md): everything a corpus update can change —
the store handle with its generation chain and tombstones, the staged HBM
shards, the id table, the IVF index — lives in ONE immutable view object
(`_ServeView`). `refresh()` builds the next view off to the side (restaging
only the appended shards, updating the index incrementally) and publishes
it with a single reference assignment: in-flight search_many buckets
finish on the view they captured, the next bucket sees the new corpus —
zero downtime, no dropped futures, never a mixed result set. metrics()
reports `store_generation` / `index_generation` / `docs_appended` /
`tombstoned` / `incremental_updates` / `full_rebuilds`. Restaging is
tombstone-aware (`updates.restage_tombstone_density`): a staged shard
whose only drift is a few new tombstones is reused with the dead rows
masked in its id table, and restages compacted once the staged block's
dead density crosses the threshold (`restage_skipped`/`restage_forced`).

Observability (docs/OBSERVABILITY.md): every search/search_many call runs
under a request-scoped trace (utils/tracing.py) — a span tree covering
queue_wait (through the micro-batcher's thread hop, handed off explicitly)
-> tokenize/encode (cache hits annotated) -> topk (ANN lists_scanned /
gather_bytes / rows_reranked as span attributes) -> merge -> format; a
request slower than `obs.slow_ms` lands, tree and all, in the bounded
slow-query log, and `cli trace` exports the recent ring as Chrome/Perfetto
trace_event JSON. Serving counters live in a per-service MetricsRegistry
(utils/telemetry.py): windowed qps/error-rate/cache-hit/p99 over the last
`obs.window_s` seconds next to the since-boot totals, lifecycle events
(view hot-swap, shard quarantine, drift rebuild, degraded/restored) with
trace-id correlation, and a Prometheus-text + JSON snapshot exposition
(`cli serve-metrics`, the `:metrics` control line).

Degradation (docs/ROBUSTNESS.md): a shard that FAILS to stage — an I/O
fault during the device_put, a checksum mismatch, or the HBM budget
overrunning mid-stage — does not kill the service. Checksum failures are
quarantined (the store drops them); every other failure falls back
PER-SHARD to the streaming top-k path: staged shards answer from HBM, the
failed ones are re-read from disk and merged on host — ONCE PER COALESCED
BATCH, not once per query, so degraded-mode disk traffic amortizes over
the batch exactly like the device dispatches do. The service marks itself
`degraded`, bumps fault counters, and reports both through the metrics
log, so a half-staged service is visible, not silent.
"""
from __future__ import annotations

import contextlib
import queue as queue_mod
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Deque, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
from dnn_page_vectors_tpu.infer.transport import DeadlineExceeded
from dnn_page_vectors_tpu.infer.vector_store import VectorStore, read_ahead
from dnn_page_vectors_tpu.ops.topk import (
    empty_topk, merge_shard_topk, scans_in_kernel, sharded_topk_fn,
    stage_shard, topk_over_store, unpack_topk)
from dnn_page_vectors_tpu.utils import faults
from dnn_page_vectors_tpu.utils.profiling import LatencyStats, PipelineProfiler
from dnn_page_vectors_tpu.utils.telemetry import MetricsRegistry
from dnn_page_vectors_tpu.utils.tracing import Tracer


class _MicroBatcher:
    """Dynamic request coalescing for SearchService.search().

    Callers enqueue (query, k, Future) onto a bounded queue; ONE dispatcher
    thread pulls the first pending request, waits up to `window_ms` for
    more (never past `max_batch`), and answers the whole batch with one
    search_many call per distinct k. The bounded queue backpressures
    callers when the dispatcher falls behind instead of buffering
    unboundedly — the serving analogue of the bulk-embed writer's pending
    budget.

    Failure isolation: when a coalesced dispatch raises (one poisoned
    query must not fail its batch-mates), the batch is retried one request
    at a time so the exception lands on exactly the failing request's
    future; the rest still get results.

    The coalescing window is read from the service PER BATCH (`window_s`
    callable): with `serve.batch_window_adaptive` the AdaptiveWindow
    controller moves it between the configured base and
    `serve.batch_window_max_ms` off the windowed queue-wait p99, and every
    measured queue wait feeds the serve.queue_wait_ms instrument the
    controller reads — the control loop closes through the registry, not
    through ad-hoc state.
    """

    _STOP = object()

    def __init__(self, svc: "SearchService", window_s, max_batch: int,
                 max_queue: int):
        self._svc = svc
        self._window_s = window_s            # () -> seconds, read per batch
        self._max = max(1, int(max_batch))
        self._q: "queue_mod.Queue[object]" = queue_mod.Queue(
            maxsize=max(self._max, int(max_queue)))
        # dispatch telemetry since boot: a count and a sum for the
        # metrics, and the newest sizes alone (bounded) for inspection
        self.batches = 0
        self.batched = 0
        self.batch_sizes: Deque[int] = deque(maxlen=4096)
        self._t = threading.Thread(target=self._run, daemon=True,
                                   name="serve-batcher")
        self._t.start()

    def submit(self, query: str, k: Optional[int],
               nprobe: Optional[int] = None,
               deadline: Optional[float] = None,
               filters: Optional[str] = None) -> Future:
        """Enqueue one request. `deadline` is ABSOLUTE on the service
        clock (svc._clock); admission-time shedding (expired / SLO
        budget) happens in the CALLER (`SearchService._admit`) before
        anything touches this queue — an already-hopeless request must
        never consume queue capacity or a bucket slot. `filters` is the
        CANONICAL predicate text (index/attrs.py) or None: coalescing
        groups per distinct (k, nprobe, filters), so a filtered request
        can never share a dispatch with a differently-filtered one."""
        fut: Future = Future()
        # capture the caller's active span HERE: the dispatcher runs on
        # another thread where the contextvar chain breaks, so the trace
        # context rides the queue explicitly (docs/OBSERVABILITY.md)
        ctx = self._svc.tracer.current()
        self._q.put((query, (k, nprobe, filters), fut, time.perf_counter(),
                     ctx, deadline))
        return fut

    def _run(self) -> None:
        # the dispatcher thread is the serve front's one scarce resource:
        # batcher_idle / batch_window / dispatch (opened in _dispatch) split
        # its time into waiting for work, waiting for company, and working
        stage = self._svc._stage
        while True:
            with stage("batcher_idle"):
                item = self._q.get()
            if item is self._STOP:
                return
            batch = [item]
            stopping = False
            with stage("batch_window"):
                deadline = time.perf_counter() + max(0.0, self._window_s())
                while len(batch) < self._max:
                    rem = deadline - time.perf_counter()
                    try:
                        nxt = (self._q.get_nowait() if rem <= 0
                               else self._q.get(timeout=rem))
                    except queue_mod.Empty:
                        break
                    if nxt is self._STOP:
                        stopping = True
                        break
                    batch.append(nxt)
            self._dispatch(batch)
            if stopping:
                return
            self._svc._adapt_window()

    def _dispatch(self, batch) -> None:
        svc = self._svc
        # THE DOOR (docs/SERVING.md "Network front end"): a request whose
        # deadline expired while it queued is rejected here, BEFORE it
        # can occupy a bucket slot — its caller gets DeadlineExceeded now
        # instead of a result that arrives too late to use, and the
        # requests that can still make their deadlines dispatch in a
        # smaller (= faster) bucket. Shed requests are excluded from the
        # queue-wait instrument: they never dispatched, so their waits
        # must not steer the adaptive-window controller.
        live = []
        for item in batch:
            deadline = item[5]
            if deadline is not None and svc._clock() >= deadline:
                item[2].set_exception(
                    svc._shed_deadline("expired_in_queue", deadline,
                                       trace=item[4]))
            else:
                live.append(item)
        if not live:
            return
        with svc._stage("dispatch"):
            self._dispatch_live(live)

    def _dispatch_live(self, batch) -> None:
        """Answer one batch that passed the door: everything here runs
        under the `dispatch` stage, so what tokenize/encode/topk/merge/
        format leave over (grouping, trace grafting, waking the callers)
        is that stage's self time."""
        svc = self._svc
        tracer = svc.tracer
        now = time.perf_counter()
        for _, _, _, t0, ctx, _ in batch:
            svc.profiler.add("queue_wait", now - t0)
            svc._m_queue_wait.observe((now - t0) * 1000.0)
            if ctx is not None:
                # finished child stamped onto the REQUEST's tree: how long
                # this request sat in the queue before its dispatch
                ctx.child("queue_wait", now - t0, t0=t0)
        # graftcheck: off=locks -- single-writer: only the dispatcher
        # thread writes; readers consume after stop() joins the thread
        self.batches += 1
        # graftcheck: off=locks -- the same single writer
        self.batched += len(batch)
        # graftcheck: off=locks -- the same single writer
        self.batch_sizes.append(len(batch))
        by_key: Dict[tuple, list] = {}
        for query, key, fut, _, ctx, deadline in batch:
            by_key.setdefault(key, []).append((query, fut, ctx, deadline))
        for (k, nprobe, ftext), items in by_key.items():
            # the shared dispatch honors the TIGHTEST deadline of the
            # coalesced group: the RPC fan-out budgets per-partition
            # waits against it
            deadlines = [d for _, _, _, d in items if d is not None]
            group_dl = min(deadlines) if deadlines else None
            try:
                # the coalesced dispatch traces ONCE under a detached root
                # (record=False: it only exists grafted into request
                # trees), then every request adopts the finished subtree —
                # one measurement, N complete span trees
                with tracer.trace("dispatch", record=False,
                                  batch_size=len(items)) as dsp:
                    # for the slow-query log alone: what of this dispatch
                    # the collector took, on any thread
                    slow_log = tracer.slow_ms is not None
                    gc0 = svc.profiler.gc_seconds() if slow_log else 0.0
                    res = svc.search_many(
                        [q for q, _, _, _ in items], k=k, nprobe=nprobe,
                        filters=ftext, _record=False, deadline=group_dl)
                    if slow_log:
                        dsp.set_attrs(gc_ms=round(
                            (svc.profiler.gc_seconds() - gc0) * 1e3, 3))
            except BaseException:  # noqa: BLE001 — isolate per request
                for q, fut, ctx, deadline in items:
                    try:
                        # per-request retry: re-activate the caller's span
                        # on THIS thread so retry spans nest under it
                        with tracer.use(ctx):
                            fut.set_result(svc.search_many(
                                [q], k=k, nprobe=nprobe, filters=ftext,
                                _record=False, deadline=deadline)[0])
                    except BaseException as e:  # noqa: BLE001
                        fut.set_exception(e)
                continue
            for (_, fut, ctx, _), r in zip(items, res):
                if ctx is not None:
                    ctx.adopt(dsp)
                fut.set_result(r)

    def close(self) -> None:
        self._q.put(self._STOP)
        self._t.join()


class AdaptiveWindow:
    """Telemetry-driven micro-batch window controller (docs/SERVING.md).

    The fixed `serve.batch_window_ms` is a compromise: too narrow and a
    loaded service dispatches half-empty buckets, too wide and a lone
    caller pays the whole window as latency. This controller moves the
    window between `base_ms` and `max_ms` off ONE signal, the windowed
    queue-wait p99 from the serve.queue_wait_ms histogram (the PR-7
    registry, not wall-clock re-derivation):

      * pressure — queue-wait p99 >= `pressure_ratio` x the current
        window (requests are stacking behind in-flight dispatches, not
        just riding out the window) -> double the window, capped at
        `max_ms`. Wider window = fuller buckets = fewer dispatches per
        second = the queue drains.
      * idle — no queue-wait samples in the rolling window, or a p99
        below `idle_ratio` x the current window -> halve back toward
        `base_ms`, so the next lone caller pays base latency again.

    Note the discriminator: a lone caller's queue wait ~= the window
    itself (it sits in the batch while the dispatcher waits out the
    window), which lands BETWEEN the idle and pressure thresholds — a
    quiet trickle of traffic holds the window steady instead of
    oscillating. Every change sets the serve.batch_window_ms gauge and
    emits a `window_adapt` event with the p99 that drove it."""

    def __init__(self, base_ms: float, max_ms: float, queue_wait,
                 gauge=None, on_change=None, pressure_ratio: float = 1.5,
                 idle_ratio: float = 0.25, min_samples: int = 4):
        self.base_ms = max(0.1, float(base_ms))
        self.max_ms = max(self.base_ms, float(max_ms))
        self._queue_wait = queue_wait        # Histogram (windowed)
        self._gauge = gauge
        self._on_change = on_change
        self.pressure_ratio = float(pressure_ratio)
        self.idle_ratio = float(idle_ratio)
        self.min_samples = max(1, int(min_samples))
        self._cur = self.base_ms
        self._lock = threading.Lock()
        if gauge is not None:
            gauge.set(self._cur)

    @property
    def current_ms(self) -> float:
        with self._lock:
            return self._cur

    def current_s(self) -> float:
        return self.current_ms / 1000.0

    def update(self) -> float:
        """One control step: read the windowed queue-wait stats, move the
        window if warranted, return the (possibly new) window in ms."""
        n = self._queue_wait.window_count()
        p99 = self._queue_wait.window_percentile(99)
        with self._lock:
            cur = self._cur
            new, reason = cur, None
            if n >= self.min_samples and p99 >= self.pressure_ratio * cur:
                new, reason = min(self.max_ms, cur * 2.0), "pressure"
            elif cur > self.base_ms and (
                    n == 0 or p99 <= self.idle_ratio * cur):
                new, reason = max(self.base_ms, cur / 2.0), "idle"
            if new == cur:
                return cur
            self._cur = new
        if self._gauge is not None:
            self._gauge.set(new)
        if self._on_change is not None:
            self._on_change(cur, new, p99, reason)
        return new


def _compile_filters(spec):
    """Normalize a filters argument (None / predicate text / compiled
    Predicate) to a Predicate-or-None. Lazy import: `index/__init__`
    pulls the whole ANN stack, which serve only loads when routing
    through it (same reason `_index()` imports ivf in-function)."""
    if spec is None or spec == "":
        return None
    from dnn_page_vectors_tpu.index import attrs as attrs_mod
    return attrs_mod.compile_filters(spec)


def _merge_topk_host(s1, i1, s2, i2, k: int):
    """Fold two [n, k] (scores fp32, page_ids int64) candidate sets into
    one top-k on host — the cross-stamp merge for the streaming dual-stamp
    path (docs/MAINTENANCE.md "Rolling model migration"); the resident
    path folds all stamps on device through the one carried scan.
    Stable on ties (first set wins), -inf/-1 padding sorts last."""
    s = np.concatenate([s1, s2], axis=1)
    i = np.concatenate([i1, i2], axis=1)
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(s, order, axis=1),
            np.take_along_axis(i, order, axis=1))


class _Shard(NamedTuple):
    """One store shard resident on the device, with everything a launch of
    the scan over it takes (_stage_view makes it, _dispatch_bucket reads
    it): a refresh hands a shard whose bytes are unchanged on whole (a new
    `span` if its slot moved), or with newer tombstones masked in `ids`."""
    ids: np.ndarray        # [n] int64 page ids, -1 = tombstoned since staged
    n: int                 # rows of `pages` that hold a vector
    pages: object          # device rows at the stored width, for the scan
    #                        alone: [pad_rows, D] int8 | float32 rows, and a
    #                        float16 store as its pair words, uint32
    #                        [pad_rows, D/2] (ops/topk.py:pair_words)
    scales: object         # [pad_rows] device fp16 scales (int8 store) | None
    span: object           # int32 [2] replicated on the device: `n`, and the
    #                        shard's first combined id, slot * pad_rows


class _ServeView:
    """One atomic serving snapshot (docs/UPDATES.md): everything
    search_many touches that a refresh() can change — the store handle
    (with its frozen generation chain and tombstone map), the staged HBM
    shards, the combined-id table, the
    degraded-tail entries, and the IVF index. The hot-swap is a single
    reference assignment: in-flight dispatches finish on the view they
    captured at entry, the next dispatch sees the new one — no lock on
    the query path, no torn half-view ever observable."""

    __slots__ = ("store", "entries", "generation", "shards", "shard_keys",
                 "shard_steps", "steps", "stream_entries", "pid_table",
                 "pad_rows", "index", "index_error", "index_info",
                 "docs_appended", "tombstoned", "num_vectors", "maint_stats",
                 "restricted")

    def __init__(self, store: VectorStore,
                 entries: Optional[List[Dict]] = None):
        self.store = store
        # frozen table snapshot — the whole store, or (partitioned
        # serving, infer/partition.py) one partition's contiguous shard
        # range; `restricted` routes the streaming sweep through THIS
        # entry subset instead of the live table
        self.entries: List[Dict] = (store.shards() if entries is None
                                    else list(entries))
        self.restricted = entries is not None
        self.generation = store.generation
        self.docs_appended = store.appended_vectors()
        self.tombstoned = store.tombstoned_count()
        self.num_vectors = store.num_vectors
        # the compaction trigger's inputs, frozen with the chain they
        # describe (docs/MAINTENANCE.md): density/dead-rows/reclaimable
        self.maint_stats: Dict = store.maintenance_stats()
        # distinct model stamps over the FULL table, ascending — mid-
        # migration (docs/MAINTENANCE.md "Rolling model migration") this is
        # [from_step, to_step] and queries encode once per stamp; computed
        # store-wide even for a restricted view so every partition splits a
        # stacked query matrix on the same block order
        self.steps: List[int] = store.model_steps()
        self.shards: Optional[List[_Shard]] = None
        self.shard_keys: List[tuple] = []
        self.shard_steps: List[Optional[int]] = []   # stamp per staged shard
        self.stream_entries: List[Dict] = []
        self.pid_table = None
        self.pad_rows = 0
        self.index = None
        self.index_error: Optional[str] = None
        self.index_info: Optional[Dict] = None


class SearchService:
    def __init__(self, cfg, embedder: BulkEmbedder, corpus,
                 store: VectorStore, preload_hbm_gb: float = 4.0,
                 snippet_chars: int = 160, query_batch: Optional[int] = None,
                 log=None, profiler: Optional[PipelineProfiler] = None,
                 registry: Optional[MetricsRegistry] = None,
                 clock=None):
        self.cfg = cfg
        self.embedder = embedder
        self.corpus = corpus
        self.store = store
        # extra query towers keyed by model step (docs/MAINTENANCE.md
        # "Rolling model migration"): begin_migration() attaches the target
        # model's params here so mid-migration queries can encode with BOTH
        # stamps; the refresh() that observes the completed stamp flip
        # adopts the new tower into `embedder` and drops this reference.
        # Whole-dict swap on write, snapshot read on the query path.
        self._towers: Dict[int, object] = {}
        self.snippet_chars = snippet_chars
        self.degraded = False
        self.fault_counters: Dict[str, int] = {}
        # per-stage serving breakdown (queue_wait/tokenize/encode/topk/
        # merge/format) — one shared instance; the batcher and concurrent
        # callers all add into it
        self.profiler = profiler or PipelineProfiler(prefix="serve.")
        # -- telemetry (docs/OBSERVABILITY.md) ----------------------------
        # One registry per service (counters must not mix across services)
        # holding every serving instrument; request-scoped tracing follows
        # the obs.* section. PipelineProfiler stays the cumulative stage
        # accountant; the registry adds what it can't say: live windowed
        # rates (qps/error/cache-hit over obs.window_s), bounded latency
        # percentiles, and the lifecycle event channel.
        obs = getattr(cfg, "obs", None)
        window_s = getattr(obs, "window_s", 10.0) if obs is not None else 10.0
        reservoir = getattr(obs, "reservoir", 4096) if obs is not None \
            else 4096
        self._window_s = window_s
        self.registry = registry or MetricsRegistry(
            events=getattr(obs, "events", 256) if obs is not None else 256)
        self.tracer = Tracer(
            enabled=getattr(obs, "enabled", True) if obs is not None
            else True,
            slow_ms=getattr(obs, "slow_ms", -1.0) if obs is not None
            else -1.0,
            slow_log_size=getattr(obs, "slow_log_size", 64)
            if obs is not None else 64,
            buffer=getattr(obs, "trace_buffer", 64) if obs is not None
            else 64)
        reg = self.registry
        self._m_requests = reg.counter("serve.requests", window_s=window_s)
        self._m_errors = reg.counter("serve.errors", window_s=window_s)
        self._m_latency = reg.histogram("serve.latency_ms",
                                        window_s=window_s, cap=reservoir)
        self._m_cache_hits = reg.counter("serve.cache_hits",
                                         window_s=window_s)
        self._m_cache_misses = reg.counter("serve.cache_misses",
                                           window_s=window_s)
        # what a tower that asks for it has counted per encode call: its
        # tokens, and its routed layers' three where it has any (the embedder
        # reduces them on the device; BulkEmbedder.encode_query_call)
        self._m_encode = {name: reg.counter("encode." + name)
                          for name in BulkEmbedder.ENCODE_COUNTERS}
        # resident buckets answered by the carried scan alone | tail too;
        # those whose resident shards the Pallas kernel scanned
        self._m_carried = reg.counter("topk.carried_buckets")
        self._m_tail = reg.counter("topk.tail_buckets")
        self._m_kernel = reg.counter("topk.kernel_buckets")
        self._m_ann_lists = reg.counter("serve.ann_lists_scanned")
        self._m_ann_reranked = reg.counter("serve.ann_candidates_reranked")
        self._m_ann_fallbacks = reg.counter("serve.ann_fallbacks")
        self._m_ann_gather = reg.counter("serve.ann_gather_bytes")
        self._m_refreshes = reg.counter("serve.refreshes")
        self._m_incremental = reg.counter("serve.incremental_updates")
        self._m_rebuilds = reg.counter("serve.full_rebuilds")
        self._m_restage_skipped = reg.counter("serve.restage_skipped")
        self._m_restage_forced = reg.counter("serve.restage_forced")
        # queue-wait distribution behind the adaptive-batching control
        # loop (docs/SERVING.md): the micro-batcher observes every
        # request's measured wait here; AdaptiveWindow reads the windowed
        # p99 back out
        self._m_queue_wait = reg.histogram("serve.queue_wait_ms",
                                           window_s=window_s, cap=reservoir)
        # recompilation visibility (docs/OBSERVABILITY.md): the serving
        # path tracks every (program, shape) key it dispatches; a
        # first-seen key means XLA compiles — the classic hidden p99
        # cliff an SLO trial would otherwise misattribute to load
        self._m_recompiles = reg.counter("serve.recompiles")
        self._compiled_keys: set = set()   # guarded-by: _compiled_lock
        self._compiled_lock = threading.Lock()
        # LRU query-embedding cache: normalized text + the store's model
        # step -> host fp32 query vector. Step in the KEY means a store
        # re-stamp (ensure_model_step) invalidates without a flush.
        serve_cfg = getattr(cfg, "serve", None)
        # guarded-by: _cache_lock
        self._cache: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
        self._cache_cap = (serve_cfg.query_cache_size
                           if serve_cfg is not None else 0)
        self._cache_lock = threading.Lock()
        # Generation-keyed result cache (docs/SERVING.md "Result cache"):
        # (normalized text, k, nprobe, store generation, index generation)
        # -> formatted top-k hits, probed at the admission door BEFORE a
        # repeat can consume a micro-batch bucket slot. refresh() bumps
        # the generations, so a swap invalidates for free — stale entries
        # age out of the LRU under unreachable keys.
        self._rcache_cap = (
            serve_cfg.result_cache_size
            if serve_cfg is not None
            and getattr(serve_cfg, "result_cache", False) else 0)
        # fleet sharing (FLAG_RESULT_CACHE / T_CACHE_* frames) rides on
        # top of the local cache — never enabled without it
        self._rcache_fleet = bool(
            self._rcache_cap
            and getattr(serve_cfg, "result_cache_fleet", False))
        # guarded-by: _rcache_lock
        self._rcache: "OrderedDict[tuple, list]" = OrderedDict()
        # guarded-by: _rcache_lock
        self._rcache_bytes = 0
        self._rcache_lock = threading.Lock()
        # result-cache peers (attach_cache_peers): SocketSearchClient
        # handles to sibling front ends sharing the hot set
        # guarded-by: _rcache_lock
        self._rcache_peers: list = []
        # per-peer circuit breakers, index-aligned with _rcache_peers
        # (cache_peer breaker scope — docs/ROBUSTNESS.md): a down
        # sibling is skipped cheaply instead of costing a dial/timeout
        # on every local miss
        # guarded-by: _rcache_lock
        self._rcache_peer_breakers: list = []
        self._m_rcache_hits = reg.counter("serve.result_cache_hits",
                                          window_s=window_s)
        self._m_rcache_misses = reg.counter("serve.result_cache_misses",
                                            window_s=window_s)
        # IVF ANN routing (docs/ANN.md): serve.index="ivf" tries the
        # inverted-file index; every request re-checks it against the
        # store's stamp and falls back to the exact path (counted) when
        # the index is missing/stale/quarantined. "exact" (the default)
        # never touches the index machinery — byte-identical behavior.
        self._serve_index = (getattr(serve_cfg, "index", "exact")
                             if serve_cfg is not None else "exact")
        self._nprobe = (getattr(serve_cfg, "nprobe", 8)
                        if serve_cfg is not None else 8)
        # PQ/ADC knobs (docs/ANN.md): exact-rerank depth per query (0 =
        # the index default) and the HBM budget for the resident hot
        # posting set — staged at view build, so resident lists answer
        # with zero per-request host gather
        self._pq_rerank = (getattr(serve_cfg, "pq_rerank", 0)
                           if serve_cfg is not None else 0)
        # filtered retrieval (docs/ANN.md "Filtered retrieval"):
        # serve.filters gates accepting/advertising predicates on the
        # wire; serve.filter_escalate is the probe-widening factor when
        # a filtered IVF probe set under-fills k (<=1 disables)
        self._filters_enabled = (getattr(serve_cfg, "filters", True)
                                 if serve_cfg is not None else True)
        self._filter_escalate = (getattr(serve_cfg, "filter_escalate", 4.0)
                                 if serve_cfg is not None else 4.0)
        self._hot_gb = (getattr(serve_cfg, "hot_postings_gb", 0.0)
                        if serve_cfg is not None else 0.0)
        # partitioned + replicated serving (infer/partition.py,
        # docs/SCALING.md "Partitioned serving"): P x R host-simulated
        # partition workers behind the scatter-gather; 1 x 1 keeps the
        # single-view path below byte-identical
        self._partitions = (getattr(serve_cfg, "partitions", 1)
                            if serve_cfg is not None else 1)
        self._replicas = (getattr(serve_cfg, "replicas", 1)
                          if serve_cfg is not None else 1)
        self._shed_queue = (getattr(serve_cfg, "replica_shed_queue", 8)
                            if serve_cfg is not None else 8)
        self._m_replica_shed = reg.counter("serve.replica_shed")
        self._m_partition_degraded = reg.counter("serve.partition_degraded")
        # -- over-the-wire serving (infer/transport.py, infer/server.py,
        # infer/partition_host.py; docs/SERVING.md "Network front end") --
        # The admission clock is injectable so deadline semantics are
        # testable on a fake clock; everything else on the query path
        # keeps using time.perf_counter directly.
        self._clock = clock if clock is not None else time.perf_counter
        # default per-request deadline budget applied at the network edge
        # when a request carries none (0 = no deadline)
        self._deadline_ms = (getattr(serve_cfg, "deadline_ms", 0.0)
                             if serve_cfg is not None else 0.0)
        # deadline-aware admission: a request shed at the door (expired,
        # or the windowed queue-wait p99 says it cannot make its budget)
        # counts here — and ONLY here; a shed is not an error
        self._m_deadline_shed = reg.counter("serve.deadline_shed",
                                            window_s=window_s)
        # hedged fan-out + wire accounting (populated by the worker
        # gateway / socket front end when transport serving is attached).
        # wire_raw_bytes is the raw-frame EQUIVALENT of the same traffic
        # — compressed RESULT frames and interned query blocks count what
        # they replaced — so raw/actual is the live wire-compression
        # ratio (serve.wire_compress, docs/SERVING.md)
        self._m_hedge_fired = reg.counter("serve.hedge_fired")
        self._m_wire_bytes = reg.counter("serve.wire_bytes")
        self._m_wire_raw = reg.counter("serve.wire_raw_bytes")
        # the RPC fan-out (partition_host.WorkerGateway), attached by
        # attach_gateway(); None = the in-process scatter-gather
        self._fanout = None
        upd_cfg = getattr(cfg, "updates", None)
        self._rebuild_drift = (getattr(upd_cfg, "rebuild_drift", 0.25)
                               if upd_cfg is not None else 0.25)
        self._auto_update_index = (
            getattr(upd_cfg, "auto_update_index", True)
            if upd_cfg is not None else True)
        self._restage_density = (
            getattr(upd_cfg, "restage_tombstone_density", 0.05)
            if upd_cfg is not None else 0.05)
        # micro-batch window: fixed at serve.batch_window_ms, or driven by
        # the AdaptiveWindow controller under serve.batch_window_adaptive
        # (off by default — the fixed path is byte-identical to before).
        # The live window is always readable as the serve.batch_window_ms
        # gauge; every adaptive change emits a window_adapt event.
        self._window_base_ms = (getattr(serve_cfg, "batch_window_ms", 2.0)
                                if serve_cfg is not None else 2.0)
        win_gauge = reg.gauge("serve.batch_window_ms")
        win_gauge.set(self._window_base_ms)
        self._window_ctl: Optional[AdaptiveWindow] = None
        if serve_cfg is not None and getattr(
                serve_cfg, "batch_window_adaptive", False):
            self._window_ctl = AdaptiveWindow(
                self._window_base_ms,
                getattr(serve_cfg, "batch_window_max_ms", 25.0),
                self._m_queue_wait, gauge=win_gauge,
                on_change=self._on_window_adapt)
        self._batcher: Optional[_MicroBatcher] = None
        self._batch_totals = (0, 0)   # (batches, requests) after close()
        # background maintenance (docs/MAINTENANCE.md): start_maintenance()
        # attaches the service and — under maintenance.bg_rebuild — moves
        # drift-triggered IVF full rebuilds off the refresh() caller onto
        # its rebuild worker (refresh defers; the worker builds beside the
        # live index and hot-swaps). Without the service attached, refresh
        # keeps the inline-rebuild behavior.
        self._maintenance = None
        self._defer_rebuilds = False
        reg.gauge("serve.index_rebuild_pending").set(0.0)
        self._log = log
        # Per-query encode is O(1 query), not the 512-row bulk-embed batch
        # wearing a serving hat (VERDICT r4 Weak #2): queries pad only to a
        # small compiled bucket, rounded UP to the next multiple of the mesh
        # 'data' axis so the batch always shards evenly — max(8, n_data)
        # broke the jitted _encode_query for non-dividing axes like 3/5/6
        # (ADVICE r5). warmup() measures the warm per-query latency.
        # ONE n_data for the whole service: the ["data"] spelling raised
        # KeyError on meshes without a 'data' axis.
        n_data = max(embedder.mesh.shape.get("data", 1), 1)
        self._n_data = n_data
        self.query_batch = query_batch or -(-8 // n_data) * n_data
        # the encode's compiled width (serve.encode_batch); by default the
        # bucket the scan uses
        self._encode_batch = cfg.serve.encode_batch or self.query_batch
        if self._encode_batch < 0 or self._encode_batch % n_data:
            raise ValueError(
                f"serve.encode_batch {self._encode_batch} must be a "
                f"positive multiple of the mesh's data axis ({n_data})")
        self.warm_latency_ms: Optional[float] = None
        self._preload_gb = preload_hbm_gb
        self._refresh_lock = threading.Lock()   # one refresh at a time
        # the refresh lock is an outer layer: the view build under it
        # counts fault retries, never the reverse (graftcheck lock-order)
        # lock-order: SearchService._refresh_lock < faults._COUNTER_LOCK
        self._pset = None
        if self._partitions * self._replicas > 1:
            from dnn_page_vectors_tpu.infer.partition import PartitionSet
            self._pset = PartitionSet(self, store,
                                      partitions=self._partitions,
                                      replicas=self._replicas,
                                      shed_queue=self._shed_queue)
            # the control view: partition 0's primary — store-level fields
            # (generation, maint stats) are identical on every view; the
            # compat windows (_shards/_index) read partition 0's slice
            self._view = self._pset.primary_view()
        else:
            self._view = self._build_view(store)
        self.registry.gauge("serve.degraded").set(
            1.0 if self.degraded else 0.0)
        self.registry.gauge("serve.store_generation").set(
            self._view.generation)
        if log is not None:
            view = self._view
            log.write({
                "serve_degraded": self.degraded,
                "serve_hbm_shards": len(view.shards or []),
                "serve_stream_shards": len(view.stream_entries),
                "serve_vectors": view.num_vectors,
                "serve_query_batch": self.query_batch,
                "serve_query_cache_size": self._cache_cap,
                "serve_index": self._serve_index,
                "serve_ann_available": view.index is not None,
                "store_generation": view.generation,
                "fault_counters": faults.counters(),
            })

    @property
    def preloaded(self) -> bool:
        return self._view.shards is not None

    # read-only compatibility windows into the current view (tests and
    # telemetry peek at these; the query path captures the view ONCE)
    @property
    def _shards(self):
        return self._view.shards

    @property
    def _stream_entries(self) -> List[Dict]:
        return self._view.stream_entries

    @property
    def _index(self):
        return self._view.index

    @property
    def _index_error(self) -> Optional[str]:
        return self._view.index_error

    # serving counters are registry instruments (docs/OBSERVABILITY.md);
    # these read-only windows keep the pre-registry attribute surface that
    # tests, `cli loadtest` and operator scripts already use
    @property
    def cache_hits(self) -> int:
        return self._m_cache_hits.value

    @property
    def cache_misses(self) -> int:
        return self._m_cache_misses.value

    @property
    def result_cache_hits(self) -> int:
        return self._m_rcache_hits.value

    @property
    def result_cache_misses(self) -> int:
        return self._m_rcache_misses.value

    @property
    def ann_lists_scanned(self) -> int:
        return self._m_ann_lists.value

    @property
    def ann_candidates_reranked(self) -> int:
        return self._m_ann_reranked.value

    @property
    def ann_fallbacks(self) -> int:
        return self._m_ann_fallbacks.value

    @property
    def ann_gather_bytes(self) -> int:
        return self._m_ann_gather.value

    @property
    def refreshes(self) -> int:
        return self._m_refreshes.value

    @property
    def incremental_updates(self) -> int:
        return self._m_incremental.value

    @property
    def full_rebuilds(self) -> int:
        return self._m_rebuilds.value

    # tombstone-aware restage policy counters (docs/UPDATES.md):
    # skipped = staged shard reused with its new dead rows masked in
    # the id table; forced = dead density crossed the threshold and
    # the shard restaged compacted
    @property
    def restage_skipped(self) -> int:
        return self._m_restage_skipped.value

    @property
    def restage_forced(self) -> int:
        return self._m_restage_forced.value

    # partitioned-serving routing counters (docs/SCALING.md): shed =
    # traffic moved off a partition's primary replica (restaging /
    # degraded / over queue budget); partition_degraded = a partition
    # whose replicas were ALL degraded served degraded locally instead of
    # returning an empty slice
    @property
    def replica_shed(self) -> int:
        return self._m_replica_shed.value

    @property
    def partition_degraded_serves(self) -> int:
        return self._m_partition_degraded.value

    @property
    def partition_set(self):
        """The live PartitionSet (None on a single-view service)."""
        return self._pset

    # -- over-the-wire serving (docs/SERVING.md "Network front end") -------
    @property
    def deadline_sheds(self) -> int:
        return self._m_deadline_shed.value

    @property
    def hedge_fires(self) -> int:
        return self._m_hedge_fired.value

    @property
    def wire_bytes(self) -> int:
        return self._m_wire_bytes.value

    @property
    def wire_raw_bytes(self) -> int:
        """Raw-frame equivalent of wire_bytes (the compression ratio's
        numerator); equals wire_bytes when nothing negotiated
        compression."""
        return self._m_wire_raw.value

    @property
    def fanout(self):
        """The attached WorkerGateway (None = in-process scatter)."""
        return self._fanout

    def attach_gateway(self, gateway) -> None:
        """Wire a partition_host.WorkerGateway into the query path: the
        scatter becomes an RPC fan-out to registered partition workers,
        and replica routing derives health from worker LIVENESS
        (heartbeats) on top of the in-process flags — a partition whose
        worker connection died sheds with reason "liveness" exactly like
        a restaging replica sheds today. Detach with attach_gateway(None)
        (the gateway itself is closed by whoever opened it)."""
        self._fanout = gateway
        pset = gateway.partition_set if gateway is not None else self._pset
        if pset is not None:
            pset.set_liveness(
                gateway.worker_alive if gateway is not None else None)

    def default_deadline(self, deadline_ms: Optional[float] = None
                         ) -> Optional[float]:
        """Resolve a RELATIVE deadline budget (ms; None/<=0 = the
        serve.deadline_ms default, which may itself be off) into an
        ABSOLUTE deadline on the service clock, or None."""
        dl = self._deadline_ms if deadline_ms is None else deadline_ms
        if dl is None or dl <= 0:
            return None
        return self._clock() + dl / 1000.0

    def _shed_deadline(self, reason: str, deadline: Optional[float],
                       queue_wait_p99_ms: Optional[float] = None,
                       trace=None) -> DeadlineExceeded:
        """Count + record one admission shed and BUILD (not raise) the
        exception: admission raises it, the micro-batch door sets it on
        the shed request's future."""
        self._m_deadline_shed.inc()
        rem_ms = (None if deadline is None
                  else round((deadline - self._clock()) * 1000.0, 3))
        cur = trace if trace is not None else self.tracer.current()
        attrs = {"reason": reason, "remaining_ms": rem_ms}
        if queue_wait_p99_ms is not None:
            attrs["queue_wait_p99_ms"] = round(queue_wait_p99_ms, 3)
        self.registry.event(
            "deadline_shed", attrs,
            trace_id=getattr(cur, "trace_id", None))
        msg = f"request shed at admission ({reason}"
        if rem_ms is not None:
            msg += f"; {rem_ms} ms remaining"
        if queue_wait_p99_ms is not None:
            msg += f"; queue-wait p99 {queue_wait_p99_ms:.1f} ms"
        return DeadlineExceeded(msg + ")")

    def _admit(self, deadline: Optional[float]) -> None:
        """The admission-control ladder (docs/SERVING.md "Network front
        end"): (1) a deadline that has ALREADY expired is shed
        immediately — it must never consume queue capacity or a
        micro-batch bucket slot; (2) SLO-budget shedding — when the
        windowed queue-wait p99 (the same instrument the adaptive-window
        controller reads) says the queue alone will eat the remaining
        budget, the request cannot make its deadline and is shed at the
        door instead of timing out after occupying a slot. Raises
        DeadlineExceeded; no-deadline requests always admit."""
        if deadline is None:
            return
        rem_ms = (deadline - self._clock()) * 1000.0
        if rem_ms <= 0.0:
            raise self._shed_deadline("expired", deadline)
        if self._batcher is not None:
            qw = self._m_queue_wait
            if qw.window_count() >= 4:
                p99 = qw.window_percentile(99)
                if p99 > rem_ms:
                    raise self._shed_deadline("slo_budget", deadline,
                                              queue_wait_p99_ms=p99)

    @contextlib.contextmanager
    def _stage(self, name: str, **attrs):
        """One serving stage, observed three times over one interval:
        cumulative seconds into the PipelineProfiler (the aggregate view),
        its `serve.<name>` event in the jax profiler's trace when a session
        is recording (the device's clock), and a span on the active request
        trace (the per-request view). Yields the span so call sites can
        attach attributes (ANN stats, cache hits)."""
        with self.profiler.stage(name), \
                self.tracer.span(name, **attrs) as sp:
            yield sp

    def _count_fault(self, name: str) -> None:
        self.fault_counters[name] = self.fault_counters.get(name, 0) + 1
        faults.count(name)

    # -- adaptive batching (docs/SERVING.md) -------------------------------
    @property
    def batch_window_ms(self) -> float:
        """The micro-batch window currently in force (ms): the configured
        base, or wherever the adaptive controller has moved it."""
        return (self._window_ctl.current_ms if self._window_ctl is not None
                else self._window_base_ms)

    def _adapt_window(self) -> None:
        """One adaptive-window control step; no-op with adaptation off.
        Called by the micro-batcher after every dispatch."""
        if self._window_ctl is not None:
            self._window_ctl.update()

    def _on_window_adapt(self, old_ms: float, new_ms: float,
                         queue_wait_p99_ms: float, reason: str) -> None:
        cur = self.tracer.current()
        self.registry.event("window_adapt", {
            "old_ms": round(old_ms, 3), "new_ms": round(new_ms, 3),
            "queue_wait_p99_ms": round(queue_wait_p99_ms, 3),
            "reason": reason,
        }, trace_id=cur.trace_id if cur is not None else None)

    # -- recompilation visibility (docs/OBSERVABILITY.md) ------------------
    @property
    def recompiles(self) -> int:
        return self._m_recompiles.value

    def _note_dispatch_shape(self, program: str, **shape) -> None:
        """Count a jit cache miss when the serving path dispatches a
        (program, shape) key it has never dispatched before — first-seen
        keys are exactly the dispatches XLA must compile for. Silent
        recompiles (a new k, a ragged bucket, a refresh changing pad_rows)
        are the classic hidden p99 cliff; the `recompile` event carries
        the bucket shape so an SLO trial's latency spike attributes to
        the compile, not to offered load."""
        key = (program, tuple(sorted(shape.items())))
        with self._compiled_lock:
            if key in self._compiled_keys:
                return
            self._compiled_keys.add(key)
        self._m_recompiles.inc()
        cur = self.tracer.current()
        self.registry.event("recompile", {"program": program, **shape},
                            trace_id=cur.trace_id if cur is not None
                            else None)

    def _count_encode(self, counts) -> None:
        """What one encode call counted (BulkEmbedder.encode_query_call's
        second value) into the registry's `encode.*`: the four sums are
        pulled, nothing else; None (a tower that counts nothing) is a
        no-op."""
        if counts is None:
            return
        for counter, n in zip(self._m_encode.values(),
                              np.asarray(counts[0]).tolist()):
            counter.inc(n)

    # -- hot-swap refresh (docs/UPDATES.md) --------------------------------
    def refresh(self, update_index: Optional[bool] = None) -> Dict:
        """Swap in the store's CURRENT generation chain with zero downtime:
        re-open the store (fresh handle — the serving view's generations
        and tombstones are frozen per view, so in-flight queries never see
        a half-applied update), restage only the shards the old view
        doesn't already hold on device, bring the IVF index up to date
        (incremental posting append, or drift-triggered full rebuild —
        `update_index` overrides updates.auto_update_index), and publish
        the new view with one atomic reference assignment between
        micro-batcher dispatches. Queries keep flowing the whole time:
        buckets in flight finish on the old view, the next bucket sees the
        new one, and a failed index update degrades THAT view to exact
        search instead of taking the service down."""
        t0 = time.perf_counter()
        part_info = None
        with self._refresh_lock:
            old = self._view
            # fresh handle: verify() gates appended bytes exactly like the
            # base open did, and the old view's store object stays frozen
            new_store = VectorStore(self.store.directory)
            upd = (self._auto_update_index if update_index is None
                   else update_index)
            if self._pset is not None:
                # partitioned: a ROLLING per-partition swap — while one
                # partition restages (its router sheds to a replica), the
                # others keep serving their current views untouched; the
                # store-level IVF update runs exactly once, on the first
                # view built (infer/partition.py)
                t_swap = time.perf_counter()
                part_info = self._pset.refresh(new_store, update_index=upd)
                view = self._pset.primary_view()
                self._view = view
            else:
                view = self._build_view(new_store, reuse=old,
                                        update_index=upd)
                t_swap = time.perf_counter()
                self._view = view    # THE swap: one reference assignment
            self.store = new_store
            self._m_refreshes.inc()
            # tower adoption (docs/MAINTENANCE.md "Rolling model
            # migration"): once the store's migration record is gone the
            # sweep either completed (stamp flipped — the target tower
            # becomes THE query encoder) or was abandoned by a reset;
            # either way the extra towers unload here, and the superseded
            # params drop with this reference
            adopted_step = None
            tw = self._towers
            if tw and new_store.migration is None:
                if new_store.model_step in tw:
                    self.embedder.params = tw[new_store.model_step]
                    adopted_step = int(new_store.model_step)
                self._towers = {}
        swap_ms = (time.perf_counter() - t_swap) * 1000.0
        info = {
            "store_generation": view.generation,
            "index_generation": (view.index.index_generation
                                 if view.index is not None else None),
            "docs_appended": view.docs_appended,
            "new_docs": view.docs_appended - old.docs_appended,
            "tombstoned": view.tombstoned,
            "vectors": view.num_vectors,
            "hbm_shards": len(view.shards or []),
            "stream_shards": len(view.stream_entries),
            "refresh_seconds": round(time.perf_counter() - t0, 3),
            "swap_ms": round(swap_ms, 3),
        }
        if view.index_info is not None:
            info["index_update"] = view.index_info
        if view.index_error is not None:
            info["index_error"] = view.index_error
        mig = view.store.migration
        if mig is not None:
            # migration progress rides every refresh log line while the
            # sweep runs: which stamps this view serves, and how far the
            # shard table has moved to the target
            table = view.store.shards()
            info["migration"] = {
                "from_step": mig.get("from_step"),
                "to_step": mig.get("to_step"),
                "shards_migrated": sum(
                    1 for e in table
                    if view.store.entry_step(e) == mig.get("to_step")),
                "shards_total": len(table),
                "stamps_serving": list(view.steps)}
        if adopted_step is not None:
            info["migration_adopted_step"] = adopted_step
        if part_info is not None:
            # per-partition rolling-swap record (docs/SCALING.md): which
            # partition restaged when, and each replica's swap window
            info["partitions"] = part_info
        if self._fanout is not None:
            # over-the-wire fleet (docs/SERVING.md "Network front end"):
            # tell every registered worker to rebuild onto this
            # generation (T_REFRESH control frame) — no worker restart.
            # The broadcast does NOT block the refresh: until a worker
            # acks, routing treats it as generation-stale and its slice
            # serves from the local view just swapped in above, so
            # results stay byte-consistent while the fleet catches up
            info["workers_refresh"] = self._fanout.broadcast_refresh(
                view.generation)
        # lifecycle event (docs/OBSERVABILITY.md): the hot-swap is the
        # transition dashboards alert on; trace-id correlation ties it to
        # the request that observed it when refresh runs under a trace
        cur = self.tracer.current()
        self.registry.event("view_swap", {
            "store_generation": view.generation,
            "new_docs": info["new_docs"],
            "swap_ms": info["swap_ms"],
            "index_error": view.index_error,
        }, trace_id=cur.trace_id if cur is not None else None)
        self.registry.gauge("serve.store_generation").set(view.generation)
        if view.index is not None:
            self.registry.gauge("serve.index_generation").set(
                view.index.index_generation)
        if self._log is not None:
            self._log.write({"serve_refresh": self.refreshes, **info})
        return info

    def restage_hot(self) -> Dict:
        """Re-rank and re-stage the CURRENT view's HBM-resident hot
        posting set against the measured popularity window (docs/ANN.md
        "Popularity tiering") — no store re-open, no view swap: the same
        index object re-pins the lists its own scan counts say are
        hottest, then halves the window. The staged state publishes with
        one reference assignment, so in-flight ADC searches finish on
        whichever residency they captured. Returns the stage_hot summary
        ({} when there is nothing to restage: exact serving, no PQ, or
        no HBM budget), and emits a `hot_restaged` event."""
        view = self._view
        idx = view.index if view is not None else None
        if idx is None or idx.pq is None or self._hot_gb <= 0:
            return {}
        with self._refresh_lock:
            hot = idx.stage_hot(self._hot_gb * 2 ** 30)
        self.registry.event("hot_restaged", dict(hot))
        return hot

    def begin_migration(self, params, step: int) -> None:
        """Attach the TARGET model's params as a second query tower for a
        rolling migration (docs/MAINTENANCE.md "Rolling model migration").
        Until the completion flip, every search encodes with both towers
        and each shard's scores come from the tower matching its recorded
        stamp; the refresh() that observes the flipped store adopts this
        tower and unloads the old one. Idempotent per step; whole-dict
        swap, so the query path never sees a half-updated tower map."""
        self._towers = {**self._towers, int(step): params}
        if self._log is not None:
            self._log.write({"serve_migration_tower": int(step),
                             "serving_step": self.store.model_step})

    def _build_view(self, store: VectorStore, reuse: "_ServeView" = None,
                    update_index: bool = False,
                    entries: Optional[List[Dict]] = None,
                    hot_gb: Optional[float] = None) -> "_ServeView":
        """One serving view over `store` — the whole shard table, or
        (partitioned serving) the `entries` subset with `hot_gb` as this
        partition's cut of the hot-posting HBM budget."""
        view = _ServeView(store, entries=entries)
        # dead-byte accounting as registry gauges (docs/MAINTENANCE.md):
        # the compaction trigger's inputs ride the same exposition as
        # every other serving number (metrics(), cli serve-metrics)
        ms = view.maint_stats
        self.registry.gauge("serve.tombstone_density").set(
            ms["tombstone_density"])
        self.registry.gauge("serve.dead_rows").set(ms["dead_rows"])
        self.registry.gauge("serve.reclaimable_bytes").set(
            ms["reclaimable_bytes"])
        # Budget against the ACTUAL device footprint: every shard is padded
        # to the max shard row count for one static compiled shape, so an
        # uneven store (merged multi-writer shards) costs
        # n_shards * padded_rows, which can far exceed num_vectors.
        rows = max((s["count"] for s in view.entries), default=0)
        rows += (-rows) % self._n_data
        view.pad_rows = rows
        # budget is PER DEVICE: shards are row-sharded over 'data', so each
        # device holds rows/n_data of every staged shard (ADVICE r4) — at
        # the STORED width (fp16 rows, or int8 codes + fp16 scale per row)
        per_row = (store.dim + 2 if store.manifest["dtype"] == "int8"
                   else store.dim * 2)
        need = len(view.entries) * rows * per_row / self._n_data
        # rows > 0: a store of only zero-count shards has nothing to stage
        # (need == 0 would pass even the explicit never-preload 0.0 budget)
        if view.entries and rows > 0 and need <= self._preload_gb * 2**30:
            self._stage_view(view, rows,
                             budget_bytes=self._preload_gb * 2**30,
                             per_row=per_row, reuse=reuse)
            if not view.shards:       # nothing survived staging
                view.shards = None    # stream instead; handles empty stores
        if self._serve_index == "ivf":
            self._attach_index(
                view, update_index,
                shard_indices=([e["index"] for e in view.entries]
                               if view.restricted else None),
                hot_gb=hot_gb, reuse=reuse)
            if (reuse is not None and reuse.index_error is not None
                    and view.index is not None):
                # a degraded-to-exact view healed across the refresh
                self.registry.event("index_restored", {
                    "was": reuse.index_error[:200],
                    "index_generation": view.index.index_generation})
        return view

    # -- IVF ANN index (docs/ANN.md, docs/UPDATES.md) ----------------------
    def _attach_index(self, view: "_ServeView", update_index: bool,
                      shard_indices: Optional[List[int]] = None,
                      hot_gb: Optional[float] = None,
                      reuse: "_ServeView" = None) -> None:
        from dnn_page_vectors_tpu.index.ivf import IndexUnavailable, IVFIndex
        hot_gb = self._hot_gb if hot_gb is None else hot_gb
        try:
            if update_index:
                serve_cfg = self.cfg.serve
                view.index, view.index_info = IVFIndex.update(
                    view.store, self.embedder.mesh,
                    rebuild_drift=self._rebuild_drift,
                    nlist=serve_cfg.nlist, iters=serve_cfg.kmeans_iters,
                    init=getattr(serve_cfg, "kmeans_init", "kmeans++"),
                    defer_rebuild=self._defer_rebuilds)
                action = view.index_info.get("action")
                if action == "incremental":
                    self._m_incremental.inc()
                elif action == "rebuild":
                    self._m_rebuilds.inc()
                    self.registry.event("drift_rebuild", {
                        "drift": view.index_info.get("drift"),
                        "nlist": view.index_info.get("nlist")})
                # a drift overrun deferred off this caller: the gauge is
                # the hand-off to the background rebuild worker
                # (docs/MAINTENANCE.md) — it clears when the worker swaps
                self.registry.gauge("serve.index_rebuild_pending").set(
                    1.0 if view.index_info.get("rebuild_pending") else 0.0)
            else:
                view.index = IVFIndex.open(view.store)
            view.index_error = None
            if view.index is not None and shard_indices is not None:
                # partitioned serving: THIS view searches only its slice
                # of the inverted file — posting gathers, ADC code reads,
                # and the hot staging below all see the partition's
                # shards and nothing else (index/ivf.py partition_view)
                view.index = view.index.partition_view(shard_indices)
            if (view.index is not None and reuse is not None
                    and reuse.index is not None
                    and reuse.index.nlist == view.index.nlist):
                # carry the measured popularity window across the view
                # rebuild (docs/ANN.md "Popularity tiering"): the fresh
                # index object starts cold, but the traffic didn't — the
                # staged hot set below should keep tracking the head
                # instead of reverting to biggest-first on every refresh
                view.index.scan_counts = reuse.index.scan_counts.copy()
            if (view.index is not None and view.index.pq is not None
                    and hot_gb > 0):
                # HBM-resident hot posting set (docs/ANN.md): staged per
                # VIEW — a refresh re-opens the index, so the staged codes
                # (and their tombstone masks) follow the same hot-swap
                # cadence as the staged store shards. A staging failure
                # costs the residency, never the index.
                try:
                    hot = view.index.stage_hot(hot_gb * 2 ** 30)
                    if view.index_info is not None:
                        view.index_info = {**view.index_info, **hot}
                except Exception as e:  # noqa: BLE001
                    self._count_fault("serve_hot_stage_faults")
                    faults.warn(f"hot posting staging failed "
                                f"({type(e).__name__}: {e}); serving the "
                                "mmap gather path")
        except IndexUnavailable as e:
            view.index = None
            view.index_error = str(e)
            self.registry.event("index_degraded",
                                {"reason": str(e)[:200], "mode": "exact"})
            faults.warn(f"IVF index unavailable ({e}); serving the exact "
                        "path per request")
        except Exception as e:  # noqa: BLE001 — e.g. a posting-append
            # fault mid-update: the on-disk manifest is untouched (it lands
            # last), but it no longer matches the live table, so THIS view
            # serves exact — visibly — until a later refresh/rebuild
            view.index = None
            view.index_error = f"{type(e).__name__}: {e}"
            self._count_fault("serve_index_update_failures")
            self.registry.event("index_degraded", {
                "reason": view.index_error[:200], "mode": "exact"})
            faults.warn(f"IVF index update failed ({view.index_error}); "
                        "serving the exact path until a rebuild")

    def _ann_topk(self, view: "_ServeView", qv: np.ndarray, n: int, k: int,
                  nprobe: Optional[int] = None, predicate=None):
        """ANN (scores [n, k], page_ids [n, k], scan_bytes) for `n` real
        queries, or None to fall back to the exact path (index missing,
        stale against the view store's CURRENT model step, mid-migration
        mixed stamps, or failing at search time — the failure quarantine
        already happened inside the index layer). `nprobe` overrides the
        serve.nprobe default per request (mixed-profile load tests)."""
        idx = view.index
        if idx is None or idx.model_step != view.store.model_step:
            return None
        if len(view.steps) > 1:
            # mid-migration a single-stamp index would rank OLD-encoder
            # centroids against new-encoder shards (or vice versa): the
            # exact path routes per shard stamp instead, and the per-stamp
            # rebuild swaps a matching index back in after completion
            return None
        nprobe = nprobe or self._nprobe
        # the index pads queries to a power-of-two bucket internally:
        # mirror that key so the counter moves exactly when XLA compiles
        self._note_dispatch_shape("ivf_search", k=k, nprobe=nprobe,
                                  qpad=1 << (max(1, n) - 1).bit_length())
        try:
            with self._stage("topk") as sp:
                scores, ids, st = idx.search(
                    qv[:n], k=k, nprobe=nprobe,
                    rerank=self._pq_rerank or None,
                    predicate=predicate,
                    escalate=self._filter_escalate)
                # the ANN cost triple ON the request's span (why THIS
                # query was slow): lists probed, payload bytes gathered,
                # rows exact-reranked — plus, filtered, how many queries
                # under-filled k and re-probed wider
                sp.set_attrs(
                    lists_scanned=st.get("lists_scanned", 0),
                    gather_bytes=st.get("gather_bytes", 0),
                    rows_reranked=st.get("candidates_reranked", 0),
                    filter_escalations=st.get("filter_escalations", 0))
        except Exception as e:  # noqa: BLE001 — any index failure degrades
            view.index = None
            view.index_error = f"{type(e).__name__}: {e}"
            cur = self.tracer.current()
            self.registry.event(
                "index_degraded",
                {"reason": view.index_error[:200], "mode": "exact"},
                trace_id=cur.trace_id if cur is not None else None)
            faults.warn(f"IVF search failed ({view.index_error}); "
                        "falling back to exact search")
            return None
        self._m_ann_lists.inc(st.get("lists_scanned", 0))
        self._m_ann_reranked.inc(st.get("candidates_reranked", 0))
        self._m_ann_gather.inc(st.get("gather_bytes", 0))
        return (np.asarray(scores, np.float32), np.asarray(ids, np.int64),
                int(st.get("gather_bytes", 0)))

    def _stage_view(self, view: "_ServeView", rows: int,
                    budget_bytes: float, per_row: int,
                    reuse: "_ServeView" = None) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        plan = faults.active()
        store = view.store
        # restage only what the old view doesn't hold: appended generations
        # arrive as NEW shard indices, so a refresh re-uses every already-
        # staged device array (keyed on gen/index/count/crc) and pays
        # device transfer for exactly the delta; ids reload host-side so
        # newer tombstones re-mask rows the device copy still carries
        reuse_map, span_of = {}, {}
        if (reuse is not None and reuse.shards
                and reuse.pad_rows == rows):
            reuse_map = {key: tup for key, tup
                         in zip(reuse.shard_keys, reuse.shards)}
            span_of = {(shard.n, slot): shard.span
                       for slot, shard in enumerate(reuse.shards)}
        # the scan's `span` argument, made HERE and kept with the shard:
        # (rows that hold a vector, first combined id) as int32 [2]
        # replicated over the mesh, so that a bucket's launch loop
        # (_dispatch_bucket) converts and transfers nothing per shard, and
        # traced, so that every slot runs the one program; a reused shard
        # brings its own along while it keeps its slot
        replicated = NamedSharding(self.embedder.mesh, P())
        staged, keys, stamps = [], [], []

        def device_span(n: int):
            slot = len(staged)
            span = span_of.get((n, slot))
            if span is None:
                span = jax.device_put(
                    np.array([n, slot * rows], np.int32), replicated)
            return span

        used = 0.0
        per_shard = rows * per_row / self._n_data
        for entry in view.entries:
            if entry["count"] == 0:   # zero-count shards hold nothing to score
                continue
            # one stamp per shard, never mixed within one (the migration
            # pin, docs/MAINTENANCE.md): recorded here so _dispatch_bucket
            # can score the shard with the matching tower's query block
            estep = store.entry_step(entry)
            key = (entry.get("gen", 0), entry["index"], entry["count"],
                   entry.get("crc", {}).get("vec"))
            try:
                hit = reuse_map.get(key)
                if hit is not None:
                    old_ids, old_n = hit.ids, hit.n
                    ids = store.load_ids(entry)
                    live = np.asarray(ids[ids >= 0], np.int64)
                    alive_old = old_ids[old_ids >= 0]
                    if np.array_equal(live, alive_old):
                        # staged block current (modulo rows already masked
                        # by an earlier skip): plain reuse
                        staged.append(hit._replace(span=device_span(old_n)))
                        keys.append(key)
                        stamps.append(estep)
                        used += per_shard
                        continue
                    # tombstone-aware restage policy (docs/UPDATES.md):
                    # key equality pins the shard BYTES, so the only
                    # possible drift is newer tombstones. Below the
                    # density threshold the staged block is REUSED with
                    # the dead rows masked in its id table — they can
                    # still occupy a per-shard top-k slot (one result
                    # short, bounded by the threshold) but never surface;
                    # past the threshold the shard restages compacted.
                    dead_frac = (old_n - live.size) / max(old_n, 1)
                    if dead_frac <= self._restage_density:
                        masked = np.where(np.isin(old_ids, live),
                                          old_ids, np.int64(-1))
                        staged.append(hit._replace(
                            ids=masked, span=device_span(old_n)))
                        keys.append(key)
                        stamps.append(estep)
                        used += per_shard
                        self._m_restage_skipped.inc()
                        continue
                    self._m_restage_forced.inc()   # falls through: restage
                plan.check("hbm_stage")
                err = store.entry_error(entry)
                if err is not None:
                    # corrupt bytes must never reach the device: quarantine
                    # drops the shard from the table entirely (its id-range
                    # returns on the next embed resume), and this service
                    # serves without it — degraded, visibly
                    store.quarantine(entry, err)
                    self._count_fault("serve_quarantined_shards")
                    self.degraded = True
                    self.registry.gauge("serve.degraded").set(1.0)
                    self.registry.event("shard_quarantine", {
                        "shard": entry["index"], "error": str(err)[:200]})
                    continue
                if used + per_shard > budget_bytes:
                    raise MemoryError(
                        f"HBM budget overrun mid-stage: shard "
                        f"{entry['index']} needs {per_shard:.0f} B on top of "
                        f"{used:.0f} staged (budget {budget_bytes:.0f})")
                ids, vecs, scl = store._load_entry(entry, raw=True)
                ids = np.asarray(ids, np.int64)
                keep = ids >= 0
                if not keep.all():
                    # compact tombstoned rows out BEFORE the device copy: a
                    # dead vector must not occupy a per-shard top-k slot
                    # (the exact merge would drop it and return short)
                    ids = ids[keep]
                    vecs = np.asarray(vecs)[keep]
                    scl = None if scl is None else np.asarray(scl)[keep]
                n = int(ids.shape[0])
                staged.append(_Shard(
                    ids, n, *stage_shard(vecs, rows, store.dim,
                                         self.embedder.mesh, scales=scl,
                                         words=True),
                    device_span(n)))
                keys.append(key)
                stamps.append(estep)
                used += per_shard
            except Exception as e:  # noqa: BLE001 — any staging failure
                # (injected I/O fault, real device OOM, budget overrun)
                # degrades THIS shard to the streaming path; the service
                # stays up on the shards that did stage
                view.stream_entries.append(entry)
                self.degraded = True
                self._count_fault("serve_stage_faults")
                self.registry.gauge("serve.degraded").set(1.0)
                self.registry.event("degraded", {
                    "shard": entry["index"],
                    "reason": f"{type(e).__name__}: {e}"[:200],
                    "mode": "streaming"})
                faults.warn(
                    f"HBM staging failed for shard {entry['index']} "
                    f"({type(e).__name__}: {e}); serving it via the "
                    "streaming path (degraded)")
        view.shards = staged
        view.shard_keys = keys
        view.shard_steps = stamps
        if not staged:
            return
        # combined-id -> page-id table for the carried scan's ids:
        # shard slot s, padded row r  ->  slot s * rows + r
        view.pid_table = np.full((len(staged) * rows,), -1, np.int64)
        for slot, shard in enumerate(staged):
            view.pid_table[slot * rows: slot * rows + shard.n] = shard.ids

    # -- query-embedding cache --------------------------------------------
    @staticmethod
    def _normalize(query: str) -> str:
        return " ".join(query.split())

    def clear_cache(self) -> None:
        """Flush EVERY serving cache — the query-embedding LRU and the
        generation-keyed result cache — and emit a `cache_cleared` event.
        The manual escape hatch for out-of-band store mutation: normal
        refresh() never needs it (generation keys invalidate for free),
        but a store mutated underneath a live view would otherwise keep
        stale results servable."""
        with self._cache_lock:
            embed_n = len(self._cache)
            self._cache.clear()
        with self._rcache_lock:
            result_n = len(self._rcache)
            self._rcache.clear()
            self._rcache_bytes = 0
        ev = {"embed_entries": embed_n, "result_entries": result_n}
        mig = self.store.migration
        if mig is not None:
            # a flush mid-migration is worth flagging: entries keyed under
            # the OLD stamp composition never come back after the flip, so
            # repeated clears here usually mean a misdriven sweep
            ev["migration"] = (f"{mig.get('from_step')}->"
                               f"{mig.get('to_step')}")
        self.registry.event("cache_cleared", ev)
        if self._log is not None:
            self._log.write({"serve_cache_cleared": True, **ev})

    # -- generation-keyed result cache (docs/SERVING.md "Result cache") ---
    def _result_cache_key(self, query: str, k: Optional[int],
                          nprobe: Optional[int],
                          view=None, filters=None) -> Optional[tuple]:
        """(normalized text, k, nprobe, store gen, index gen, predicate)
        — or None when the cache is off. Generations in the KEY are the
        whole invalidation story: refresh() bumps them, so an entry
        filled against the old view can never answer a post-swap probe.

        The predicate slot is the CANONICAL filter text ("" unfiltered,
        index/attrs.py): a filtered hit and its unfiltered twin live
        under different keys, so a filtered probe can never be answered
        by an unfiltered fill (or vice versa) — same staleness-zero
        story as the generations, by construction not by TTL.

        The store-gen slot COMPOSES the view's model stamp into its high
        32 bits (docs/MAINTENANCE.md "Rolling model migration"): scores
        cached under one encoder must never answer a query encoded by
        another, even across a stamp flip that somehow left both
        generation numbers unchanged — e.g. a restored-from-backup store
        whose counters ran behind. One u64 keeps the peer-cache wire
        format (`transport._CACHE_HEAD`) and cross-front-end keys
        byte-identical without a protocol bump."""
        if self._rcache_cap <= 0:
            return None
        if view is None:
            view = self._view
        if view is None:
            return None          # partitioned serving caches per-request
        index_gen = (view.index.index_generation
                     if view.index is not None else -1)
        sgen = ((int(view.generation) & 0xFFFFFFFF)
                | ((int(view.store.model_step or 0) & 0xFFFFFFFF) << 32))
        return (self._normalize(query), int(k or self.cfg.eval.recall_k),
                int(nprobe or 0), sgen, int(index_gen),
                str(getattr(filters, "text", filters) or ""))

    def _result_cache_get(self, key: Optional[tuple],
                          count: bool = True) -> Optional[list]:
        if key is None:
            return None
        with self._rcache_lock:
            hits = self._rcache.get(key)
            if hits is not None:
                self._rcache.move_to_end(key)
        if hits is None:
            if count:
                self._m_rcache_misses.inc()
            return None
        if count:
            self._m_rcache_hits.inc()
        # copy per hit: callers may mutate the dicts they receive, and
        # the cached entry must stay byte-identical for the next repeat
        return [dict(h) for h in hits]

    def _result_cache_put(self, key: Optional[tuple], hits: list) -> None:
        if key is None:
            return
        size = 96 + sum(64 + len(h.get("snippet") or "") for h in hits)
        entry = [dict(h) for h in hits]
        with self._rcache_lock:
            old = self._rcache.pop(key, None)
            if old is not None:
                self._rcache_bytes -= self._entry_bytes(old)
            self._rcache[key] = entry
            self._rcache_bytes += size
            while len(self._rcache) > self._rcache_cap:
                _, ev = self._rcache.popitem(last=False)
                self._rcache_bytes -= self._entry_bytes(ev)

    @staticmethod
    def _entry_bytes(hits: list) -> int:
        return 96 + sum(64 + len(h.get("snippet") or "") for h in hits)

    def attach_cache_peers(self, clients: Sequence) -> None:
        """Attach sibling front ends' SocketSearchClient handles (built
        with result_cache=True) for fleet-wide sharing: a local miss
        probes each peer's cache before computing, and a local fill is
        pushed to every peer fire-and-forget. Peers that never negotiated
        FLAG_RESULT_CACHE degrade to no-ops per the transport contract.

        Each peer gets its own circuit breaker (`serve.breaker_*` knobs,
        docs/ROBUSTNESS.md "Network failure model"): after K consecutive
        probe failures the sibling is skipped outright — a down peer
        costs one failed dial per open interval, not a dial/timeout on
        every local miss. `serve.breaker_failures <= 0` disables."""
        serve_cfg = getattr(self.cfg, "serve", None)
        k_fail = int(getattr(serve_cfg, "breaker_failures", 3)
                     if serve_cfg is not None else 3)
        open_s = float(getattr(serve_cfg, "breaker_open_s", 0.25)
                       if serve_cfg is not None else 0.25)
        max_s = float(getattr(serve_cfg, "breaker_max_s", 30.0)
                      if serve_cfg is not None else 30.0)
        with self._rcache_lock:
            self._rcache_peers = list(clients)
            self._rcache_peer_breakers = [
                faults.CircuitBreaker(
                    failures=k_fail, open_s=open_s, max_open_s=max_s,
                    on_open=lambda b: faults.count(
                        "cache_peer_breaker_open"))
                if k_fail > 0 else None
                for _ in self._rcache_peers]

    def _peers_with_breakers(self) -> list:
        with self._rcache_lock:
            return list(zip(self._rcache_peers,
                            self._rcache_peer_breakers))

    def _peer_lookup(self, key: tuple) -> Optional[list]:
        """Probe attached peers for a miss; a hit is re-formatted against
        the LOCAL store (same corpus fleet-wide, so byte-identical) and
        inserted locally so the next repeat stays in-process."""
        peers = self._peers_with_breakers()
        if not peers:
            return None
        text, k, nprobe, store_gen, index_gen, ftext = key
        if ftext:
            # the peer-cache wire format (`transport._CACHE_HEAD`) has no
            # predicate slot: filtered entries stay front-end-local, so a
            # cross-peer probe can never alias a filtered key onto an
            # unfiltered sibling entry
            return None
        for peer, br in peers:
            if br is not None and not br.allow():
                continue         # breaker open: skip the down sibling
            try:
                got = peer.cache_lookup(text, k=k, nprobe=nprobe,
                                        store_gen=store_gen,
                                        index_gen=index_gen)
            except Exception:
                if br is not None:
                    br.record_failure()
                continue         # a broken peer never breaks a query
            if br is not None:
                br.record_success()
            if got is None:
                continue
            scores, ids = got
            hits = self._format(scores[0], ids[0])
            self._result_cache_put(key, hits)
            return hits
        return None

    def _peer_put(self, key: Optional[tuple], hits: list) -> None:
        if key is None:
            return
        peers = self._peers_with_breakers()
        if not peers:
            return
        text, k, nprobe, store_gen, index_gen, ftext = key
        if ftext:
            return               # filtered fills never ship to peers
        scores = np.full((k,), -np.inf, np.float32)
        ids = np.full((k,), -1, np.int64)
        for i, h in enumerate(hits[:k]):
            scores[i] = h["score"]
            ids[i] = h["page_id"]
        for peer, br in peers:
            if br is not None and not br.allow():
                continue
            try:
                # False = the frame never left (broken connection, or a
                # peer that never negotiated the flag — skipping that one
                # is free either way), so the bool feeds the breaker
                ok = peer.cache_put(text, k=k, nprobe=nprobe,
                                    store_gen=store_gen,
                                    index_gen=index_gen,
                                    scores=scores, ids=ids)
            except Exception:
                if br is not None:
                    br.record_failure()
                continue
            if br is not None:
                if ok:
                    br.record_success()
                else:
                    br.record_failure()

    # wire-facing helpers (infer/server.py T_CACHE_LOOKUP / T_CACHE_PUT):
    # operate on the raw [1, k] score/id arrays the RESULT frame ships
    def _result_cache_wire_get(self, ck) -> Optional[tuple]:
        """CacheKey probe from a peer. Returns ([1,k] scores, [1,k] ids)
        on a hit, None on a miss / disabled / generation mismatch. Never
        computes — a probe is cheaper than the shed it would replace."""
        if self._rcache_cap <= 0 or not self._rcache_fleet:
            return None
        key = (self._normalize(ck.query), ck.k, int(ck.nprobe),
               ck.store_gen, ck.index_gen, "")
        hits = self._result_cache_get(key)
        if hits is None:
            return None
        scores = np.full((1, ck.k), -np.inf, np.float32)
        ids = np.full((1, ck.k), -1, np.int64)
        for i, h in enumerate(hits[:ck.k]):
            scores[0, i] = h["score"]
            ids[0, i] = h["page_id"]
        return scores, ids

    def _result_cache_wire_put(self, ck, scores: np.ndarray,
                               ids: np.ndarray) -> bool:
        """CacheKey fill from a peer. The generations in the key are
        validated against the LIVE view — a stale push (peer behind a
        refresh) is silently dropped, never inserted under a reachable
        key. Formatting runs against the local store: same corpus
        fleet-wide, so the entry is byte-identical to a local fill."""
        if self._rcache_cap <= 0 or not self._rcache_fleet:
            return False
        live = self._result_cache_key(ck.query, ck.k, ck.nprobe or None)
        if live is None:
            return False
        if (live[3], live[4]) != (ck.store_gen, ck.index_gen):
            return False         # stale generations: drop
        key = (self._normalize(ck.query), ck.k, int(ck.nprobe),
               ck.store_gen, ck.index_gen, "")
        self._result_cache_put(
            key, self._format(np.asarray(scores).reshape(-1),
                              np.asarray(ids).reshape(-1)))
        return True

    def _tower_params(self, step) -> object:
        """Query-tower params for `step`: the extra tower attached by
        begin_migration() when one is loaded for that stamp, else THE
        embedder's own params (snapshot read — the tower map is whole-dict
        swapped)."""
        tw = self._towers
        if step is not None and step in tw:
            return tw[step]
        return self.embedder.params

    def _embed_queries_cached(self, queries: Sequence[str],
                              steps: Optional[Sequence[int]] = None
                              ) -> np.ndarray:
        """[n] texts -> [n, D] fp32 host query vectors — or, when `steps`
        lists more than one model stamp (dual-stamp serving,
        docs/MAINTENANCE.md "Rolling model migration"), [n, S*D] with one
        D-wide block per stamp in ascending-step order; `_qv_blocks` is
        the inverse. Each stamp encodes through the matching tower and its
        own cache keyspace."""
        if steps is None or len(steps) <= 1:
            return self._embed_queries_step(
                queries, steps[0] if steps else self.store.model_step)
        return np.concatenate(
            [self._embed_queries_step(queries, s) for s in steps], axis=1)

    def _embed_queries_step(self, queries: Sequence[str],
                            step) -> np.ndarray:
        """[n] texts -> [n, D] fp32 host query vectors for ONE model
        stamp, through the LRU cache; only the misses pay tokenize +
        compiled encode (in query_batch buckets). Host-side vectors cost
        the queries one device round trip per bucket — amortized over the
        coalesced batch, and the price of cache hits skipping the encode
        dispatch entirely."""
        params = self._tower_params(step)
        keys = [(step, self._normalize(q)) for q in queries]
        out = np.zeros((len(queries), self.store.dim), np.float32)
        miss: List[int] = []
        if self._cache_cap > 0:
            with self._cache_lock:
                for i, key in enumerate(keys):
                    vec = self._cache.get(key)
                    if vec is not None:
                        self._cache.move_to_end(key)
                        out[i] = vec
                    else:
                        miss.append(i)
            self._m_cache_hits.inc(len(queries) - len(miss))
            self._m_cache_misses.inc(len(miss))
        else:
            miss = list(range(len(queries)))
        # cache-hit annotation on the request trace: an all-hit request
        # legitimately has NO tokenize/encode spans — the annotation says
        # why, instead of the trace just looking truncated
        cur = self.tracer.current()
        if cur is not None:
            cur.set_attrs(cache_hits=len(queries) - len(miss),
                          cache_misses=len(miss))
        if not miss:
            return out
        # intra-batch dedup: a coalesced batch of head-skewed traffic
        # repeats queries — encode each unique missing key once, fan the
        # vector out to its duplicates (they still count as lookup misses)
        first: Dict[tuple, int] = {}
        alias: List[tuple] = []
        uniq: List[int] = []
        for i in miss:
            j = first.get(keys[i])
            if j is None:
                first[keys[i]] = i
                uniq.append(i)
            else:
                alias.append((i, j))
        tok = self.embedder.query_tok or self.embedder.page_tok
        B = self._encode_batch
        for s in range(0, len(uniq), B):
            grp = uniq[s: s + B]
            with self._stage("tokenize", queries=len(grp)):
                enc = tok.encode_batch([queries[i] for i in grp])
            pad = B - enc.shape[0]
            if pad:
                enc = np.concatenate(
                    [enc, np.zeros((pad,) + enc.shape[1:], enc.dtype)])
            self._note_dispatch_shape("encode_query", batch=B,
                                      tokens=int(enc.shape[1]))
            # split where the host starts to wait: the put and the launch,
            # then the pull, which blocks on the tower
            with self._stage("encode", queries=len(grp)):
                with self._stage("encode_launch"):
                    vecs, counts = self.embedder.encode_query_call(enc,
                                                                   params)
                with self._stage("encode_wait"):
                    vecs = np.asarray(vecs, np.float32)[: len(grp)]
            self._count_encode(counts)
            out[grp] = vecs
        for i, j in alias:
            out[i] = out[j]
        if self._cache_cap > 0:
            with self._cache_lock:
                for i in miss:
                    self._cache[keys[i]] = out[i]
                    self._cache.move_to_end(keys[i])
                while len(self._cache) > self._cache_cap:
                    self._cache.popitem(last=False)
        return out

    # -- micro-batcher -----------------------------------------------------
    def start_batcher(self) -> "SearchService":
        """Route subsequent search() calls through the dynamic micro-batcher
        (serve.batch_window_ms / serve.max_batch): concurrent callers
        coalesce into shared search_many dispatches. Idempotent; close()
        stops it."""
        if self._batcher is None:
            s = self.cfg.serve
            # the batcher reads the window per batch: fixed base, or
            # wherever the adaptive controller currently has it
            window_s = (self._window_ctl.current_s
                        if self._window_ctl is not None
                        else lambda: self._window_base_ms / 1000.0)
            self._batcher = _MicroBatcher(self, window_s,
                                          s.max_batch, s.max_queue)
            # the collector's passes stall the dispatcher wherever they
            # run: timed as stage `gc` until close()
            self.profiler.watch_gc()
        return self

    @property
    def batching(self) -> bool:
        return self._batcher is not None

    # -- background maintenance (docs/MAINTENANCE.md) ----------------------
    def start_maintenance(self, threads: bool = True):
        """Attach the background MaintenanceService to this service:
        compaction, off-path index rebuilds, and the janitor run against
        this store, hot-swapping completed work in via refresh(). Under
        maintenance.bg_rebuild (the default), drift-triggered full
        rebuilds are DEFERRED off the refresh() caller from here on — the
        worker builds the next index generation beside the live one.
        `threads=False` attaches without spawning workers (callers drive
        `run_once()` themselves: the loadtest mutator, tests). Idempotent;
        close() stops it."""
        if self._maintenance is None:
            from dnn_page_vectors_tpu.maintenance import MaintenanceService
            m_cfg = getattr(self.cfg, "maintenance", None)
            if getattr(m_cfg, "bg_rebuild", True):
                self._defer_rebuilds = True
            self._maintenance = MaintenanceService(
                self.cfg, self.store.directory, self.embedder.mesh,
                svc=self)
            if threads:
                self._maintenance.start()
        return self._maintenance

    def close(self) -> None:
        if self._maintenance is not None:
            self._maintenance.close()
            self._maintenance = None
        if self._pset is not None:
            self._pset.close()
        if self._batcher is not None:
            self._batcher.close()
            self.profiler.unwatch_gc()
            # telemetry survives the thread: metrics() after close still
            # reports what the batcher did
            self._batch_totals = (self._batcher.batches,
                                  self._batcher.batched)
            self._batcher = None
        if self._log is not None:
            self._log.write(self.metrics())

    def __enter__(self) -> "SearchService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def metrics(self) -> Dict:
        """Serving counters + the per-stage breakdown, metrics-log ready."""
        total = self.cache_hits + self.cache_misses
        view = self._view
        rec = {
            "serve_degraded": self.degraded,
            "serve_cache_hits": self.cache_hits,
            "serve_cache_misses": self.cache_misses,
            "serve_cache_hit_rate": round(self.cache_hits / total, 4)
            if total else 0.0,
            # live-update state (docs/UPDATES.md): which store/index
            # generation this service is answering from, and how it got
            # there — always present so dashboards can alert on drift
            "store_generation": view.generation,
            "index_generation": (view.index.index_generation
                                 if view.index is not None else None),
            "docs_appended": view.docs_appended,
            "tombstoned": view.tombstoned,
            "refreshes": self.refreshes,
            "incremental_updates": self.incremental_updates,
            "full_rebuilds": self.full_rebuilds,
            # tombstone-aware restage policy (docs/UPDATES.md)
            "restage_skipped": self.restage_skipped,
            "restage_forced": self.restage_forced,
            # dead-byte accounting (docs/MAINTENANCE.md): what the
            # background compactor would reclaim from THIS view's chain
            "tombstone_density": view.maint_stats["tombstone_density"],
            "dead_rows": view.maint_stats["dead_rows"],
            "reclaimable_bytes": view.maint_stats["reclaimable_bytes"],
            # recompilation + adaptive-window state (docs/SERVING.md):
            # how many distinct compiled shapes this service has
            # dispatched, and the micro-batch window currently in force
            "serve_recompiles": self.recompiles,
            "serve_batch_window_ms": round(self.batch_window_ms, 3),
            **self._window_metrics(),
            **self.profiler.summary(prefix="serve_stage_"),
        }
        b = self._batcher
        batches, batched = ((b.batches, b.batched) if b is not None
                            else self._batch_totals)
        if batches:
            rec["serve_batches"] = batches
            rec["serve_mean_batch"] = round(batched / batches, 2)
        if self._pset is not None:
            # partitioned-serving topology + routing health
            # (docs/SCALING.md): per-partition/replica qps, p99, queue
            # depth, shed and degraded-serve counts — the loadtest report
            # and dashboards read this block as-is
            rec["serve_partitions"] = self._pset.partitions
            rec["serve_replicas"] = self._pset.replicas
            rec["replica_shed"] = self.replica_shed
            rec["partition_degraded"] = self.partition_degraded_serves
            rec["partitions"] = self._pset.stats()
        # over-the-wire serving block (docs/SERVING.md "Network front
        # end") — emitted ONLY when non-empty, so every pre-transport
        # consumer of this record (report-shape tests, dashboards, the
        # loadgen trial records that copy it) stays byte-stable on an
        # in-process service
        transport: Dict = {}
        if self.wire_bytes:
            transport["wire_bytes"] = self.wire_bytes
            if self.wire_raw_bytes > self.wire_bytes:
                # the wire-compression pair (docs/SERVING.md): what the
                # same traffic would have cost raw, and the live ratio
                transport["wire_raw_bytes"] = self.wire_raw_bytes
                transport["wire_compression_ratio"] = round(
                    self.wire_raw_bytes / self.wire_bytes, 3)
        if self.deadline_sheds:
            transport["deadline_sheds"] = self.deadline_sheds
        if self.hedge_fires:
            transport["hedge_fires"] = self.hedge_fires
        if self._fanout is not None:
            transport.update(self._fanout.stats())
        if transport:
            rec["transport"] = transport
        if self._rcache_cap > 0:
            # generation-keyed result cache (docs/SERVING.md "Result
            # cache") — emitted ONLY when the feature is on, so the
            # default record shape stays byte-stable
            rhits = self.result_cache_hits
            rmiss = self.result_cache_misses
            with self._rcache_lock:
                entries = len(self._rcache)
                rbytes = self._rcache_bytes
            rec["result_cache"] = {
                "hits": rhits, "misses": rmiss,
                "hit_rate": round(rhits / (rhits + rmiss), 4)
                if (rhits + rmiss) else 0.0,
                "entries": entries, "bytes": rbytes,
                "capacity": self._rcache_cap,
                "fleet": self._rcache_fleet,
            }
        if self._serve_index != "exact":
            # ANN counters + the active index config (the PR 3
            # cache-counter pattern: flat keys, always present when the
            # feature is on, so dashboards need no key-existence logic)
            rec["ann_lists_scanned"] = self.ann_lists_scanned
            rec["ann_candidates_reranked"] = self.ann_candidates_reranked
            rec["ann_fallbacks"] = self.ann_fallbacks
            # store payload bytes the ANN gather actually moved (codes +
            # rerank rows on a PQ index, stored-width rows otherwise) —
            # the bandwidth denominator behind ann_gather_mbytes_per_s
            rec["ann_gather_bytes"] = self.ann_gather_bytes
            rec["ann_index"] = {
                "index": self._serve_index, "nprobe": self._nprobe,
                "nlist": self._index.nlist if self._index else None,
                "available": self._index is not None,
                "pq_m": self._index.pq_m if self._index else 0,
                "hot_rows": self._index.hot_rows if self._index else 0,
                **({"error": self._index_error}
                   if self._index_error else {})}
        if self.fault_counters:
            rec["fault_counters"] = faults.counters()
        return rec

    def _window_metrics(self) -> Dict[str, float]:
        """The live windowed view (docs/OBSERVABILITY.md): rates and tail
        latency over the last obs.window_s seconds, not since boot — the
        "qps @ p99 < X ms" SLO pair reads straight off these."""
        req_w = self._m_requests.window_count()
        err_w = self._m_errors.window_count()
        hit_w = self._m_cache_hits.window_count()
        miss_w = self._m_cache_misses.window_count()
        lat = self._m_latency
        return {
            "serve_window_s": self._window_s,
            "serve_window_qps": round(self._m_requests.rate(), 3),
            "serve_window_error_rate": round(
                err_w / (req_w + err_w), 4) if (req_w + err_w) else 0.0,
            "serve_window_cache_hit_rate": round(
                hit_w / (hit_w + miss_w), 4) if (hit_w + miss_w) else 0.0,
            "serve_window_p50_ms": round(lat.window_percentile(50), 3),
            "serve_window_p99_ms": round(lat.window_percentile(99), 3),
            "serve_window_queue_wait_p99_ms": round(
                self._m_queue_wait.window_percentile(99), 3),
        }

    def autoscale_signals(self) -> Dict[str, float]:
        """The two windowed pressure signals the maintenance autoscale
        pillar ladders on (docs/SCALING.md "Scale-out tier"): queue-wait
        p99 over the telemetry window — requests stacking faster than
        dispatches drain — and the deadline-shed rate — admission
        already refusing work. Both read the SAME instruments the
        adaptive batcher and the admission door feed, so the policy
        sees exactly what the serving path saw."""
        return {
            "queue_wait_p99_ms": round(
                self._m_queue_wait.window_percentile(99), 3),
            "queue_wait_samples": float(self._m_queue_wait.window_count()),
            "shed_rate": round(self._m_deadline_shed.rate(), 4),
            "window_s": self._window_s,
        }

    # -- exposition (docs/OBSERVABILITY.md) --------------------------------
    def metrics_snapshot(self) -> Dict:
        """JSON snapshot endpoint: the flat metrics() record plus the full
        registry view (typed instruments, windowed stats, the lifecycle
        event ring). Everything json-serializable — served by
        `cli serve-metrics --json` and the `:metrics` control line."""
        return {"metrics": self.metrics(), **self.registry.snapshot()}

    def prometheus_text(self) -> str:
        """Prometheus text exposition of the service registry — served by
        `cli serve-metrics`; one scrape of this is the dashboard feed."""
        return self.registry.prometheus_text()

    # -- search ------------------------------------------------------------
    def warmup(self, k: Optional[int] = None, timing_iters: int = 3) -> None:
        """Compile the encode + top-k programs before the first query, then
        time `timing_iters` warm searches (MEDIAN, so one GC pause
        can't skew the reported number; results are fully
        materialized to host, so the clock covers tokenize + encode +
        top-k + snippet end-to-end) into `warm_latency_ms`. The cache is
        bypassed while timing — warm latency means the real encode path,
        not a dictionary lookup. Pass the SAME k the queries will use —
        the top-k program cache is keyed on it, so a different k would
        leave the real program cold."""
        self.search_many(["warmup"], k=k)
        lat = LatencyStats()
        cap, self._cache_cap = self._cache_cap, 0
        rcap, self._rcache_cap = self._rcache_cap, 0
        try:
            for _ in range(max(1, timing_iters)):
                with lat.timed():
                    self.search_many(["warmup"], k=k)
        finally:
            self._cache_cap = cap
            self._rcache_cap = rcap
        self.warm_latency_ms = lat.percentile_ms(50)

    def search(self, query: str, k: Optional[int] = None,
               nprobe: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               deadline: Optional[float] = None,
               filters=None) -> List[Dict]:
        """One query -> top-k results. With the micro-batcher running
        (start_batcher), the call enqueues and blocks on its future —
        concurrent callers share dispatches; otherwise it is a direct
        single-query search_many. Either way the request is traced
        (obs.enabled) and lands in the windowed latency/qps instruments:
        the batched path's trace follows the request THROUGH the
        dispatcher thread (queue_wait + the adopted shared dispatch).
        `nprobe` overrides serve.nprobe for this request on an IVF
        service (the batcher coalesces per distinct (k, nprobe)).

        `deadline_ms` is this request's RELATIVE latency budget (None =
        the serve.deadline_ms default; <= 0 disables); `deadline` is an
        ABSOLUTE deadline on the service clock, already anchored — the
        network front end resolves each request's budget at frame
        receipt and passes it through here, so a request that aged out
        between the socket and this thread is ALREADY expired at
        admission. A request that cannot make its deadline is shed at
        admission — or at the micro-batch door if it expires while
        queued — with DeadlineExceeded; sheds count in
        serve.deadline_shed, never in serve.errors (docs/SERVING.md
        "Network front end").

        `filters` restricts results to rows whose packed attribute word
        satisfies the predicate (text or compiled, index/attrs.py,
        docs/ANN.md "Filtered retrieval"): the canonical form keys the
        cache and the batcher's coalescing group, the IVF path
        intersects it with the posting gather BEFORE ADC scoring, and
        the exact fallback scans only matching rows. A malformed
        predicate raises FilterError (a ValueError) before admission."""
        pred = _compile_filters(filters)
        if deadline is None:
            deadline = self.default_deadline(deadline_ms)
        # result-cache probe at the admission door (docs/SERVING.md
        # "Result cache"): a repeat answers BEFORE admission, so a hit
        # can never be shed and never consumes a micro-batch bucket
        # slot — the generation-qualified key makes a stale hit
        # impossible, not merely unlikely
        rkey = self._result_cache_key(query, k, nprobe, filters=pred)
        if rkey is not None:
            t0 = time.perf_counter()
            hits = self._result_cache_get(rkey, count=False)
            if hits is None:
                hits = self._peer_lookup(rkey)
            if hits is not None:
                self._m_rcache_hits.inc()
                self._m_requests.inc()
                self._m_latency.observe(
                    (time.perf_counter() - t0) * 1000.0)
                return hits
            self._m_rcache_misses.inc()
        # admission happens BEFORE the queue: a shed request never
        # consumes queue capacity or a bucket slot (raises out of here)
        self._admit(deadline)
        b = self._batcher
        if b is None:
            return self.search_many([query], k=k, nprobe=nprobe,
                                    filters=pred, deadline=deadline,
                                    _probe_cache=False)[0]
        t0 = time.perf_counter()
        try:
            with self.tracer.trace("search",
                                   k=k or self.cfg.eval.recall_k,
                                   query=self._normalize(query)[:80]):
                res = b.submit(query, k, nprobe, deadline=deadline,
                               filters=pred.text if pred is not None
                               else None).result()
        except DeadlineExceeded:
            # the micro-batch door shed it (expired while queued): a
            # deliberate availability decision, already counted in
            # serve.deadline_shed — not a serving error
            raise
        except BaseException:
            self._m_errors.inc()
            raise
        self._m_requests.inc()
        self._m_latency.observe((time.perf_counter() - t0) * 1000.0)
        return res

    def search_many(self, queries: Sequence[str], k: Optional[int] = None,
                    nprobe: Optional[int] = None, filters=None,
                    *, _record: bool = True, _probe_cache: bool = True,
                    deadline: Optional[float] = None) -> List[List[Dict]]:
        """Vectorized multi-query search: one result list per query, in
        order. Queries fill the compiled `query_batch` bucket (larger lists
        tile over full buckets — one compiled program regardless of count);
        the per-shard top-k, which carries the cross-shard merge, runs once
        per bucket, and on a degraded service the failed shards' disk sweep
        folds in once per bucket too.

        Telemetry: the call runs under a request trace (a fresh root for
        direct callers, a child span inside a batcher dispatch) and — for
        direct callers (`_record`) — counts every query into the windowed
        request/error/latency instruments; the batcher records per-request
        numbers itself so coalesced queries are never double-counted.
        `filters` applies ONE attribute predicate (text or compiled,
        index/attrs.py) to the whole batch — per-query predicates arrive
        as separate calls (the batcher coalesces per predicate)."""
        k = k or self.cfg.eval.recall_k
        n = len(queries)
        if n == 0:
            return []
        pred = _compile_filters(filters)
        # result-cache shortcut for direct callers (`_record` — batcher
        # dispatches and search()'s delegated misses skip the re-probe):
        # an ALL-hit batch answers without embedding or scanning anything;
        # a partial batch recomputes whole (one dispatch either way) and
        # only the true misses count as misses
        if _record and _probe_cache and self._rcache_cap > 0:
            t0 = time.perf_counter()
            cached = [self._result_cache_get(
                self._result_cache_key(q, k, nprobe, filters=pred),
                count=False) for q in queries]
            miss_n = sum(1 for c in cached if c is None)
            if miss_n == 0:
                self._m_rcache_hits.inc(n)
                self._m_requests.inc(n)
                self._m_latency.observe(
                    (time.perf_counter() - t0) * 1000.0, n=n)
                return cached
            self._m_rcache_misses.inc(miss_n)
        # ONE view for the whole call (docs/UPDATES.md): a refresh() swap
        # mid-call cannot mix generations inside a result set — this
        # dispatch finishes on the view it captured, the next one sees the
        # new view
        view = self._view
        t0 = time.perf_counter()
        try:
            with self.tracer.root_or_span("search_many", n_queries=n, k=k):
                out = self._search_view(view, list(queries), n, k, nprobe,
                                        deadline=deadline, predicate=pred)
        except BaseException:
            if _record:
                self._m_errors.inc(n)
            raise
        if _record:
            self._m_requests.inc(n)
            self._m_latency.observe((time.perf_counter() - t0) * 1000.0,
                                    n=n)
        return out

    def _search_view(self, view: "_ServeView", queries: List[str],
                     n: int, k: int,
                     nprobe: Optional[int] = None,
                     deadline: Optional[float] = None,
                     predicate=None) -> List[List[Dict]]:
        if predicate is not None:
            # one event per filtered dispatch (docs/OBSERVABILITY.md):
            # which predicate ran, how many queries rode it
            self.registry.event("filtered_query", {
                "predicate": predicate.text[:200], "n_queries": n})
        # mid-migration the view serves two stamps: encode the batch once
        # per stamp (stacked [n, S*D]) so every shard can be scored by the
        # tower matching its recorded model step; the stacked matrix ships
        # over the scatter paths unchanged (VQUERY frames carry a dynamic
        # dim) and each receiver splits it against ITS view's stamp list.
        # The kwarg only appears when the view's stamp table disagrees
        # with the serving model step — two stamps mid-sweep, or one
        # stamp that isn't the manifest's (a crash landed between the
        # last unit flip and complete()'s stamp flip): model-free tests
        # swap in single-argument embed stubs on the common path.
        qv = (self._embed_queries_cached(queries, steps=view.steps)
              if len(view.steps) > 1
              or (view.steps and view.steps[0] != view.store.model_step)
              else self._embed_queries_cached(queries))
        fanout = self._fanout
        if fanout is not None and fanout.active():
            # over-the-wire scatter (infer/partition_host.py): the RPC
            # fan-out to registered partition workers, with per-partition
            # deadlines, hedged requests, and a per-partition LOCAL
            # fallback that keeps results byte-identical when a worker
            # dies mid-request
            best_s, best_i = fanout.topk(qv, n, k, nprobe,
                                         deadline=deadline,
                                         predicate=predicate)
        elif self._pset is not None:
            # partitioned scatter-gather (infer/partition.py): the
            # coalesced bucket's query matrix broadcasts ONCE to every
            # partition; each answers its local top-k over only its shard
            # range, results fold through the partition merge tree
            best_s, best_i = self._pset.topk(qv, n, k, nprobe,
                                             predicate=predicate)
        else:
            best_s, best_i, _ = self._topk_view(view, qv, n, k, nprobe,
                                                predicate=predicate)
        with self._stage("format"):
            out = [self._format(best_s[i], best_i[i]) for i in range(n)]
        if self._rcache_cap > 0:
            # fill keyed against the CAPTURED view's generations: a
            # refresh that swapped mid-compute files this result under
            # the old (now unreachable) key, so a stale fill can never
            # answer a post-swap probe — staleness-zero by construction
            for q, hits in zip(queries, out):
                key = self._result_cache_key(q, k, nprobe, view=view,
                                             filters=predicate)
                self._result_cache_put(key, hits)
                self._peer_put(key, hits)
        return out

    def topk_vectors(self, qv: np.ndarray, k: Optional[int] = None,
                     nprobe: Optional[int] = None,
                     deadline: Optional[float] = None,
                     filters=None) -> tuple:
        """Raw retrieval for PRE-COMPUTED query vectors: (scores [n, k]
        fp32, page_ids [n, k] int64, -1-padded), skipping tokenize/encode
        and snippet formatting. `PartitionSet.simulate`'s host-simulated
        scatter, the network front end's vector protocol, and vector-level
        tests drive the full serving top-k (RPC fan-out, partitioned, or
        single-view) through this without a model."""
        k = k or self.cfg.eval.recall_k
        pred = _compile_filters(filters)
        qv = np.asarray(qv, np.float32)
        n = qv.shape[0]
        fanout = self._fanout
        if fanout is not None and fanout.active():
            return fanout.topk(qv, n, k, nprobe, deadline=deadline,
                               predicate=pred)
        if self._pset is not None:
            return self._pset.topk(qv, n, k, nprobe, predicate=pred)
        s, i, _ = self._topk_view(self._view, qv, n, k, nprobe,
                                  predicate=pred)
        return s, i

    def _topk_view(self, view: "_ServeView", qv: np.ndarray, n: int, k: int,
                   nprobe: Optional[int] = None, predicate=None):
        """Raw top-k of `n` real query rows of `qv` over ONE view:
        (scores [n, k] fp32, page_ids [n, k] int64, scan_bytes). This is
        the per-partition unit of work of the scatter-gather — a
        partition worker runs it over its own restricted view — and the
        whole retrieval of the single-view path. `scan_bytes` is the
        candidate payload this view scanned to answer: the ANN gather
        bytes, or the view's full row bytes on the exact path — the
        per-partition critical-path byte count `PartitionSet.simulate`
        reports (drops ~1/P under partitioning)."""
        qv = np.asarray(qv, np.float32)
        blocks = self._qv_blocks(view, qv)
        if self._serve_index == "ivf":
            # a mixed-stamp view never consults the index (_ann_topk's
            # migration guard): each shard must be scored by its own
            # tower's block, which the exact path below routes per shard
            res = (self._ann_topk(view, next(iter(blocks.values())),
                                  n, k, nprobe, predicate=predicate)
                   if len(view.steps) <= 1 else None)
            if res is not None:
                return res
            # exact path serves this request; visible in metrics + counters
            self._m_ann_fallbacks.inc(n)
            faults.count("serve_ann_fallbacks", n)
        if predicate is not None:
            # filtered exact: host-mask each shard's attribute words and
            # scan only the matching rows — the resident HBM program and
            # the streaming sweep both score EVERY row, so neither can
            # honor the scan-bytes contract for a predicate
            return self._filtered_exact(view, blocks, n, k, predicate)
        B = self.query_batch
        row_bytes = view.store.row_bytes
        if view.shards is None:
            # streaming store: pad the query matrix to a bucket multiple so
            # every call reuses one compiled shape, then sweep disk ONCE
            # per stamp group (one group total outside a migration). The
            # sweep reads the VIEW's store handle — refresh() never mutates
            # it (it opens a fresh handle for the next view), so a swap
            # mid-sweep cannot mix generations, while an in-place store
            # mutation (ensure_model_step under a live service) still
            # propagates per request like it always did. A RESTRICTED
            # (partition) view sweeps its frozen entry subset instead —
            # its shard range is the ownership contract.
            groups: Dict = {}
            for e in view.entries:
                groups.setdefault(view.store.entry_step(e), []).append(e)
            scan = sum(e["count"] for e in view.entries) * row_bytes
            fallback = next(iter(blocks.values()))

            def _sweep(step, entries):
                qp = blocks.get(step, fallback)[:n]
                pad = (-n) % B
                if pad:
                    qp = np.concatenate(
                        [qp, np.zeros((pad, qp.shape[1]), np.float32)])
                self._note_dispatch_shape("topk_over_store", batch=B, k=k)
                return topk_over_store(
                    qp, view.store, self.embedder.mesh, k=k,
                    query_batch=B, entries=entries)

            if len(groups) <= 1:
                step = next(iter(groups)) if groups else None
                with self._stage("topk", path="streaming"):
                    scores, ids = _sweep(
                        step, view.entries if view.restricted else None)
                return scores[:n], ids[:n], scan
            out_s = np.full((n, k), -np.inf, np.float32)
            out_i = np.full((n, k), -1, np.int64)
            with self._stage("topk", path="streaming",
                             stamps=len(groups)):
                for step, entries in groups.items():
                    s_g, i_g = _sweep(step, entries)
                    out_s, out_i = _merge_topk_host(
                        out_s, out_i, np.asarray(s_g[:n], np.float32),
                        np.asarray(i_g[:n], np.int64), k)
            return out_s, out_i, scan
        # Two passes over the buckets: dispatch them ALL first (the last
        # scan's output stays on device — JAX's async queue runs bucket i+1's
        # top-k while bucket i's packed transfer drains), THEN materialize
        # in order. A >bucket batch therefore pipelines compute against
        # transfer instead of serializing dispatch/drain per bucket.
        pending = [(s, self._dispatch_bucket(
                        view, {st: blk[s: s + B]
                               for st, blk in blocks.items()}, k))
                   for s in range(0, n, B)]
        out_s = np.full((n, k), -np.inf, np.float32)
        out_i = np.full((n, k), -1, np.int64)
        for s0, (nreal, qs, packed) in pending:
            bs, bi = self._collect_bucket(view, nreal, qs, packed, k)
            out_s[s0: s0 + nreal] = bs[:nreal]
            out_i[s0: s0 + nreal] = bi[:nreal]
        scan = (sum(shard.n for shard in view.shards)
                + sum(e["count"] for e in view.stream_entries)) * row_bytes
        return out_s, out_i, scan

    def _filtered_exact(self, view: "_ServeView", blocks: Dict, n: int,
                        k: int, predicate) -> tuple:
        """Exact filtered retrieval over ONE view: per shard, evaluate
        the predicate against the packed attribute words on host, gather
        ONLY the matching rows, and fold their exact scores into the
        running top-k (docs/ANN.md "Filtered retrieval"). Every topology
        — local, partitioned scatter, socket fan-out — answers a
        filtered exact query through this method over its own frozen
        entry subset, and the stable host merge makes the folded result
        byte-identical to the single-process filtered oracle, the same
        contract the unfiltered exact path pins.

        `scan_bytes` counts the attribute words read (4 B/row over the
        view) plus the matching rows' stored payload: a predicate of
        selectivity s scans ~s× the unfiltered exact bytes — the number
        tests/test_filtered.py holds to <=0.3x at selectivity 0.1."""
        row_bytes = view.store.row_bytes
        fallback = next(iter(blocks.values()))
        out_s = np.full((n, k), -np.inf, np.float32)
        out_i = np.full((n, k), -1, np.int64)
        scan = 0
        with self._stage("topk", path="filtered_exact"):
            for entry in view.entries:
                if entry["count"] == 0:
                    continue
                words = view.store.load_attrs(entry)
                scan += int(words.nbytes)
                keep = predicate.matches(words)
                if not keep.any():
                    continue
                ids, vecs = view.store._load_entry(entry)
                ids = ids[keep]
                live = ids >= 0      # tombstones match nothing
                if not live.any():
                    continue
                rows = np.asarray(np.asarray(vecs)[keep][live], np.float32)
                ids = ids[live]
                scan += int(rows.shape[0]) * row_bytes
                qp = np.asarray(
                    blocks.get(view.store.entry_step(entry), fallback)[:n],
                    np.float32)
                scores = qp @ rows.T
                kk = min(k, scores.shape[1])
                part = np.argpartition(-scores, kk - 1,
                                       axis=1)[:, :kk]
                out_s, out_i = _merge_topk_host(
                    out_s, out_i,
                    np.take_along_axis(scores, part, axis=1)
                    .astype(np.float32),
                    ids[part].astype(np.int64), k)
        return out_s, out_i, scan

    def _qv_blocks(self, view: "_ServeView",
                   qv: np.ndarray) -> Dict:
        """Split a query matrix into per-stamp [n, D] blocks keyed by
        model step, ascending — the inverse of the stacked encode in
        _embed_queries_cached (docs/MAINTENANCE.md "Rolling model
        migration"). Handles the two transient skews a rolling fleet
        walk-through can produce:

          * WIDE matrix onto a single-stamp view (a dual-stamp front end
            scattering to a receiver whose store handle hasn't caught the
            migration record yet, or already passed the completion flip):
            pick this view's block by the migration record's
            ascending-stamp order, else the LAST block — completion skew
            is the common case and the target stamp stacks last;
          * NARROW matrix onto a mixed view (an encoder predating the
            record): score every shard with the one block — old-stamp
            shards exactly, new-stamp shards approximately, for the one
            refresh round it takes the caller to catch up (counted as
            `serve_stamp_skew`)."""
        D = int(view.store.dim)
        w = int(qv.shape[1])
        steps = view.steps
        if len(steps) <= 1:
            step = steps[0] if steps else None
            if w <= D:
                return {step: qv}
            nb = w // D
            mig = view.store.migration or {}
            order = sorted({int(s) for s in (mig.get("from_step"),
                                             mig.get("to_step"))
                            if s is not None})
            pos = (order.index(step)
                   if step in order and order.index(step) < nb
                   else nb - 1)
            return {step: qv[:, pos * D:(pos + 1) * D]}
        if w <= D:
            self._count_fault("serve_stamp_skew")
            return {s: qv for s in steps}
        nb = w // D
        return {s: qv[:, min(i, nb - 1) * D: (min(i, nb - 1) + 1) * D]
                for i, s in enumerate(steps)}

    # graftcheck: hot
    def _dispatch_bucket(self, view: "_ServeView", qblocks: Dict, k: int):
        """HBM-resident fast path for ONE compiled bucket (<= query_batch
        real rows): the bucket's running top-k, one packed [B, 2k] array
        (ops/topk.py:pack_topk, ids in the view's combined numbering), is
        threaded through one launch of the scan per resident shard, in
        slot order, under JAX's async queue. The last launch's output IS
        the bucket's result and is returned still on device — exactly ONE
        drain round trip per BUCKET happens later in _collect_bucket,
        regardless of shard count or how many queries share the dispatch.

        `qblocks` maps model stamp -> [<=B, D] query block (_qv_blocks):
        each shard is scored by the block matching its recorded stamp, so
        a mid-migration bucket runs the same one chain — the dual-stamp
        routing costs one extra h2d put per extra stamp, not a second
        sweep.

        The launch loop holds nothing but the launches: the jitted scan is
        resolved once per bucket, and each shard's arguments were made
        when the view was staged (_stage_view). The query blocks and the
        empty carry go up by one explicit put; after that a bucket moves
        nothing from host to device and runs one program per shard and no
        other. The scan DONATES the carry: each launch writes its one
        output into the buffer the last one left, the runtime allocates
        nothing a launch, and only the newest carry may be touched."""
        import jax

        nreal = next(iter(qblocks.values())).shape[0]
        B = self.query_batch
        # ONE explicit put: the blocks (float32 from _topk_view) zero-padded
        # to the bucket and the empty carry beside them; a put of its own
        # for the carry cost 0.15-0.2 ms a bucket
        *blocks, packed = jax.device_put(
            [np.pad(blk, ((0, B - blk.shape[0]), (0, 0)))
             for blk in qblocks.values()] + [empty_topk(B, k)],
            view.shards[0].span.sharding)
        qs: Dict = dict(zip(qblocks, blocks))
        fallback = blocks[0]
        self._note_dispatch_shape("sharded_topk", batch=B, k=k,
                                  rows=view.pad_rows,
                                  shards=len(view.shards))
        with self._stage("topk", shards=len(view.shards)):
            # a store has one dtype: every shard of a view has scales, or
            # none has
            scaled = view.shards[0].scales is not None
            scan = sharded_topk_fn(self.embedder.mesh, k, scaled=scaled)
            if scaled:
                for st, (_, _, pages, scl, span) in zip(view.shard_steps,
                                                        view.shards):
                    packed = scan(qs.get(st, fallback), pages, scl, span,
                                  packed)
            else:
                for st, (_, _, pages, _, span) in zip(view.shard_steps,
                                                      view.shards):
                    packed = scan(qs.get(st, fallback), pages, span, packed)
        return nreal, qs, packed

    # graftcheck: hot
    def _collect_bucket(self, view: "_ServeView", nreal: int, qs, packed,
                        k: int):
        """Drain one dispatched bucket to host (scores [nreal, k] fp32,
        page_ids [nreal, k] int64) — formatting happens once per call in
        _search_view, so the partitioned scatter-gather can fold raw
        per-partition candidates before any snippet work. Stage `merge` is
        the pull alone: the cross-shard merge happened in the launches."""
        with self._stage("merge"):
            # graftcheck: off=host-sync -- THE one packed d2h per
            # bucket: the whole point of the carried [B, 2k] layout
            packed = np.asarray(packed)
        (self._m_tail if view.stream_entries else self._m_carried).inc()
        if scans_in_kernel(view.shards[0].pages.dtype, k):
            self._m_kernel.inc()
        top_s, top_i = unpack_topk(packed)
        pids = np.where(top_i >= 0,
                        view.pid_table[np.clip(top_i, 0, None)], -1)
        best_s = np.where(np.isfinite(top_s), top_s, -np.inf).astype(
            np.float32)
        best_i = pids.astype(np.int64)
        if not view.stream_entries:
            return best_s[:nreal], best_i[:nreal]
        # degraded tail: shards that failed to stage are re-read from disk
        # — ONCE for the whole bucket, prefetched one shard ahead on a
        # reader thread — and folded into the resident results through the
        # same merge_shard_topk the streaming path uses: identical results,
        # per-bucket disk reads for exactly the failed shards

        def _load_tail():
            for entry in view.stream_entries:
                ids, vecs, scl = view.store._load_entry(entry, raw=True)
                # graftcheck: off=host-sync -- mmap'd host arrays
                # (degraded tail reads disk, no device involved)
                yield np.asarray(ids, np.int64), np.asarray(vecs), scl

        fallback = next(iter(qs.values()))
        with self._stage("topk", path="degraded_tail",
                         shards=len(view.stream_entries)):
            tail = read_ahead(_load_tail(), depth=1)
            for entry, (ids, vecs, scl) in zip(view.stream_entries, tail):
                nrows = vecs.shape[0]
                if nrows == 0:
                    continue
                pages, scales = stage_shard(vecs, view.pad_rows,
                                            view.store.dim,
                                            self.embedder.mesh, scales=scl,
                                            words=True)
                # the degraded tail routes by stamp too: a failed-to-stage
                # shard still scores against its own tower's block
                q_e = qs.get(view.store.entry_step(entry), fallback)
                best_s, best_i = merge_shard_topk(
                    q_e, pages, ids, nrows, self.embedder.mesh, k,
                    best_s, best_i, scales=scales)
        return best_s[:nreal], best_i[:nreal]

    def _format(self, scores, ids) -> List[Dict]:
        return [
            {"page_id": int(i), "score": round(float(s), 4),
             "snippet": self.corpus.page_text(int(i))[: self.snippet_chars]}
            for s, i in zip(scores, ids) if i >= 0]
