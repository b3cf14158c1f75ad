"""Partitioned + replicated serving (docs/SCALING.md "Partitioned
serving").

The ROADMAP's "millions of users, 1B pages" north star needs serving to
scale *out*, and until this module every layer assumed one process owned
the whole corpus. The abstraction here is deliberately thin:

  * `PartitionSpec` — one partition's ownership contract: a CONTIGUOUS
    shard range (computed by `parallel/multihost.py:
    partition_shard_ranges`, balanced by row count), which implies its
    slice of the IVF posting files (`index/ivf.py:partition_view`) and
    its proportional cut of the `serve.hot_postings_gb` HBM hot set.
    Contiguity keeps a partition's page-id space an interval, so in a
    real multi-host deployment each host's shard files, posting files,
    and append ranges stay disjoint and the existing per-writer append
    leases (maintenance/lease.py) give mutual exclusion unchanged.
  * `_PartitionReplica` — one host-simulated worker: a thread draining a
    task queue, owning an independent `_ServeView` over the spec's
    entries. The view swap is the same single-reference-assignment
    hot-swap the single-view path uses (docs/UPDATES.md).
  * `PartitionSet` — P specs x R replicas plus the router. `topk()` is
    the scatter-gather: the (already encoded) query matrix broadcasts
    once to one routed replica per partition, each answers its local
    top-k via `SearchService._topk_view` over only its shard range — so
    per-query scan bytes drop ~1/P and partitions run concurrently — and
    the per-partition winners fold through
    `ops/topk.py:merge_partition_topk` (a balanced merge tree with
    `merge_topk_host` as the fold).

Health-based routing: the router prefers the first replica that is not
mid-restage, not degraded (staging failures pushed its shards onto the
streaming disk path), and under `serve.replica_shed_queue` requests in
flight. Leaving the primary counts `serve.replica_shed` and emits a
`replica_shed` event (on state transitions, not per request); when every
replica of a partition is degraded the least-bad one still serves —
degraded, visibly (`serve.partition_degraded`, `partition_degraded`
event) — never an empty slice of results.

Per-partition refresh: `refresh()` builds every partition's next view
BESIDE the serving table, partition by partition — while one replica
restages, its router sheds to a sibling (or, with R=1, the old view
keeps serving), and every OTHER partition is untouched — then publishes
the finished table with ONE reference assignment. A scatter snapshots
the table once, so a result set can never mix store generations across
partitions: the PR-5 no-mixed-result-sets pin, extended to P views.
Background maintenance (docs/MAINTENANCE.md) composes for free:
compaction and off-path rebuilds land through `SearchService.refresh()`,
which is this build-beside-then-publish swap.

Host simulation vs production: a replica worker thread stands in for one
serving host. On a multi-core host the scatter is real parallelism (the
scan work runs under released-GIL device/numpy calls); on the 1-core
build sandbox wall-clock threads cannot show multi-host scaling, so
`simulate()` accounts for it instead (tests/test_partition.py) — sequential
per-partition execution with critical-path accounting (simulated latency
= max over partitions + the measured merge fold), the honest
one-box simulation of P independent hosts.
"""
from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dnn_page_vectors_tpu.ops.topk import merge_partition_topk
from dnn_page_vectors_tpu.parallel.multihost import partition_shard_ranges
from dnn_page_vectors_tpu.utils.profiling import LatencyStats


@dataclasses.dataclass(frozen=True)
class PartitionSpec:
    """One partition's ownership contract: its contiguous slice of the
    store's shard table (entry dicts frozen at spec time), the shard
    indices that slice implies for the IVF posting files, its live row
    count, and its cut of the hot-posting HBM budget (proportional to
    rows, so a lopsided split doesn't starve the big partition)."""

    pid: int
    entries: Tuple[Dict, ...]
    shard_indices: Tuple[int, ...]
    rows: int
    hot_gb: float


def make_partition_specs(entries: Sequence[Dict], partitions: int,
                         hot_gb: float = 0.0) -> List[PartitionSpec]:
    """Split a shard table into at most `partitions` contiguous,
    row-balanced PartitionSpecs (deterministic: pure arithmetic over the
    table, so every worker/host derives the identical split)."""
    entries = list(entries)
    total = sum(e["count"] for e in entries) or 1
    ranges = partition_shard_ranges([e["count"] for e in entries],
                                    partitions)
    specs = []
    for pid, (lo, hi) in enumerate(ranges):
        part = entries[lo:hi]
        rows = sum(e["count"] for e in part)
        specs.append(PartitionSpec(
            pid=pid, entries=tuple(part),
            shard_indices=tuple(e["index"] for e in part),
            rows=rows, hot_gb=hot_gb * rows / total))
    return specs


class _PartitionReplica:
    """One host-simulated partition worker: a task-queue thread owning an
    independent `_ServeView` over its spec's shard range. Health state
    (restaging flag, queue depth, per-replica stats) is lock-guarded; the
    view itself follows the `_ServeView` swap idiom — replaced by one
    reference assignment, snapshot-read by tasks in flight."""

    _STOP = object()

    def __init__(self, spec: PartitionSpec, rid: int):
        self.spec = spec
        self.rid = rid
        self.view = None                  # _ServeView; swapped by refresh
        self._lock = threading.Lock()
        self._q: "queue_mod.Queue[object]" = queue_mod.Queue()
        self._outstanding = 0             # guarded-by: _lock
        self._restaging = False           # guarded-by: _lock
        self.requests = 0                 # guarded-by: _lock
        self.scan_bytes = 0               # guarded-by: _lock
        self.lat = LatencyStats()         # guarded-by: _lock
        # the worker thread handle itself is only touched by the owner
        # (start here, join in close) — no lock
        self._t = threading.Thread(
            target=self._run, daemon=True,
            name=f"serve-part{spec.pid}r{rid}")
        self._t.start()

    # -- health ------------------------------------------------------------
    @property
    def restaging(self) -> bool:
        with self._lock:
            return self._restaging

    def set_restaging(self, flag: bool) -> None:
        with self._lock:
            self._restaging = bool(flag)

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return self._outstanding

    @property
    def degraded(self) -> bool:
        """Staging failures pushed shards onto the streaming disk path
        (or no view yet): this replica answers, but slowly — routing
        prefers a healthy sibling."""
        view = self.view
        return view is None or bool(view.stream_entries)

    # -- work --------------------------------------------------------------
    def submit(self, fn) -> Future:
        fut: Future = Future()
        with self._lock:
            self._outstanding += 1
        self._q.put((fn, fut))
        return fut

    def run_inline(self, fn):
        """Execute one task ON THE CALLER (`simulate()`'s host-simulation
        mode): returns (result, seconds). Sequential execution keeps the
        per-partition timing free of same-core thread contention — the
        measured seconds are one simulated host's critical path."""
        t0 = time.perf_counter()
        res = fn()
        dt = time.perf_counter() - t0
        self._record(res, dt)
        return res, dt

    def _record(self, res, dt: float) -> None:
        with self._lock:
            self.requests += 1
            self.lat.add(dt)
            if isinstance(res, tuple) and len(res) == 3:
                self.scan_bytes += int(res[2])

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is self._STOP:
                return
            fn, fut = item
            t0 = time.perf_counter()
            try:
                res = fn()
            except BaseException as e:  # noqa: BLE001 — task errors ride
                fut.set_exception(e)    # the future back to the gather
                res = None
            else:
                fut.set_result(res)
            dt = time.perf_counter() - t0
            with self._lock:
                self._outstanding -= 1
            self._record(res, dt)

    def stats(self) -> Dict:
        with self._lock:
            return {
                "replica": self.rid,
                "requests": self.requests,
                "p50_ms": round(self.lat.percentile_ms(50), 3),
                "p99_ms": round(self.lat.percentile_ms(99), 3),
                "scan_bytes": self.scan_bytes,
                "queue": self._outstanding,
                "restaging": self._restaging,
                "degraded": self.degraded,
            }

    def close(self) -> None:
        self._q.put(self._STOP)
        self._t.join()


class PartitionSet:
    """P partitions x R replicas behind one scatter-gather router."""

    def __init__(self, svc, store, partitions: int, replicas: int,
                 shed_queue: int = 8):
        self._svc = svc
        self._shed_queue = max(0, int(shed_queue))
        specs = make_partition_specs(store.shards(), partitions,
                                     hot_gb=svc._hot_gb)
        self.partitions = len(specs)
        self.replicas = max(1, int(replicas))
        # the replica grid only ever GROWS (resize never removes rows):
        # an in-flight scatter that captured a wider table keeps routing
        # into the tail rows until it finishes — rows beyond
        # self.partitions are simply never routed by new scatters
        self._parts: List[List[_PartitionReplica]] = []
        table: List[tuple] = []
        for spec in specs:
            reps, row = [], []
            for rid in range(self.replicas):
                rep = _PartitionReplica(spec, rid)
                # each replica stages an INDEPENDENT view (its own device
                # arrays, its own restricted index) — the host simulation
                # of R copies on R hosts
                rep.view = svc._build_view(store, entries=list(spec.entries),
                                           hot_gb=spec.hot_gb)
                reps.append(rep)
                row.append(rep.view)
            self._parts.append(reps)
            table.append(tuple(row))
        # THE generation-consistency anchor: every scatter snapshots this
        # table once, and refresh() publishes a fully-built replacement
        # with one reference assignment — so one query's result set can
        # never mix store generations ACROSS partitions (the PR-5
        # no-mixed-result-sets pin, extended to P views)
        self._view_table = tuple(table)
        self._route_lock = threading.Lock()
        self._sheds = [0] * self.partitions        # guarded-by: _route_lock
        self._degraded_serves = [0] * self.partitions  # guarded-by: _route_lock
        self._last_health: Dict[int, tuple] = {}   # guarded-by: _route_lock
        # liveness oracle (docs/SERVING.md "Network front end"): when a
        # WorkerGateway is attached, (pid, rid) -> is that replica's
        # partition worker alive (registered + heartbeating)? None = the
        # in-process default, every replica counts as live. Swapped by
        # one reference assignment (set_liveness), snapshot-read per call.
        self._liveness = None
        # creation timestamp: written once here, read-only afterwards
        self._t0 = time.perf_counter()

    def primary_view(self):
        """Partition 0's primary view — the service's control view (its
        store-level fields are identical on every view)."""
        return self._parts[0][0].view

    def specs(self) -> List[PartitionSpec]:
        return [reps[0].spec for reps in self._parts]

    # -- routing -----------------------------------------------------------
    def set_liveness(self, fn) -> None:
        """Install (or clear, with None) the worker-liveness oracle:
        `fn(pid, rid) -> bool`. With a gateway attached, routing health
        derives from worker liveness (registration + heartbeats) on top
        of the in-process flags (docs/SERVING.md "Network front end")."""
        self._liveness = fn

    def _alive(self, pid: int, rid: int) -> bool:
        fn = self._liveness
        return True if fn is None else bool(fn(pid, rid))

    def _route(self, pid: int) -> _PartitionReplica:
        """Pick the replica that answers partition `pid`'s next request.
        Preference order: live + healthy (worker heartbeating, serving
        its HBM view, not restaging, under the queue budget) >
        live-but-over-budget > healthy-with-a-dead-worker (serves its
        LOCAL view — the gateway's fallback) > degraded. Leaving the
        primary is a shed (counted; `replica_shed` event on transitions,
        reason restaging/degraded/liveness/queue); serving on a degraded
        replica because every sibling is degraded too is a
        `partition_degraded` — the never-empty fallback the availability
        contract demands."""
        reps = self._parts[pid]
        primary = reps[0]
        chosen = None
        degraded_serve = False
        for r in reps:
            if (not r.restaging and not r.degraded
                    and self._alive(pid, r.rid)
                    and r.queue_depth <= self._shed_queue):
                chosen = r
                break
        if chosen is None:
            for r in reps:
                if (not r.restaging and not r.degraded
                        and self._alive(pid, r.rid)):
                    chosen = r
                    break
        if chosen is None:
            # no replica has a LIVE worker: a healthy replica still
            # serves from its local view (the gateway falls back to
            # in-process compute) — healthy local serving is NOT a
            # degraded serve
            for r in reps:
                if not r.restaging and not r.degraded:
                    chosen = r
                    break
        if chosen is None:
            for r in reps:
                if not r.restaging:
                    chosen = r
                    degraded_serve = True
                    break
        if chosen is None:
            # every replica mid-restage: the primary's OLD view is still
            # valid (the swap is atomic) — serve on it
            chosen = primary
            degraded_serve = primary.degraded
        svc = self._svc
        shed = chosen is not primary
        reason = None
        if shed:
            reason = ("restaging" if primary.restaging
                      else "degraded" if primary.degraded
                      else "liveness" if not self._alive(pid, primary.rid)
                      else "queue")
            svc._m_replica_shed.inc()
        if degraded_serve:
            svc._m_partition_degraded.inc()
        state = (chosen.rid, reason, degraded_serve)
        with self._route_lock:
            if shed:
                self._sheds[pid] += 1
            if degraded_serve:
                self._degraded_serves[pid] += 1
            changed = self._last_health.get(pid) != state
            self._last_health[pid] = state
        if changed:
            # events fire on TRANSITIONS, not per request — the ring
            # records the routing change, counters carry the volume
            if shed:
                svc.registry.event("replica_shed", {
                    "partition": pid, "from_replica": primary.rid,
                    "to_replica": chosen.rid, "reason": reason})
            if degraded_serve:
                svc.registry.event("partition_degraded", {
                    "partition": pid, "replica": chosen.rid})
        return chosen

    # -- the scatter-gather ------------------------------------------------
    def topk(self, qv: np.ndarray, n: int, k: int,
             nprobe: Optional[int] = None, predicate=None
             ) -> Tuple[np.ndarray, np.ndarray]:
        """Scatter the (already encoded) query matrix to one routed
        replica per partition, gather each partition's local top-k, fold
        through the partition merge tree. Returns (scores [n, k] fp32,
        page_ids [n, k] int64). `predicate` (index/attrs.py) rides the
        scatter verbatim: each partition intersects it with its own scan
        and the merge fold is predicate-blind — filtered results stay
        byte-identical to the single-view filtered path."""
        svc = self._svc
        qv = np.asarray(qv, np.float32)
        # ONE table snapshot for the whole scatter: every partition
        # answers from the same published generation set, so a refresh
        # landing mid-scatter cannot mix generations across partitions.
        # The scatter WIDTH also derives from the snapshot (not from
        # self.partitions): an elastic resize() publishing mid-scatter
        # can therefore never mix partition splits inside one result set
        # — the PR-14 no-mixed-generations pin, extended to splits
        table = self._view_table
        with svc._stage("scatter", partitions=len(table)):
            futs = []
            for pid in range(len(table)):
                rep = self._route(pid)
                view = table[pid][rep.rid]
                futs.append(rep.submit(
                    lambda v=view: svc._topk_view(v, qv, n, k, nprobe,
                                                  predicate=predicate)))
            parts = [f.result() for f in futs]
        with svc._stage("merge"):
            return merge_partition_topk([(s, i) for s, i, _ in parts])

    def simulate(self, qv: np.ndarray, n: int, k: int,
                 nprobe: Optional[int] = None, predicate=None) -> Dict:
        """Host-simulation mode (one box standing in for P hosts): run
        every partition's task SEQUENTIALLY on the caller, timing each,
        then the merge fold. The simulated per-query latency is the
        critical path max(partition seconds) + merge seconds — what P
        independent hosts would deliver — with the per-partition scan
        bytes alongside. Returns {scores, ids, partition_seconds,
        merge_seconds, critical_path_seconds, scan_bytes}."""
        svc = self._svc
        qv = np.asarray(qv, np.float32)
        table = self._view_table
        parts, times, scans = [], [], []
        for pid in range(len(table)):
            rep = self._route(pid)
            view = table[pid][rep.rid]
            (res, dt) = rep.run_inline(
                lambda v=view: svc._topk_view(v, qv, n, k, nprobe,
                                              predicate=predicate))
            parts.append(res)
            times.append(dt)
            scans.append(int(res[2]))
        t0 = time.perf_counter()
        s, i = merge_partition_topk([(s_, i_) for s_, i_, _ in parts])
        merge_s = time.perf_counter() - t0
        return {
            "scores": s, "ids": i,
            "partition_seconds": times,
            "merge_seconds": merge_s,
            "critical_path_seconds": max(times) + merge_s,
            "scan_bytes": scans,
        }

    # -- rolling refresh (docs/UPDATES.md, per partition) ------------------
    def refresh(self, new_store, update_index: bool = False) -> List[Dict]:
        """Bring every replica onto `new_store`'s current generation:
        each partition's next views build BESIDE the serving table,
        partition by partition (the replica being restaged sheds — its
        router prefers a sibling — and every other partition keeps
        serving untouched: a compaction or off-path rebuild landing
        through here never blocks the fleet), the store-level IVF update
        runs exactly once on the first view built, and the finished table
        publishes with ONE reference assignment — a scatter snapshots the
        table, so no query ever mixes generations across partitions.
        Returns the per-partition restage record."""
        svc = self._svc
        specs = make_partition_specs(new_store.shards(), self.partitions,
                                     hot_gb=svc._hot_gb)
        # shard growth can change the balanced split width; a shrunken
        # table (quarantine) can yield fewer balanced ranges than live
        # partitions: the tail partitions get explicit EMPTY specs — they
        # serve nothing rather than a stale view
        while len(specs) < self.partitions:
            specs.append(PartitionSpec(pid=len(specs), entries=(),
                                       shard_indices=(), rows=0,
                                       hot_gb=0.0))
        out: List[Dict] = []
        first = True
        new_table: List[tuple] = []
        for pid, spec in enumerate(specs):
            reps = self._parts[pid]
            swaps = []
            row = []
            for rep in reps:
                t0 = time.perf_counter()
                rep.set_restaging(True)
                try:
                    row.append(svc._build_view(
                        new_store, reuse=rep.view,
                        update_index=update_index and first,
                        entries=list(spec.entries), hot_gb=spec.hot_gb))
                finally:
                    rep.set_restaging(False)
                first = False
                swaps.append(round((time.perf_counter() - t0) * 1000.0, 3))
            new_table.append(tuple(row))
            out.append({"partition": pid,
                        "shards": list(spec.shard_indices),
                        "rows": spec.rows,
                        "restage_ms": swaps})
        self._view_table = tuple(new_table)  # THE swap: one assignment
        for pid, row in enumerate(new_table):
            for rep, view in zip(self._parts[pid], row):
                # health/compat windows follow the published table; tasks
                # in flight keep the view they captured from the snapshot
                rep.view = view
                rep.spec = specs[pid]
        return out

    # -- elastic re-split (docs/SCALING.md "Scale-out tier") ---------------
    def resize(self, new_store, partitions: int) -> List[Dict]:
        """Re-split the store over a NEW partition width (elastic fleet
        membership: a worker joined or drained). Same build-beside-then-
        publish discipline as refresh(): every partition's view over its
        new contiguous slice builds beside the serving table, then the
        finished table — at the new width — publishes with ONE reference
        assignment. A scatter snapshots the table once and derives its
        width from the snapshot, so no result set ever mixes splits.
        Rows the shrink strands (pid >= new width) stay in the replica
        grid for scatters in flight but are never routed again. Returns
        the per-partition restage record (refresh()'s shape)."""
        svc = self._svc
        specs = make_partition_specs(new_store.shards(),
                                     max(1, int(partitions)),
                                     hot_gb=svc._hot_gb)
        width = len(specs)       # clamped to the shard count
        while len(self._parts) < width:
            pid = len(self._parts)
            reps = [_PartitionReplica(specs[pid], rid)
                    for rid in range(self.replicas)]
            self._parts.append(reps)
            with self._route_lock:
                self._sheds.append(0)
                self._degraded_serves.append(0)
        out: List[Dict] = []
        new_table: List[tuple] = []
        for pid in range(width):
            spec = specs[pid]
            swaps, row = [], []
            for rep in self._parts[pid]:
                t0 = time.perf_counter()
                rep.set_restaging(True)
                try:
                    row.append(svc._build_view(
                        new_store, reuse=rep.view,
                        entries=list(spec.entries), hot_gb=spec.hot_gb))
                finally:
                    rep.set_restaging(False)
                swaps.append(round((time.perf_counter() - t0) * 1000.0, 3))
            new_table.append(tuple(row))
            out.append({"partition": pid,
                        "shards": list(spec.shard_indices),
                        "rows": spec.rows,
                        "restage_ms": swaps})
        self._view_table = tuple(new_table)  # THE swap: one assignment
        self.partitions = width
        for pid in range(width):
            for rep, view in zip(self._parts[pid], new_table[pid]):
                rep.view = view
                rep.spec = specs[pid]
        return out

    # -- telemetry ---------------------------------------------------------
    def stats(self) -> List[Dict]:
        """Per-partition topology + routing health: the metrics() /
        loadtest "partitions" block."""
        elapsed = max(time.perf_counter() - self._t0, 1e-9)
        with self._route_lock:
            sheds = list(self._sheds)
            degr = list(self._degraded_serves)
        out = []
        # bounded by the LIVE width: rows a shrink stranded are not part
        # of the serving topology any more
        for pid, reps in enumerate(self._parts[:self.partitions]):
            rstats = [r.stats() for r in reps]
            out.append({
                "partition": pid,
                "shards": list(reps[0].spec.shard_indices),
                "rows": reps[0].spec.rows,
                "qps": round(sum(r["requests"] for r in rstats) / elapsed,
                             3),
                "p99_ms": max((r["p99_ms"] for r in rstats), default=0.0),
                "sheds": sheds[pid],
                "degraded_serves": degr[pid],
                "replicas": rstats,
            })
        return out

    def close(self) -> None:
        for reps in self._parts:
            for rep in reps:
                rep.close()
