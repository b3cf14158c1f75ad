"""Multi-process partition serving: workers, registration, hedged fan-out
(docs/SERVING.md "Network front end", docs/SCALING.md "Partitioned
serving").

PR 12 made partitions an abstraction (`infer/partition.py`): P x R
host-simulated worker THREADS, each owning a `_ServeView` over its
`PartitionSpec` slice. This module puts each replica behind a real
process and socket boundary:

  * `PartitionWorker` — one partition replica as its own process (or, in
    tests, a thread with its own service instance): opens the store,
    builds ONE restricted view over its spec's contiguous shard range
    (the same `SearchService._build_view` the in-process replicas use, so
    results are byte-identical by construction), connects to the front
    end's `WorkerGateway`, REGISTERs, heartbeats, and answers `T_VQUERY`
    frames with `_topk_view` over its slice. `cli partition-worker` is
    the process entry point.
  * `WorkerGateway` — the front-end side: a plain-socket listener where
    workers register, one reader thread per worker demultiplexing
    responses by request id, and the scatter itself — `topk()` fans the
    coalesced query block out to one routed worker per partition (routing
    still goes through `PartitionSet._route`, which now sees worker
    LIVENESS: a dead worker's replica sheds with reason "liveness"
    exactly like a restaging one sheds in-process).

Tail-latency control:

  * **per-partition deadlines** — the fan-out budgets each RPC against
    the coalesced batch's tightest deadline (relative remaining ms on the
    wire; the worker re-anchors on its own clock).
  * **hedged requests** — when a partition's answer has not arrived
    within the `serve.hedge_quantile` quantile of that partition's
    observed RPC latency, the SAME request fires at a sibling replica's
    worker and the first answer wins (`serve.hedge_fired` counter,
    `hedge_fired` event). Hedging needs a latency history (>= 8 samples)
    — a cold gateway never hedges on guesses.
  * **local fallback** — a worker that is dead, times out, or tears its
    response degrades EXACTLY like the in-process shed path: the gateway
    computes that partition's slice on the front end's own view
    (`_topk_view` over the identical shard range), so a kill -9 or a
    truncated frame can change latency but never bytes — the result-set
    identity pin extends over the wire.

Liveness: a worker is alive while its registration connection is open
and its last heartbeat is younger than 2 x `serve.heartbeat_s`.
Connection EOF / torn frames mark it lost immediately (`worker_lost`
event) and fail its in-flight RPCs over to the fallback path — recovery
is bounded by one heartbeat interval even for a silently hung peer.

Self-healing (docs/ROBUSTNESS.md "Network failure model"): a lost
worker is no longer gone for good — `PartitionWorker.run` is a
supervised loop that re-dials with exponential backoff + jitter
(`serve.reconnect_base_s` / `serve.reconnect_max_s`) and re-REGISTERs
with its current generation; the gateway re-admits it (`worker_rejoined`
event) and nudges a generation-lagging rejoiner with T_REFRESH so it
serves nothing stale. Gateway-side, each replica slot carries a
persistent circuit breaker (`serve.breaker_*`): K consecutive wire
failures open it and routing skips the replica (straight to local
fallback, no per-request timeout) until a half-open probe succeeds.
"""
from __future__ import annotations

import dataclasses
import json
import os
import random
import socket
import threading
import time
from collections import OrderedDict
from concurrent.futures import FIRST_COMPLETED, Future
from concurrent.futures import wait as futures_wait
from typing import Dict, List, Optional, Tuple

import numpy as np

from dnn_page_vectors_tpu.infer import transport
from dnn_page_vectors_tpu.utils import faults
from dnn_page_vectors_tpu.infer.transport import (
    DeadlineExceeded, FrameError, FLAG_FILTERS, FLAG_RESULT_CACHE,
    FLAG_WIRE_COMPRESS, FrameSender, InternTable, RemoteError, T_BYE,
    T_DRAIN, T_HEARTBEAT, T_HELLO, T_REFRESH, T_REGISTER, T_RESULT,
    T_RESULT_C, T_SHED, T_ERROR, T_VQUERY, T_VQUERY_PUT, T_VQUERY_REF)
from dnn_page_vectors_tpu.ops.topk import merge_partition_topk
from dnn_page_vectors_tpu.utils.profiling import LatencyStats


class MeshEmbedder:
    """The model-free embedder stub a partition worker serves with: the
    serving top-k only needs the device mesh (staging + compiled top-k);
    tokenize/encode never run on the vector RPC path."""

    def __init__(self, mesh):
        self.mesh = mesh
        self.query_tok = None
        self.page_tok = None


class _WorkerConn:
    """Front-end-side record of one registered partition worker."""

    def __init__(self, sock: socket.socket, addr, partition: int,
                 replica: int, pid: int, flags: int = 0,
                 generation: int = 0):
        self.sock = sock
        self.addr = addr
        self.partition = int(partition)
        self.replica = int(replica)
        self.pid = int(pid)
        self.flags = int(flags)            # negotiated caps, set once
        self.wlock = threading.Lock()      # serializes frame writes
        # send-path state shared with the writer: the reused encode
        # buffer and the query-block intern ring both live under wlock
        self.sender = FrameSender(sock)    # guarded-by: wlock
        self.intern = InternTable()        # guarded-by: wlock
        self._lock = threading.Lock()
        self._last_beat = time.perf_counter()   # guarded-by: _lock
        self._dead = False                       # guarded-by: _lock
        self._lost_reason: Optional[str] = None  # guarded-by: _lock
        self._generation = int(generation)       # guarded-by: _lock
        # the partition-split width this worker's REFRESH ack says its
        # view was built over; None until the first ack lands (a
        # pre-elastic worker never reports one). Elastic routing gates
        # on it exactly like it gates on generation — a worker on the
        # wrong split serves NOTHING until it re-splits, so one result
        # set can never mix splits across the wire.
        self._split: Optional[int] = None        # guarded-by: _lock
        # a draining worker announced T_DRAIN: routing stops sending it
        # new work (its slice falls back to the local view) and the
        # elastic fleet width no longer counts it
        self._draining = False                   # guarded-by: _lock

    def beat(self) -> None:
        with self._lock:
            self._last_beat = time.perf_counter()

    def alive(self, max_age_s: float) -> bool:
        with self._lock:
            if self._dead:
                return False
            return (time.perf_counter() - self._last_beat) <= max_age_s

    def mark_dead(self, reason: str) -> bool:
        """-> True exactly once (the caller that transitions it emits the
        worker_lost event)."""
        with self._lock:
            if self._dead:
                return False
            self._dead = True
            self._lost_reason = reason
            return True

    @property
    def dead(self) -> bool:
        with self._lock:
            return self._dead

    @property
    def generation(self) -> int:
        with self._lock:
            return self._generation

    def set_generation(self, gen: int,
                       split: Optional[int] = None) -> None:
        with self._lock:
            self._generation = int(gen)
            if split is not None and split > 0:
                self._split = int(split)

    @property
    def split(self) -> Optional[int]:
        with self._lock:
            return self._split

    def set_draining(self) -> bool:
        """-> True exactly once (the transitioning caller emits the
        worker_draining event and triggers the elastic shrink)."""
        with self._lock:
            if self._draining:
                return False
            self._draining = True
            return True

    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining


class WorkerGateway:
    """The front end's worker registry + RPC fan-out (one per service).

    Workers connect to `port` and REGISTER; the gateway reads heartbeats
    and responses off each connection on a dedicated reader thread and
    exposes `topk()` — the over-the-wire scatter `SearchService` routes
    through when attached (`svc.attach_gateway(gw)`)."""

    def __init__(self, svc, pset=None, host: str = "127.0.0.1",
                 port: int = 0, heartbeat_s: Optional[float] = None,
                 hedge_quantile: Optional[float] = None,
                 rpc_timeout_s: float = 10.0):
        self._svc = svc
        serve_cfg = getattr(svc.cfg, "serve", None)
        self.heartbeat_s = (heartbeat_s if heartbeat_s is not None
                            else getattr(serve_cfg, "heartbeat_s", 0.5)
                            if serve_cfg is not None else 0.5)
        self.hedge_quantile = (
            hedge_quantile if hedge_quantile is not None
            else getattr(serve_cfg, "hedge_quantile", 0.95)
            if serve_cfg is not None else 0.95)
        # serve.wire_compress: what THIS end confirms when a worker
        # advertises compression at REGISTER; off = the whole fleet
        # talks raw frames regardless of worker capability
        self._compress = bool(getattr(serve_cfg, "wire_compress", True)
                              if serve_cfg is not None else True)
        # fleet result cache (docs/SERVING.md "Result cache"): what THIS
        # end confirms when a worker advertises FLAG_RESULT_CACHE — the
        # worker then answers repeated vector blocks from its per-hop
        # block cache instead of re-scanning
        self._rcache = bool(
            serve_cfg is not None
            and getattr(serve_cfg, "result_cache", False)
            and getattr(serve_cfg, "result_cache_fleet", False))
        # filtered retrieval (docs/ANN.md "Filtered retrieval"): what
        # THIS end confirms when a worker advertises FLAG_FILTERS — a
        # filtered scatter only routes a partition to a worker that
        # negotiated the flag; everyone else's slice serves locally
        self._filters = bool(getattr(serve_cfg, "filters", True)
                             if serve_cfg is not None else True)
        # per-replica circuit breakers (docs/ROBUSTNESS.md "Network
        # failure model"): serve.breaker_failures consecutive wire
        # failures open a replica's breaker and routing skips it until a
        # half-open probe succeeds; <= 0 disables breakers entirely
        self._breaker_failures = int(
            getattr(serve_cfg, "breaker_failures", 3)
            if serve_cfg is not None else 3)
        self._breaker_open_s = float(
            getattr(serve_cfg, "breaker_open_s", 0.25)
            if serve_cfg is not None else 0.25)
        self._breaker_max_s = float(
            getattr(serve_cfg, "breaker_max_s", 30.0)
            if serve_cfg is not None else 30.0)
        # serve.elastic (docs/SCALING.md "Scale-out tier"): fleet
        # membership drives the partition split. A worker joining at the
        # next tail index widens the split (deterministic
        # partition_shard_ranges re-cut), a draining tail worker shrinks
        # it — both through the same generation-gated REFRESH handoff a
        # store swap uses, so no result set ever mixes splits. Off (the
        # default), the split is fixed at boot exactly as before.
        self._elastic = bool(getattr(serve_cfg, "elastic", False)
                             if serve_cfg is not None else False)
        self.rpc_timeout_s = float(rpc_timeout_s)
        self._own_pset = None
        if pset is None:
            pset = svc.partition_set
        if pset is None:
            # single-view service: fan out through a 1-partition set the
            # gateway owns (routing/health state lives there) — the P=1
            # over-the-wire topology is a worker, not a special case
            from dnn_page_vectors_tpu.infer.partition import PartitionSet
            self._own_pset = pset = PartitionSet(svc, svc.store,
                                                 partitions=1, replicas=1)
        self.partition_set = pset
        self._lock = threading.Lock()
        # registry lock over per-worker connection state: stats() reads
        # worker liveness (the _WorkerConn._lock property) while holding
        # the registry lock, never the reverse (graftcheck lock-order)
        # lock-order: WorkerGateway._lock < _WorkerConn._lock
        self._workers: Dict[Tuple[int, int], _WorkerConn] = {}  # guarded-by: _lock
        # breakers OUTLIVE their _WorkerConn: keyed by replica slot, so
        # trip history spans re-registrations (the breaker itself locks
        # its own state; only the dict is registry state)
        self._breakers: Dict[Tuple[int, int], faults.CircuitBreaker] = {}  # guarded-by: _lock
        self._pending: Dict[int, Tuple[Future, _WorkerConn]] = {}  # guarded-by: _lock
        self._lat: Dict[int, LatencyStats] = {}   # guarded-by: _lock
        self._registered = 0                      # guarded-by: _lock
        self._rpcs = 0                            # guarded-by: _lock
        self._rpc_fallbacks = 0                   # guarded-by: _lock
        self._resplits = 0                        # guarded-by: _lock
        self._wait_timeouts = 0                   # guarded-by: _lock
        self._closed = False                      # guarded-by: _lock
        self._threads: List[threading.Thread] = []   # guarded-by: _lock
        # serializes elastic re-splits (a join and a drain landing
        # together must re-cut once, not interleave two resizes). Held
        # OUTSIDE the registry lock and the service's refresh lock: the
        # membership snapshot is taken under _lock and released before
        # the resize starts, and the resize itself runs under the same
        # svc._refresh_lock a store refresh uses, so a refresh and a
        # re-split can never interleave their view swaps.
        # lock-order: WorkerGateway._resplit_lock < SearchService._refresh_lock
        # lock-order: SearchService._refresh_lock < WorkerGateway._lock
        self._resplit_lock = threading.Lock()
        # the listener socket and the accept-thread handle are OWNER
        # state: bound here, closed/joined only by close() — reader
        # threads never touch them
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, int(port)))
        self._sock.listen(64)
        self.host, self.port = self._sock.getsockname()[:2]
        self._accept_t = threading.Thread(target=self._accept_loop,
                                          daemon=True,
                                          name="worker-gateway-accept")
        self._accept_t.start()

    # -- registry ----------------------------------------------------------
    def _accept_loop(self) -> None:
        while True:
            try:
                conn, addr = self._sock.accept()
            except OSError:
                return            # listener closed
            spec = faults.active().wire("gateway_accept")
            if spec is not None:
                # an injected accept fault: the worker's dial lands and
                # immediately dies (or stalls) — its retry_wire/reconnect
                # path is what's under test
                if spec.kind in ("delay", "frame_delay"):
                    time.sleep(faults.active().wire_delay_s())
                else:
                    conn.close()
                    continue
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            t = threading.Thread(target=self._conn_loop, args=(conn, addr),
                                 daemon=True, name="worker-gateway-reader")
            with self._lock:
                if self._closed:
                    conn.close()
                    return
                self._threads.append(t)
            t.start()

    def _conn_loop(self, conn: socket.socket, addr) -> None:
        """One registered worker's reader: REGISTER first, then
        heartbeats and RPC responses until EOF/torn frame."""
        svc = self._svc
        worker: Optional[_WorkerConn] = None
        reason = "connection closed"
        try:
            frame = transport.read_frame(conn)
            if frame is None or frame[0] != T_REGISTER:
                conn.close()
                return
            self._account(transport.HEADER.size + len(frame[1]))
            pid_, rid, wpid, wflags, wgen = transport.decode_register(
                frame[1])
            agreed = wflags & ((FLAG_WIRE_COMPRESS if self._compress else 0)
                               | (FLAG_RESULT_CACHE if self._rcache else 0)
                               | (FLAG_FILTERS if self._filters else 0))
            worker = _WorkerConn(conn, addr, pid_, rid, wpid,
                                 flags=agreed, generation=wgen)
            with self._lock:
                old = self._workers.get((pid_, rid))
                self._workers[(pid_, rid)] = worker
                self._registered += 1
            rejoined = False
            if old is not None:
                if old.mark_dead("replaced"):
                    self._fail_inflight(old, "replaced by a new "
                                             "registration")
                else:
                    # the slot's previous connection was already LOST:
                    # this registration is the self-healing worker's
                    # re-dial landing (docs/ROBUSTNESS.md)
                    rejoined = True
            if wflags:
                # confirm the negotiated capability set on the same
                # ordered stream — the ack lands before any VQUERY, so
                # the worker knows the agreed flags by its first answer
                with worker.wlock:
                    worker.sender.send(T_HELLO, transport.encode_hello(
                        agreed), counter=svc._m_wire_bytes,
                        raw_counter=svc._m_wire_raw)
            svc.registry.event("worker_registered", {
                "partition": pid_, "replica": rid, "pid": wpid,
                "addr": f"{addr[0]}:{addr[1]}",
                "wire_compress": bool(agreed & FLAG_WIRE_COMPRESS),
                "result_cache": bool(agreed & FLAG_RESULT_CACHE),
                "filters": bool(agreed & FLAG_FILTERS),
                "generation": wgen})
            if rejoined:
                # liveness restored: the fresh connection wipes the
                # breaker's consecutive-failure history (the in-flight
                # RPCs the loss failed already counted against it)
                self._breaker_result(pid_, rid, ok=True)
                svc.registry.event("worker_rejoined", {
                    "partition": pid_, "replica": rid, "pid": wpid,
                    "generation": wgen})
            # a (re)joining worker whose view lags the routed generation
            # serves NOTHING until REFRESH catches it up (generation
            # gating in _pick_worker) — nudge it immediately instead of
            # leaving it stale until the next broadcast_refresh. In
            # elastic mode the nudge ALWAYS fires and carries the routed
            # split width too: a joiner's split is unknown until its ack
            # lands (split gating), so the nudge is also how it becomes
            # routable at all.
            cur_gen = self._routed_generation(pid_)
            cur_split = (len(self.partition_set._view_table)
                         if self._elastic else 0)
            if cur_gen is not None and (wgen != cur_gen or self._elastic):
                try:
                    with worker.wlock:
                        worker.sender.send(
                            T_REFRESH,
                            transport.encode_refresh(cur_gen, cur_split),
                            counter=svc._m_wire_bytes,
                            raw_counter=svc._m_wire_raw)
                except OSError:
                    pass          # a dying worker re-registers fresh
            # a join at the next tail index widens the elastic fleet:
            # re-cut the split over the new width and broadcast the
            # handoff (no-op unless serve.elastic and the live set is
            # contiguous at a new width)
            self._maybe_resplit(trigger="join")
            while True:
                frame = transport.read_frame(conn)
                if frame is None:
                    break
                ftype, payload = frame
                actual = transport.HEADER.size + len(payload)
                if ftype == T_HEARTBEAT:
                    self._account(actual)
                    worker.beat()
                elif ftype in (T_RESULT, T_RESULT_C, T_SHED, T_ERROR):
                    worker.beat()     # any traffic proves liveness
                    self._resolve(ftype, payload, actual)
                elif ftype == T_REFRESH:
                    # the worker's view-rebuild ack: it now serves this
                    # store generation (and, extended form, this split
                    # width) and is routable again
                    self._account(actual)
                    gen, wsplit = transport.decode_refresh(payload)
                    worker.set_generation(gen, split=wsplit)
                    worker.beat()
                    svc.registry.event("worker_refreshed", {
                        "partition": worker.partition,
                        "replica": worker.replica, "generation": gen,
                        "partitions": wsplit})
                elif ftype == T_DRAIN:
                    # the worker announced a graceful exit: stop routing
                    # it new work NOW (its slice serves from the local
                    # view), and let the elastic fleet shrink around it
                    self._account(actual)
                    worker.beat()
                    if worker.set_draining():
                        svc.registry.event("worker_draining", {
                            "partition": worker.partition,
                            "replica": worker.replica, "pid": worker.pid})
                        self._maybe_resplit(trigger="drain")
                elif ftype == T_BYE:
                    self._account(actual)
                    reason = "deregistered"
                    break
                else:
                    self._account(actual)
                    reason = f"unexpected frame type {ftype}"
                    break
        except FrameError as e:
            # torn response / garbage: indistinguishable from a crashed
            # peer — treated exactly like one
            reason = f"torn frame: {e}"
        except OSError as e:
            reason = f"socket error: {e}"
        finally:
            try:
                conn.close()
            except OSError:
                pass
            if worker is not None and worker.mark_dead(reason):
                self._fail_inflight(worker, reason)
                svc.registry.event("worker_lost", {
                    "partition": worker.partition,
                    "replica": worker.replica,
                    "reason": reason[:200]})

    def _account(self, actual: int, raw: Optional[int] = None) -> None:
        """Wire-byte accounting: actual bytes moved, plus the raw-frame
        equivalent (what the same traffic would have cost uncompressed)
        feeding the wire-compression ratio."""
        self._svc._m_wire_bytes.inc(actual)
        self._svc._m_wire_raw.inc(actual if raw is None else raw)

    def _resolve(self, ftype: int, payload: bytes, actual: int) -> None:
        if ftype in (T_RESULT, T_RESULT_C):
            req_id, scores, ids, scan = transport.decode_result_any(
                ftype, payload)
            self._account(actual,
                          raw=transport.result_raw_bytes(*scores.shape)
                          if ftype == T_RESULT_C else actual)
            ok: Optional[Tuple] = (scores, ids, scan)
            exc: Optional[Exception] = None
        elif ftype == T_SHED:
            self._account(actual)
            req_id, code, why = transport.decode_shed(payload)
            ok, exc = None, DeadlineExceeded(why or f"shed code {code}")
        else:
            self._account(actual)
            req_id, msg = transport.decode_error(payload)
            ok, exc = None, RemoteError(msg)
        with self._lock:
            entry = self._pending.pop(req_id, None)
        if entry is None:
            return                # a hedged loser landing late: discard
        fut, _ = entry
        if exc is None:
            fut.set_result(ok)
        else:
            fut.set_exception(exc)

    def _fail_inflight(self, worker: _WorkerConn, reason: str) -> None:
        with self._lock:
            doomed = [rid for rid, (_, w) in self._pending.items()
                      if w is worker]
            entries = [self._pending.pop(rid) for rid in doomed]
        for fut, _ in entries:
            fut.set_exception(RemoteError(f"worker lost: {reason}"))

    def _routed_generation(self, pid: int) -> Optional[int]:
        """The store generation the front end currently routes for
        partition `pid` — what a worker must serve to be eligible."""
        try:
            views = self.partition_set._view_table[pid]
        except IndexError:
            return None
        return views[0].generation if views else None

    # -- circuit breakers (docs/ROBUSTNESS.md "Network failure model") -----
    def _breaker(self, pid: int, rid: int) -> faults.CircuitBreaker:
        """Replica (pid, rid)'s persistent breaker, created on first
        use. The open/close callbacks run OUTSIDE the breaker's lock
        (CircuitBreaker contract), so taking the registry lock in
        _breaker_event keeps the gateway's lock order intact."""
        with self._lock:
            br = self._breakers.get((pid, rid))
            if br is None:
                br = self._breakers[(pid, rid)] = faults.CircuitBreaker(
                    failures=self._breaker_failures,
                    open_s=self._breaker_open_s,
                    max_open_s=self._breaker_max_s,
                    on_open=lambda b, p=pid, r=rid: self._breaker_event(
                        "breaker_open", p, r, b),
                    on_close=lambda b, p=pid, r=rid: self._breaker_event(
                        "breaker_close", p, r, b))
            return br

    def _breaker_allow(self, pid: int, rid: int) -> bool:
        if self._breaker_failures <= 0:
            return True
        return self._breaker(pid, rid).allow()

    def _breaker_result(self, pid: int, rid: int, ok: bool) -> None:
        """Feed a wire outcome for replica (pid, rid) into its breaker.
        Only WIRE failures count (send errors, lost workers, remote
        errors) — a deadline shed is deliberate backpressure from a
        healthy worker and never opens a breaker."""
        if self._breaker_failures <= 0:
            return
        br = self._breaker(pid, rid)
        if ok:
            br.record_success()
        else:
            br.record_failure()

    def _breaker_event(self, name: str, pid: int, rid: int,
                       br: faults.CircuitBreaker) -> None:
        with self._lock:
            n_open = sum(1 for b in self._breakers.values()
                         if b.state == "open")
        reg = self._svc.registry
        reg.gauge("serve.breakers_open").set(n_open)
        attrs = {"partition": pid, "replica": rid,
                 "trips": br.trips, "open": n_open}
        if name == "breaker_open":
            reg.event("breaker_open", attrs)
        else:
            reg.event("breaker_close", attrs)

    # -- liveness (PartitionSet routing + availability tests) --------------
    def _alive_age_s(self) -> float:
        """Max heartbeat age before a CONNECTED worker counts as hung:
        two missed beats, plus a floor for host scheduling jitter (a
        loaded 1-core box can delay an idle worker's heartbeat thread
        past a bare 2x multiple). Crashes never wait for this — a dead
        connection reads EOF and marks the worker lost immediately."""
        return 2.0 * self.heartbeat_s + 0.25

    def worker_alive(self, pid: int, rid: int) -> bool:
        with self._lock:
            w = self._workers.get((pid, rid))
        return w is not None and w.alive(self._alive_age_s())

    def active(self) -> bool:
        """Any live worker at all? False = the in-process scatter serves
        (zero per-request overhead when no fleet is attached)."""
        with self._lock:
            workers = list(self._workers.values())
        age = self._alive_age_s()
        return any(w.alive(age) for w in workers)

    def live_workers(self) -> List[Tuple[int, int]]:
        with self._lock:
            keys = list(self._workers)
        return [key for key in keys if self.worker_alive(*key)]

    def wait_for_workers(self, n: int, timeout_s: float = 30.0) -> bool:
        """Block until `n` workers are live (fleet-start barrier for
        cli loadtest) — False on timeout, after recording WHAT the barrier
        waited for and the fleet state it saw (`gateway_wait_timeout`
        event + the stats() wait_timeouts counter): a silent False is
        undebuggable once re-splits make barriers routine."""
        t0 = time.perf_counter()
        t_end = t0 + timeout_s
        while time.perf_counter() < t_end:
            if len(self.live_workers()) >= n:
                return True
            time.sleep(0.01)
        live = len(self.live_workers())
        if live >= n:
            return True
        with self._lock:
            registered = self._registered
        self._note_wait_timeout(
            "workers", time.perf_counter() - t0, timeout_s,
            wanted=int(n), live=live, registered=registered)
        return False

    def _note_wait_timeout(self, barrier: str, waited_s: float,
                           timeout_s: float, **state) -> None:
        with self._lock:
            self._wait_timeouts += 1
        self._svc.registry.event("gateway_wait_timeout", dict(
            {"barrier": barrier, "waited_s": round(waited_s, 3),
             "timeout_s": round(float(timeout_s), 3)}, **state))

    def _pick_worker(self, pid: int, prefer_rid: int,
                     exclude: Tuple[int, ...] = (),
                     generation: Optional[int] = None,
                     split: Optional[int] = None,
                     require_flags: int = 0
                     ) -> Optional[_WorkerConn]:
        """The live worker that should answer partition `pid`: the routed
        replica's own worker when live, else the lowest-rid live sibling
        not in `exclude`. With `generation` set, a worker whose view
        serves a DIFFERENT store generation is ineligible — after a
        refresh the fan-out serves that slice locally (on the already-
        swapped front-end view) until the worker's T_REFRESH ack lands,
        so one result set can never mix generations across the wire.
        `split` gates identically on the partition-split width the
        worker last ACKED (elastic mode): a worker cut over a different
        width — or one that never reported — serves nothing, so one
        result set can never mix splits either. A draining worker is
        skipped unconditionally (its slice falls back to the local
        view). A replica whose circuit breaker is open is skipped the
        same way — the breaker check runs LAST because a half-open
        breaker's allow() consumes its single probe slot. `require_flags`
        restricts to workers whose NEGOTIATED capability set covers the
        mask — a filtered scatter passes FLAG_FILTERS here, so a legacy
        worker is simply unroutable for that request (its slice serves
        from the local filtered view: never wrong results)."""
        with self._lock:
            cands = [(rid, w) for (p, rid), w in self._workers.items()
                     if p == pid and rid not in exclude]
        cands.sort(key=lambda t: (t[0] != prefer_rid, t[0]))
        age = self._alive_age_s()
        for _, w in cands:
            if w.alive(age) and not w.draining \
                    and (w.flags & require_flags) == require_flags \
                    and (generation is None
                         or w.generation == generation) \
                    and (split is None or w.split == split) \
                    and self._breaker_allow(pid, w.replica):
                return w
        return None

    # -- the RPC fan-out ---------------------------------------------------
    def _prepare(self, qv: np.ndarray, n: int) -> Tuple[bytes, int, int]:
        """The shared fan-out encode: the query block's wire bytes are
        built ONCE per coalesced bucket and shared across every
        partition send (and every hedge/failover resend) — each RPC adds
        only its per-request head. -> (block bytes, n, dim)."""
        block = np.ascontiguousarray(qv[:n], dtype="<f4")
        return block.tobytes(), n, block.shape[1]

    def _send(self, worker: _WorkerConn, prep: Tuple[bytes, int, int],
              k: int, nprobe: Optional[int],
              deadline: Optional[float],
              ftext: Optional[str] = None) -> Future:
        svc = self._svc
        block, n, dim = prep
        req_id = transport.next_request_id()
        rem_ms = 0.0
        if deadline is not None:
            rem_ms = max((deadline - svc._clock()) * 1000.0, 0.001)
        head = transport._VQUERY_HEAD.pack(req_id, rem_ms, int(k),
                                           int(nprobe or 0), n, dim)
        # the optional predicate field is PER REQUEST — it rides after
        # the block on every variant and is never interned (routing
        # guarantees this worker negotiated FLAG_FILTERS when non-empty)
        tail = transport._filters_field(ftext)
        fut: Future = Future()
        with self._lock:
            self._pending[req_id] = (fut, worker)
            self._rpcs += 1
        try:
            with worker.wlock:
                if worker.flags & FLAG_WIRE_COMPRESS:
                    # interned send: the block ships once per connection
                    # slot; repeats cost a 2-byte reference
                    slot, fresh = worker.intern.slot_for(block)
                    slot_b = transport._SLOT.pack(slot)
                    raw = (transport.HEADER.size + len(head) + len(block)
                           + len(tail))
                    if fresh:
                        worker.sender.send(T_VQUERY_PUT, head, slot_b,
                                           block, tail,
                                           counter=svc._m_wire_bytes,
                                           raw_counter=svc._m_wire_raw,
                                           raw_len=raw)
                    else:
                        worker.sender.send(T_VQUERY_REF, head, slot_b,
                                           tail,
                                           counter=svc._m_wire_bytes,
                                           raw_counter=svc._m_wire_raw,
                                           raw_len=raw)
                else:
                    worker.sender.send(T_VQUERY, head, block, tail,
                                       counter=svc._m_wire_bytes,
                                       raw_counter=svc._m_wire_raw)
        except OSError as e:
            # popping the entry claims the right to complete the future:
            # the reader thread races us here (a torn send closes the
            # socket, so its _fail_inflight may fail this req_id first)
            with self._lock:
                claimed = self._pending.pop(req_id, None) is not None
            if worker.mark_dead(f"send failed: {e}"):
                self._fail_inflight(worker, f"send failed: {e}")
                svc.registry.event("worker_lost", {
                    "partition": worker.partition,
                    "replica": worker.replica,
                    "reason": f"send failed: {e}"[:200]})
            # no breaker feed here: the RemoteError future is observed
            # in _await_partition, which records exactly one failure
            if claimed:
                fut.set_exception(RemoteError(f"send failed: {e}"))
        return fut

    def _hedge_delay_s(self, pid: int) -> Optional[float]:
        """The wait before hedging partition `pid`: the hedge-quantile
        point of its observed RPC latency, or None while the history is
        too thin (< 8 samples) to hedge on evidence."""
        q = self.hedge_quantile
        if not 0.0 < q < 1.0:
            return None
        with self._lock:
            lat = self._lat.get(pid)
            if lat is None or len(lat) < 8:
                return None
            return max(lat.percentile_ms(q * 100.0) / 1000.0, 1e-4)

    def _record_latency(self, pid: int, seconds: float) -> None:
        with self._lock:
            lat = self._lat.get(pid)
            if lat is None:
                lat = self._lat[pid] = LatencyStats()
            lat.add(seconds)

    def _await_partition(self, pid: int, prefer_rid: int, first: Future,
                         first_rid: int, prep: Tuple[bytes, int, int],
                         k: int, nprobe: Optional[int],
                         deadline: Optional[float],
                         generation: Optional[int] = None,
                         split: Optional[int] = None,
                         ftext: Optional[str] = None
                         ) -> Optional[Tuple]:
        """Wait for partition `pid`'s RPC answer, hedging to a sibling at
        the latency-quantile point and failing over on worker loss; None
        when every wire route failed (the caller serves locally)."""
        svc = self._svc
        t0 = time.perf_counter()
        budget = self.rpc_timeout_s
        if deadline is not None:
            rem = deadline - svc._clock()
            budget = min(budget, max(rem, 0.0))
        in_flight: Dict[Future, int] = {first: first_rid}
        tried = {first_rid}
        hedged = False
        while True:
            elapsed = time.perf_counter() - t0
            remaining = budget - elapsed
            hedge_s = None if hedged else self._hedge_delay_s(pid)
            if hedge_s is not None and elapsed < hedge_s:
                timeout = min(hedge_s - elapsed, max(remaining, 0.0))
            else:
                timeout = max(remaining, 0.0)
            done, _ = futures_wait(set(in_flight), timeout=timeout,
                                   return_when=FIRST_COMPLETED)
            for fut in done:
                rid = in_flight.pop(fut)
                exc = fut.exception()
                if exc is not None and isinstance(exc, RemoteError):
                    # a wire failure (lost worker / failed send / remote
                    # error) feeds the breaker; a DeadlineExceeded shed
                    # is deliberate backpressure and never counts
                    self._breaker_result(pid, rid, ok=False)
                if exc is None:
                    self._breaker_result(pid, rid, ok=True)
                    if not hedged:
                        # only UNHEDGED completions feed the hedge-delay
                        # history: a hedged call finishes slow by
                        # definition (the hedge only fired because it
                        # crossed the quantile), and recording it would
                        # drag the threshold up until hedging turned
                        # itself off — the healthy-path distribution is
                        # the reference the quantile must track
                        self._record_latency(pid,
                                             time.perf_counter() - t0)
                    return fut.result()
                tried.add(rid)
            elapsed = time.perf_counter() - t0
            if elapsed >= budget and not in_flight:
                return None
            if not in_flight:
                # every issued RPC failed: fail over to an untried live
                # sibling (not a hedge — the first copy is already dead)
                w = self._pick_worker(pid, prefer_rid,
                                      exclude=tuple(tried),
                                      generation=generation, split=split,
                                      require_flags=(FLAG_FILTERS
                                                     if ftext else 0))
                if w is None:
                    return None
                in_flight[self._send(w, prep, k, nprobe, deadline,
                                     ftext)] = w.replica
                tried.add(w.replica)
                continue
            if elapsed >= budget:
                return None
            if (not hedged and hedge_s is not None
                    and elapsed >= hedge_s):
                hedged = True
                w = self._pick_worker(pid, prefer_rid,
                                      exclude=tuple(tried),
                                      generation=generation, split=split,
                                      require_flags=(FLAG_FILTERS
                                                     if ftext else 0))
                if w is not None:
                    svc._m_hedge_fired.inc()
                    cur = svc.tracer.current()
                    svc.registry.event("hedge_fired", {
                        "partition": pid, "from_replica": first_rid,
                        "to_replica": w.replica,
                        "after_ms": round(elapsed * 1000.0, 3),
                    }, trace_id=getattr(cur, "trace_id", None))
                    in_flight[self._send(w, prep, k, nprobe, deadline,
                                         ftext)] = w.replica
                    tried.add(w.replica)

    # graftcheck: hot
    def topk(self, qv: np.ndarray, n: int, k: int,
             nprobe: Optional[int] = None,
             deadline: Optional[float] = None,
             predicate=None) -> Tuple[np.ndarray, np.ndarray]:
        """The over-the-wire scatter-gather: one routed worker RPC per
        partition (hedged, deadline-budgeted), per-partition LOCAL
        fallback on any wire failure, winners folded through the same
        partition merge tree as the in-process scatter — results
        byte-identical to `PartitionSet.topk` by construction.

        With `predicate` (a compiled `index/attrs.Predicate`) the
        canonical text rides each RPC's optional filter field, routing
        restricts to FLAG_FILTERS workers, and every fallback slice runs
        the same filtered `_topk_view` — so the filtered result set is
        byte-identical to the in-process filtered scatter too."""
        svc = self._svc
        pset = self.partition_set
        ftext = predicate.text if predicate is not None else None
        req_flags = FLAG_FILTERS if ftext else 0
        # ONE table snapshot anchors the whole scatter: its length IS
        # the split width every per-partition decision below is gated
        # on, so a concurrent elastic re-split (which publishes a new
        # table in one assignment) can never hand this result set a
        # mixed cut — the same snapshot idiom that pins generations
        table = pset._view_table
        P = len(table)
        split = P if self._elastic else None
        # ONE shared encode for the whole scatter (and its hedges): the
        # block bytes build here and every per-partition send reuses them
        prep = self._prepare(qv, n)
        calls: List[Tuple[int, object, Optional[Future], int]] = []
        with svc._stage("scatter", partitions=P, transport="socket"):
            for pid in range(P):
                rep = pset._route(pid)
                gen = table[pid][rep.rid].generation
                w = self._pick_worker(pid, rep.rid, generation=gen,
                                      split=split,
                                      require_flags=req_flags)
                if w is None:
                    calls.append((pid, rep, None, -1))
                else:
                    calls.append((pid, rep,
                                  self._send(w, prep, k, nprobe, deadline,
                                             ftext),
                                  w.replica))
            parts: List[Optional[Tuple]] = [None] * P
            for pid, rep, fut, rid in calls:
                res = None
                if fut is not None:
                    with svc._stage("rpc", partition=pid, replica=rid):
                        res = self._await_partition(
                            pid, rep.rid, fut, rid, prep, k, nprobe,
                            deadline,
                            generation=table[pid][rep.rid].generation,
                            split=split, ftext=ftext)
                if res is None:
                    # the in-process degrade path, verbatim: this
                    # partition's slice computed on the front end's own
                    # view — a dead/torn/late worker costs latency,
                    # never bytes
                    if fut is not None:
                        with self._lock:
                            self._rpc_fallbacks += 1
                    view = table[pid][rep.rid]
                    res = svc._topk_view(view, qv, n, k, nprobe,
                                         predicate=predicate)
                parts[pid] = res
        with svc._stage("merge"):
            return merge_partition_topk([(s, i) for s, i, _ in parts])

    # -- store-generation control (docs/SERVING.md) ------------------------
    def broadcast_refresh(self, generation: int, wait_s: float = 0.0,
                          split: Optional[int] = None,
                          refresh_own: bool = True) -> Dict:
        """Tell every live worker to re-open the store and rebuild its
        view (T_REFRESH carrying the target generation — and, in elastic
        mode, the split width to re-cut over) — the wire fleet's half of
        `SearchService.refresh()`: a store generation swap no longer
        needs a worker restart. Until a worker ACKS with its own
        T_REFRESH, routing treats it as generation-stale (and, elastic,
        split-stale) and the fan-out serves its slice from the front
        end's local view, so the swap stays byte-consistent while the
        fleet catches up. With `wait_s` > 0 the call blocks up to that
        long for every live worker's ack. `split` defaults to the
        routed table's width in elastic mode, 0 (unspecified: the
        worker keeps its cut) otherwise; `refresh_own=False` skips the
        private-pset rebuild when the caller (resplit) already did it."""
        svc = self._svc
        if self._own_pset is not None and refresh_own:
            # single-view service: the gateway's private 1-partition set
            # must follow the store too, or its table (and the local
            # fallback views in it) would serve the old generation
            # forever while generation gating kept every worker
            # ineligible
            self._own_pset.refresh(svc.store)
        if split is None:
            split = (len(self.partition_set._view_table)
                     if self._elastic else 0)
        with self._lock:
            workers = list(self._workers.values())
        age = self._alive_age_s()
        told = 0
        for w in workers:
            if not w.alive(age) or (w.generation == generation
                                    and (split <= 0 or w.split == split)):
                continue
            try:
                with w.wlock:
                    w.sender.send(
                        T_REFRESH,
                        transport.encode_refresh(generation, split),
                        counter=svc._m_wire_bytes,
                        raw_counter=svc._m_wire_raw)
                told += 1
            except OSError:
                pass              # a dying worker re-registers fresh
        if wait_s > 0:
            self.wait_for_generation(generation, timeout_s=wait_s,
                                     split=split)
        return {"workers_told": told,
                "workers_stale": self.stale_workers(generation,
                                                    split=split)}

    def stale_workers(self, generation: int, split: int = 0) -> int:
        """Live workers whose view still serves another generation (or,
        with `split` > 0, another partition-split width)."""
        with self._lock:
            workers = list(self._workers.values())
        age = self._alive_age_s()
        return sum(1 for w in workers
                   if w.alive(age) and (w.generation != generation
                                        or (split > 0
                                            and w.split != split)))

    def wait_for_generation(self, generation: int,
                            timeout_s: float = 30.0,
                            split: int = 0) -> bool:
        """Block until no live worker lags `generation` (and `split`,
        when > 0) — the fleet-wide refresh barrier for tests/cli; False
        on timeout, after recording how long it waited and how many
        workers stayed stale (`gateway_wait_timeout` event + stats()
        counter)."""
        t0 = time.perf_counter()
        t_end = t0 + timeout_s
        while time.perf_counter() < t_end:
            if self.stale_workers(generation, split=split) == 0:
                return True
            time.sleep(0.01)
        stale = self.stale_workers(generation, split=split)
        if stale == 0:
            return True
        self._note_wait_timeout(
            "generation", time.perf_counter() - t0, timeout_s,
            generation=int(generation), split=int(split), stale=stale,
            live=len(self.live_workers()))
        return False

    # -- elastic membership (docs/SCALING.md "Scale-out tier") -------------
    def _fleet_width(self) -> Optional[int]:
        """The partition-split width the live fleet implies: one slice
        per distinct live, non-draining partition index — but only when
        those indices are exactly {0..W-1}. Membership changes at the
        TAIL (spawn the next index, drain the highest); a gapped set
        (a mid-fleet crash, an out-of-order spawn) returns None and the
        split stays put — crash recovery is rejoin + local fallback,
        never a re-cut under a hole."""
        with self._lock:
            workers = list(self._workers.values())
        age = self._alive_age_s()
        pids = {w.partition for w in workers
                if w.alive(age) and not w.draining}
        if not pids:
            return None
        width = max(pids) + 1
        if pids != set(range(width)):
            return None
        return width

    def _maybe_resplit(self, trigger: str) -> Optional[Dict]:
        """Re-cut the partition split if the live fleet's width moved
        (no-op unless serve.elastic)."""
        if not self._elastic:
            return None
        width = self._fleet_width()
        if width is None:
            return None
        with self._resplit_lock:
            if width != len(self.partition_set._view_table):
                return self._resplit(width, trigger)
        return None

    # holds-lock: _resplit_lock
    def _resplit(self, width: int, trigger: str) -> Dict:
        """The elastic re-cut: rebuild the front end's view table over
        `width` partitions (`partition_shard_ranges` over the new fleet
        size — deterministic, so every front end sharing the fleet cuts
        identically), then broadcast the generation+split handoff. The
        resize runs under the SAME svc._refresh_lock a store refresh
        takes, and publishes the new table in one assignment — in-flight
        scatters keep their snapshot of the old cut, new scatters see
        the new one, and split gating keeps every worker unroutable
        until it acks the new width, so no result set ever mixes
        splits."""
        svc = self._svc
        pset = self.partition_set
        old = len(pset._view_table)
        with svc._refresh_lock:
            pset.resize(svc.store, width)
        generation = self._routed_generation(0)
        info = self.broadcast_refresh(generation, split=width,
                                      refresh_own=False)
        with self._lock:
            self._resplits += 1
        svc.registry.event("fleet_resplit", {
            "trigger": trigger, "from_partitions": old,
            "to_partitions": width, "generation": generation,
            "workers_told": info["workers_told"]})
        return dict(info, partitions=width)

    # -- telemetry / lifecycle --------------------------------------------
    def stats(self) -> Dict:
        """The metrics()/loadtest transport sub-block."""
        with self._lock:
            registered = self._registered
            rpcs = self._rpcs
            fallbacks = self._rpc_fallbacks
            resplits = self._resplits
            wait_timeouts = self._wait_timeouts
            workers = list(self._workers.values())
            compressing = sum(
                1 for w in workers
                if not w.dead and w.flags & FLAG_WIRE_COMPRESS)
            filtering = sum(1 for w in workers
                            if not w.dead and w.flags & FLAG_FILTERS)
            breakers = list(self._breakers.values())
        return {
            "workers_live": len(self.live_workers()),
            "workers_registered": registered,
            "workers_compressing": compressing,
            "workers_filtering": filtering,
            "workers_draining": sum(1 for w in workers
                                    if not w.dead and w.draining),
            "rpcs": rpcs,
            "rpc_fallbacks": fallbacks,
            "resplits": resplits,
            "wait_timeouts": wait_timeouts,
            "breakers_open": sum(1 for b in breakers
                                 if b.state == "open"),
            "breaker_trips": sum(b.trips for b in breakers),
        }

    def close(self) -> None:
        with self._lock:
            self._closed = True
            workers = list(self._workers.values())
            threads = list(self._threads)
        try:
            self._sock.close()
        except OSError:
            pass
        for w in workers:
            # a clean BYE first: workers exit their serve loop instead of
            # reading a reset mid-frame (part of the graceful-drain
            # contract — docs/SERVING.md)
            if not w.dead:
                try:
                    with w.wlock:
                        w.sender.send(T_BYE)
                except OSError:
                    pass
            w.mark_dead("gateway closed")
            try:
                w.sock.close()
            except OSError:
                pass
        self._accept_t.join(timeout=5.0)
        for t in threads:
            t.join(timeout=5.0)
        if self._own_pset is not None:
            self._own_pset.close()


# ---------------------------------------------------------------------------
# the worker side
# ---------------------------------------------------------------------------

class _GatewayLink:
    """One worker->gateway connection's session state. A PartitionWorker
    serving N front ends runs one link per `--connect` endpoint: each
    link owns its OWN socket, sender, negotiated capability flags,
    intern slots, block cache, heartbeat thread, and reconnect
    supervisor — per-gateway wire state stays isolated by construction
    (the same invariant the per-connection intern tables rely on) while
    every link serves the ONE shared view."""

    def __init__(self, connect: Tuple[str, int], index: int):
        self.connect = (connect[0], int(connect[1]))
        self.index = int(index)
        self.sock: Optional[socket.socket] = None
        self.send_lock = threading.Lock()  # serializes frame writes
        self.sender: Optional[FrameSender] = None  # guarded-by: send_lock
        # agreed capabilities — re-negotiated per connection, written
        # and read only on this link's serve loop
        self.flags = 0
        # per-hop block cache: (query-block bytes, k, nprobe) -> (view,
        # scores, ids, scan). Link serve-loop only. A hit replays ONLY
        # if the cached view IS this request's snapshotted view object —
        # identity, not equality — so a refresh or re-split swap makes
        # every old entry unreachable without any cross-thread clearing.
        self.block_cache: OrderedDict = OrderedDict()
        self.sessions = 0   # completed dial+REGISTER rounds (serve loop)


class PartitionWorker:
    """One partition replica serving its `PartitionSpec` slice over a
    socket. As a process: `cli partition-worker` (the production shape);
    in tests it also runs as a thread with its own service instance —
    either way it owns an independent restricted view built by the exact
    `_build_view` the in-process replicas use.

    Multi-front-end (docs/SCALING.md "Scale-out tier"): `connect` may be
    a LIST of gateway endpoints — the worker registers with every one
    and answers each over its own `_GatewayLink`, all serving the same
    view. T_REFRESH from any gateway re-cuts/re-opens the shared view
    (idempotent: a second gateway's broadcast for a state already served
    just acks), so N front ends converge on one split without talking
    to each other."""

    def __init__(self, cfg, store_dir: str, connect,
                 partition: int, partitions: int, replica: int = 0,
                 mesh=None, preload_hbm_gb: float = 4.0,
                 heartbeat_s: Optional[float] = None,
                 slow_ms: float = 0.0):
        from dnn_page_vectors_tpu.infer.partition import make_partition_specs
        from dnn_page_vectors_tpu.infer.serve import SearchService
        from dnn_page_vectors_tpu.infer.vector_store import VectorStore
        self.partition = int(partition)
        self.partitions = int(partitions)
        self.replica = int(replica)
        if connect and isinstance(connect[0], (list, tuple)):
            endpoints = [(h, int(p)) for h, p in connect]
        else:
            endpoints = [(connect[0], int(connect[1]))]
        self.connect = endpoints[0]   # primary endpoint (back-compat)
        self._links = [_GatewayLink(ep, i)
                       for i, ep in enumerate(endpoints)]
        self.heartbeat_s = (heartbeat_s if heartbeat_s is not None
                            else getattr(cfg.serve, "heartbeat_s", 0.5))
        # wire compression is ADVERTISED at REGISTER and only used after
        # the gateway confirms (T_HELLO ack) — a raw gateway, or a raw
        # sibling on the same gateway, interoperates untouched
        self.wire_compress = bool(getattr(cfg.serve, "wire_compress", True))
        # fleet result cache, advertised like compression and only used
        # after the gateway confirms: repeated vector blocks (the Zipf
        # head re-encoded to the same query matrix) replay their scored
        # answer without touching the store
        self.result_cache = bool(
            getattr(cfg.serve, "result_cache", False)
            and getattr(cfg.serve, "result_cache_fleet", False))
        # filtered retrieval, advertised like compression: the gateway
        # only ships the VQUERY filter field after confirming the flag
        self.filters = bool(getattr(cfg.serve, "filters", True))
        self._block_cache_cap = 64   # per-link block-cache entries
        # drill hook (tests/test_net.py's hedge drill): added per-request
        # latency, so a deliberately slow replica provokes hedging
        self.slow_ms = float(slow_ms)
        if mesh is None:
            from dnn_page_vectors_tpu.parallel.multihost import local_mesh
            mesh = local_mesh(cfg.mesh)
        # the worker's own service answers exactly ONE slice: its config
        # is forced single-partition so no nested scatter can recurse
        cfg1 = cfg.replace(serve=dataclasses.replace(
            cfg.serve, partitions=1, replicas=1))
        store = VectorStore(store_dir)
        self.svc = SearchService(cfg1, MeshEmbedder(mesh), None, store,
                                 preload_hbm_gb=0.0)
        self.svc._preload_gb = preload_hbm_gb
        specs = make_partition_specs(store.shards(), self.partitions,
                                     hot_gb=cfg.serve.hot_postings_gb)
        if self.partition >= self.partitions:
            raise ValueError(
                f"partition {self.partition} does not exist: this worker "
                f"was asked for a {self.partitions}-way split")
        if self.partition < len(specs):
            self.spec = specs[self.partition]
        else:
            # the balanced split clamps below the requested width (more
            # workers than shards): an EMPTY slice is a valid elastic
            # member — it serves nothing until a re-split assigns it rows
            from dnn_page_vectors_tpu.infer.partition import PartitionSpec
            self.spec = PartitionSpec(pid=self.partition, entries=(),
                                      shard_indices=(), rows=0, hot_gb=0.0)
        self.view = self.svc._build_view(store,
                                         entries=list(self.spec.entries),
                                         hot_gb=self.spec.hot_gb)
        self._stop = threading.Event()
        # serializes the shared view/spec/split swap: T_REFRESH can now
        # arrive on N link threads at once; the swap itself stays one
        # reference assignment per field, the lock only orders rebuilds
        # (and lets a duplicate refresh short-circuit to an ack)
        # lock-order: PartitionWorker._swap_lock < _GatewayLink.send_lock
        self._swap_lock = threading.Lock()
        # self-healing (docs/ROBUSTNESS.md "Network failure model"): on
        # connection loss run() re-dials with exponential backoff +
        # jitter instead of exiting; serve.reconnect=False restores the
        # connection-loss-is-terminal behavior
        self.reconnect = bool(getattr(cfg.serve, "reconnect", True))
        self.reconnect_base_s = float(
            getattr(cfg.serve, "reconnect_base_s", 0.05))
        self.reconnect_max_s = float(
            getattr(cfg.serve, "reconnect_max_s", 2.0))
        # seeded per-replica jitter: deterministic under test, still
        # decorrelated across a fleet restarting together
        self._rng = random.Random(1 + (self.partition << 8) | self.replica)

    @property
    def sessions(self) -> int:
        """Completed dial+REGISTER rounds, across every gateway link."""
        return sum(ln.sessions for ln in self._links)

    # -- lifecycle ---------------------------------------------------------
    def _heartbeat_loop(self, link: _GatewayLink) -> None:
        while not self._stop.wait(self.heartbeat_s):
            try:
                with link.send_lock:
                    if link.sender is None:
                        return    # between sessions: this beat's done
                    link.sender.send(T_HEARTBEAT)
            except OSError:
                return

    def run(self) -> None:
        """Supervised serve loop (docs/ROBUSTNESS.md "Network failure
        model"): dial + REGISTER + serve on every gateway link; on EOF /
        torn frame / socket error a link re-dials with exponential
        backoff + jitter (base `serve.reconnect_base_s`, cap
        `serve.reconnect_max_s`) and re-REGISTERs with the CURRENT view
        generation, so a transient gateway blip costs one reconnect
        instead of the replica. A link exits on its gateway's clean
        T_BYE (deregistered), stop(), or — with serve.reconnect off —
        the first connection loss; run() returns when EVERY link has
        exited (one front end restarting never takes the worker down
        for its siblings). Blocking — the process entry point."""
        extra = [threading.Thread(target=self._run_link, args=(ln,),
                                  daemon=True,
                                  name=f"worker-p{self.partition}"
                                       f"r{self.replica}-g{ln.index}")
                 for ln in self._links[1:]]
        for t in extra:
            t.start()
        self._run_link(self._links[0])
        for t in extra:
            t.join()

    def _run_link(self, link: _GatewayLink) -> None:
        failures = 0
        while not self._stop.is_set():
            try:
                if self._serve_session(link):
                    break         # clean T_BYE: deregistered on purpose
                failures = 0      # a registered session resets the ramp
            except (FrameError, OSError):
                failures += 1     # gateway unreachable or stream torn
            if not self.reconnect or self._stop.is_set():
                break
            delay = min(self.reconnect_base_s * (2.0 ** max(failures - 1,
                                                            0)),
                        self.reconnect_max_s)
            delay += self._rng.uniform(0.0, delay / 2.0)
            faults.count("worker_reconnect")
            if self._stop.wait(delay):
                break

    def _dial(self, link: _GatewayLink) -> socket.socket:
        """Dial + REGISTER under the wire retry profile
        (faults.retry_wire — idempotent: a re-REGISTER replaces the
        previous registration), advertising the current view
        generation."""
        def _connect() -> socket.socket:
            faults.active().check("worker_dial")
            sock = socket.create_connection(link.connect)
            # an OSError on setsockopt or the REGISTER write must close
            # the socket on its way out (the retry dials fresh), not
            # leak it (graftcheck lifecycle rule)
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                view = self.view
                transport.write_frame(
                    sock, T_REGISTER,
                    transport.encode_register(
                        self.partition, self.replica, os.getpid(),
                        flags=(FLAG_WIRE_COMPRESS
                               if self.wire_compress else 0)
                        | (FLAG_RESULT_CACHE
                           if self.result_cache else 0)
                        | (FLAG_FILTERS if self.filters else 0),
                        generation=view.generation))
            except OSError:
                try:
                    sock.close()
                except OSError:
                    pass
                raise
            return sock
        return faults.retry_wire(_connect, op="worker_dial",
                                 backoff=self.reconnect_base_s,
                                 max_backoff=self.reconnect_max_s)

    def _serve_session(self, link: _GatewayLink) -> bool:
        """One dial + REGISTER + serve round on `link`. -> True on a
        clean T_BYE, False on EOF at a frame boundary (the supervisor
        re-dials); torn frames and socket errors propagate to the
        supervisor's backoff path."""
        sock = self._dial(link)
        hb: Optional[threading.Thread] = None
        slots: Dict[int, bytes] = {}   # per-connection intern table
        bye = False
        try:
            link.sock = sock
            link.sessions += 1
            link.flags = 0             # re-negotiated per connection
            with link.send_lock:
                link.sender = FrameSender(sock)
            hb = threading.Thread(target=self._heartbeat_loop,
                                  args=(link,), daemon=True,
                                  name=f"worker-p{self.partition}"
                                       f"r{self.replica}-g{link.index}-hb")
            hb.start()
            while not self._stop.is_set():
                frame = transport.read_frame(sock)
                if frame is None:
                    break
                ftype, payload = frame
                if ftype in (T_VQUERY, T_VQUERY_PUT, T_VQUERY_REF):
                    self._answer(link, ftype, payload, slots)
                elif ftype == T_HELLO:
                    # the gateway's negotiation ack: these capabilities
                    # are agreed for the rest of the connection
                    link.flags = transport.decode_hello(payload)
                elif ftype == T_REFRESH:
                    gen, parts = transport.decode_refresh(payload)
                    self._refresh(link, gen, parts)
                elif ftype == T_BYE:
                    bye = True
                    break
                # anything else from the gateway is ignorable control
        finally:
            # close FIRST: the heartbeat thread's next send then fails
            # fast and it exits inside the join window
            try:
                sock.close()
            except OSError:
                pass
            with link.send_lock:
                link.sender = None
            if hb is not None:
                hb.join(timeout=self.heartbeat_s + 2.0)
        return bye

    def _refresh(self, link: _GatewayLink, generation: int,
                 partitions: int = 0) -> None:
        """The T_REFRESH control path: re-open the store, rebuild this
        replica's restricted view over the shard split — re-cut over
        `partitions` when the extended frame carries a width (elastic
        re-split), the current width otherwise — swap it in with one
        reference assignment, and ack with the (generation, width) now
        served: byte-identical to a worker restarted against the same
        store, with no restart. With N gateways the rebuild is
        serialized and IDEMPOTENT — a second front end's broadcast for a
        state this worker already serves short-circuits straight to the
        ack. A rebuild failure keeps the OLD view serving (the gateway
        routes around the stale generation until a later refresh
        lands)."""
        from dnn_page_vectors_tpu.infer.partition import (
            make_partition_specs)
        from dnn_page_vectors_tpu.infer.vector_store import VectorStore
        with self._swap_lock:
            width = int(partitions) if partitions > 0 else self.partitions
            try:
                if (width != self.partitions
                        or self.view.generation != int(generation)):
                    new_store = VectorStore(self.svc.store.directory)
                    specs = make_partition_specs(
                        new_store.shards(), width,
                        hot_gb=self.svc.cfg.serve.hot_postings_gb)
                    if self.partition < len(specs):
                        spec = specs[self.partition]
                    else:    # the balanced split clamps under this slice
                        from dnn_page_vectors_tpu.infer.partition import (
                            PartitionSpec)
                        spec = PartitionSpec(pid=self.partition,
                                             entries=(), shard_indices=(),
                                             rows=0, hot_gb=0.0)
                    view = self.svc._build_view(new_store, reuse=self.view,
                                                entries=list(spec.entries),
                                                hot_gb=spec.hot_gb)
                    self.spec = spec
                    self.view = view   # THE swap: one reference assignment
                    self.partitions = width
                    self.svc.store = new_store
                    # this link's block cache self-invalidates (hits
                    # check view identity), but drop it eagerly anyway
                    # rather than letting dead entries squat the LRU;
                    # other links' caches age out on their own loops
                    link.block_cache.clear()
            except Exception:  # noqa: BLE001 — keep serving the old view
                pass
            try:
                with link.send_lock:
                    link.sender.send(T_REFRESH, transport.encode_refresh(
                        self.view.generation, self.partitions))
            except OSError:
                pass

    # graftcheck: hot
    def _answer(self, link: _GatewayLink, ftype: int, payload: bytes,
                slots: Dict[int, bytes]) -> None:
        req = transport.decode_vquery_any(ftype, payload, slots)
        t0 = time.perf_counter()
        parts: Tuple
        try:
            if self.slow_ms > 0:
                time.sleep(self.slow_ms / 1000.0)
            k = req.k or self.svc.cfg.eval.recall_k
            # the filter field only arrives when the gateway negotiated
            # FLAG_FILTERS with us; the canonical text folds into the
            # block-cache key so a filtered answer never replays for an
            # unfiltered repeat of the same block (or vice versa)
            from dnn_page_vectors_tpu.infer.serve import _compile_filters
            pred = _compile_filters(req.filters)
            # ONE view snapshot answers this request — the compute, the
            # cache hit check, and the cache fill all reference it, so a
            # concurrent refresh/re-split swap can't mix states
            view = self.view
            ckey = None
            hit = None
            if link.flags & FLAG_RESULT_CACHE:
                # per-hop block cache: a hit replays only when the
                # cached entry was computed on THIS view object
                # (identity check below), which makes it byte-identical
                # to a recompute — and unreachable the moment a refresh
                # or re-split swaps the view
                ckey = (req.qv.tobytes(), k, int(req.nprobe or 0),
                        req.filters or "")
                hit = link.block_cache.get(ckey)
                if hit is not None and hit[0] is view:
                    link.block_cache.move_to_end(ckey)
                else:
                    hit = None
            if hit is not None:
                _, scores, ids, scan = hit
            else:
                scores, ids, scan = self.svc._topk_view(
                    view, req.qv, req.qv.shape[0], k,
                    req.nprobe or None, predicate=pred)
                if ckey is not None:
                    link.block_cache[ckey] = (view, scores, ids, scan)
                    while len(link.block_cache) > self._block_cache_cap:
                        link.block_cache.popitem(last=False)
            if req.deadline_ms > 0 and \
                    (time.perf_counter() - t0) * 1000.0 > req.deadline_ms:
                # the budget died during compute: a late answer is waste
                # on the wire — the gateway already fell back
                rtype = T_SHED
                parts = (transport.encode_shed(
                    req.req_id, transport.SHED_DEADLINE,
                    "deadline expired during partition compute"),)
            elif link.flags & FLAG_WIRE_COMPRESS:
                rtype = T_RESULT_C
                parts = (transport.encode_result_c(req.req_id, scores,
                                                   ids, scan_bytes=scan),)
            else:
                rtype = T_RESULT
                scores = np.ascontiguousarray(scores, dtype="<f4")
                ids = np.ascontiguousarray(ids, dtype="<i8")
                parts = (transport._RESULT_HEAD.pack(
                    req.req_id, int(scan), *scores.shape), scores, ids)
        except Exception as e:  # noqa: BLE001 — the request fails, the
            # worker survives: per-request isolation like the batcher's
            rtype = T_ERROR
            parts = (transport.encode_error(req.req_id,
                                            f"{type(e).__name__}: {e}"),)
        with link.send_lock:
            link.sender.send(rtype, *parts)

    @staticmethod
    def _tear(sock: Optional[socket.socket]) -> None:
        """shutdown + close: a bare close() does not wake the serve
        loop's blocked recv (the in-flight syscall pins the kernel
        socket, so no FIN is sent either) — shutdown() tears the stream
        NOW, exactly like the process dying would."""
        if sock is None:
            return
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            sock.close()
        except OSError:
            pass

    def stop(self) -> None:
        """Abrupt local shutdown (tests' stand-in for kill -9): tear
        every link's socket out from under its serve loop."""
        self._stop.set()
        for ln in self._links:
            self._tear(ln.sock)

    def kill_connection(self) -> None:
        """Drill hook (tests/test_chaos.py's kill drill): tear every live
        connection out from under its serve loop WITHOUT stopping the
        worker — the supervised link loops re-dial and re-REGISTER,
        which is exactly the recovery path the chaos drills measure."""
        for ln in self._links:
            self._tear(ln.sock)

    def drain(self, wait_s: Optional[float] = None) -> None:
        """Graceful exit (docs/SCALING.md "Scale-out tier" drain rules):
        announce T_DRAIN on every link — each gateway stops routing this
        worker NEW work immediately and serves its slice from the local
        view (an elastic front end also shrinks the split around a
        drained tail index) — wait `wait_s` (default one heartbeat) for
        in-flight answers to flush, then BYE each gateway and stop. The
        announce-then-BYE split is what makes the handoff lossless: no
        request is ever in flight to a worker that has already gone."""
        for ln in self._links:
            try:
                with ln.send_lock:
                    if ln.sender is not None:
                        ln.sender.send(T_DRAIN)
            except OSError:
                pass              # that gateway already lost us
        time.sleep(self.heartbeat_s if wait_s is None else float(wait_s))
        for ln in self._links:
            try:
                with ln.send_lock:
                    if ln.sender is not None:
                        ln.sender.send(T_BYE)
            except OSError:
                pass
        self.stop()


def run_partition_worker(cfg, store_dir: str, connect: str, partition: int,
                         partitions: int, replica: int = 0,
                         preload_hbm_gb: float = 4.0) -> Dict:
    """`cli partition-worker` entry: build the worker (store + restricted
    view + mesh, NO model or checkpoint), print one ready line, serve
    until every gateway hangs up. `connect` is one `host:port` — or a
    comma-separated list of them for a worker shared by N front ends.
    Returns the exit record."""
    endpoints = []
    for one in connect.split(","):
        host, _, port = one.strip().rpartition(":")
        endpoints.append((host or "127.0.0.1", int(port)))
    slow = float(os.environ.get("DPV_WORKER_SLOW_MS", "0") or 0.0)
    worker = PartitionWorker(cfg, store_dir, endpoints,
                             partition=partition, partitions=partitions,
                             replica=replica, preload_hbm_gb=preload_hbm_gb,
                             slow_ms=slow)
    ready = {
        "partition_worker": worker.partition,
        "partitions": worker.partitions,
        "replica": worker.replica,
        "gateways": len(endpoints),
        "shards": list(worker.spec.shard_indices),
        "rows": worker.spec.rows,
        "pid": os.getpid(),
    }
    print(json.dumps(ready, sort_keys=True), flush=True)
    worker.run()
    return ready
