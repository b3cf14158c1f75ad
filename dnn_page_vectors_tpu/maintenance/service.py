"""The background maintenance service (docs/MAINTENANCE.md).

`MaintenanceService` supervises one worker thread per pillar, each polling
its trigger every `maintenance.interval_s` seconds and running its job
under one shared mutation lock (two pillars must never interleave manifest
flips):

  * **compactor** — when the chain's tombstone density crosses
    `maintenance.compact_tombstone_density`, fold the generation chain
    into a fresh compacted base (maintenance/compact.py), rebuild the IVF
    index over it when one exists, hot-swap the serving view, then purge
    the old chain's bytes;
  * **rebuilder** — when a drift rebuild was deferred off the refresh()
    path (`serve.index_rebuild_pending`, docs/UPDATES.md) or the live
    index degraded to exact, build the next index generation BESIDE the
    live one (`IVFIndex.build(dirname=...)` reusing the recorded
    pq/balance config), flip the store's index-dir pointer atomically,
    and hot-swap via the existing `_ServeView` refresh — a drift rebuild
    never again blocks an append or a query;
  * **janitor** — sweep expired append leases, stale index generations
    (dirs the pointer moved off), and compaction debris a crashed run
    left behind. Old artifacts are deleted one full cycle after they go
    stale, so in-flight readers on the previous view never lose a file
    mid-query;
  * **migrator** (docs/MAINTENANCE.md "Rolling model migration") — once
    armed via `request_migration`, re-embed the live store to a new model
    step one unit per pass (base, then each generation, oldest first)
    through `MigrationPlan`, hot-swapping the serving view between units
    so the fleet walks through the stamp flip with no restarts; on
    completion rebuild the index over the new stamp and let the serving
    refresh retire the old tower;
  * **autoscaler** (docs/SCALING.md "Scale-out tier") — ladder the
    worker-fleet size off the serving telemetry: windowed queue-wait p99
    or deadline-shed rate over the up-thresholds spawns the next tail
    worker, sustained calm drains the highest one — acting through
    operator-attached hooks (`attach_scaler`), observable-only without
    them, and rate-limited by `maintenance.autoscale_cooldown_s` so a
    resize's own dip never reads as fresh pressure.

Every mutation goes through the manifest writers (`_write_shard_files`,
`_atomic_dump`, `set_index_dir`); worker exceptions are counted
(`maintenance_<pillar>_errors`), logged, and never kill the worker. The
service is driven by `cli maintain [--once]`, or attached in-process to a
`SearchService` via `start_maintenance()` — which also moves drift
rebuilds off the refresh path (`maintenance.bg_rebuild`).

API: `start()` (spawn the workers, idempotent), `pause()`/`resume()`
(freeze/unfreeze trigger checks), `drain()` (block until in-flight jobs
finish), `run_once()` (one synchronous pass of all three pillars — works
with or without the threads), `close()` (stop + join).
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
import threading
import time
from typing import Callable, Dict, Optional

from dnn_page_vectors_tpu.infer.vector_store import VectorStore
from dnn_page_vectors_tpu.maintenance.compact import (
    compact_store, purge_stale)
from dnn_page_vectors_tpu.maintenance.lease import expire_stale_lease
from dnn_page_vectors_tpu.maintenance.migrate import MigrationPlan
from dnn_page_vectors_tpu.utils import faults, telemetry

_INDEX_DIR_RE = re.compile(r"^ivf(-\d+)?$")


def _next_index_dirname(current: str) -> str:
    """ivf -> ivf-0001 -> ivf-0002 ... (the next index generation's home,
    built beside the live one and pointer-flipped in)."""
    m = re.match(r"^ivf-(\d+)$", current)
    return f"ivf-{(int(m.group(1)) if m else 0) + 1:04d}"


class MaintenanceService:
    """Supervised pillar workers over one store (docs/MAINTENANCE.md).

    `svc` (optional) attaches a live `SearchService`: its registry carries
    the maintenance instruments, completed swaps hot-swap the serving view
    through `svc.refresh()`, and background rebuilds count into the
    service's `full_rebuilds` — the acceptance pin that rebuilds happen
    ONLY here, never on the refresh caller."""

    PILLARS = ("compaction", "rebuild", "janitor", "autoscale", "migrate")

    def __init__(self, cfg, store_dir: str, mesh, svc=None, registry=None):
        self._cfg = cfg
        self._store_dir = store_dir
        self._mesh = mesh
        self._svc = svc
        self.registry = registry or (
            svc.registry if svc is not None
            else telemetry.default_registry())
        m = getattr(cfg, "maintenance", None)
        self._density = (getattr(m, "compact_tombstone_density", 0.2)
                         if m is not None else 0.2)
        self._interval_s = (getattr(m, "interval_s", 5.0)
                            if m is not None else 5.0)
        # autoscale pillar knobs (docs/SCALING.md "Scale-out tier")
        self._as_on = bool(getattr(m, "autoscale", False)
                           if m is not None else False)
        self._as_min = int(getattr(m, "autoscale_min_workers", 1)
                           if m is not None else 1)
        self._as_max = int(getattr(m, "autoscale_max_workers", 4)
                           if m is not None else 4)
        self._as_up_queue = float(
            getattr(m, "autoscale_up_queue_p99_ms", 50.0)
            if m is not None else 50.0)
        self._as_up_shed = float(
            getattr(m, "autoscale_up_shed_rate", 0.5)
            if m is not None else 0.5)
        self._as_down_queue = float(
            getattr(m, "autoscale_down_queue_p99_ms", 5.0)
            if m is not None else 5.0)
        self._as_cooldown_s = float(
            getattr(m, "autoscale_cooldown_s", 30.0)
            if m is not None else 30.0)
        # scaling acts only through operator-attached hooks; without
        # them the pillar still evaluates and emits events (the policy
        # is observable before it is trusted). All three are touched
        # only under the mutation lock (the pillar job) or before
        # start() — attach_scaler is a wiring call, not a hot path.
        self._spawn_hook: Optional[Callable[[int], None]] = None
        self._drain_hook: Optional[Callable[[int], None]] = None
        self._size_hook: Optional[Callable[[], int]] = None
        self._last_scale_t: Optional[float] = None
        # migrate pillar knobs (docs/MAINTENANCE.md "Rolling model
        # migration")
        mg = getattr(cfg, "migrate", None)
        self._mig_batch_rows = int(getattr(mg, "batch_rows", 4096)
                                   if mg is not None else 4096)
        self._mig_units = int(getattr(mg, "units_per_pass", 1)
                              if mg is not None else 1)
        self._mig_purge = bool(getattr(mg, "purge", True)
                               if mg is not None else True)
        self._migrate_req: Optional[Dict] = None   # guarded-by: _mlock
        # injectable for the fake-clock pillar-ladder tests
        self._clock: Callable[[], float] = time.monotonic
        self._lock = threading.Lock()
        # one mutation at a time across pillars AND run_once (re-entrant:
        # run_once drives all three jobs under one hold). The mutation
        # lock is the OUTER layer of the hierarchy — stats/fault counters
        # nest under it, never the reverse (graftcheck lock-order):
        # lock-order: MaintenanceService._mlock < MaintenanceService._lock
        # lock-order: MaintenanceService._mlock < faults._COUNTER_LOCK
        self._mlock = threading.RLock()
        self._stop = threading.Event()
        self._threads: list = []
        self._paused = False                  # guarded-by: _lock
        self._busy = 0                        # guarded-by: _lock
        self._stats: Dict[str, int] = {}      # guarded-by: _lock
        self._last: Dict[str, Dict] = {}      # guarded-by: _lock

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "MaintenanceService":
        """Spawn one worker per pillar (idempotent)."""
        if self._threads:
            return self
        for name, job in (("compaction", self._compact_once),
                          ("rebuild", self._rebuild_once),
                          ("janitor", self._janitor_once),
                          ("autoscale", self._autoscale_once),
                          ("migrate", self._migrate_once)):
            t = threading.Thread(target=self._run_worker, args=(name, job),
                                 daemon=True, name=f"maint-{name}")
            self._threads.append(t)
            t.start()
        return self

    def attach_scaler(self, spawn: Callable[[int], None],
                      drain: Callable[[int], None],
                      size: Optional[Callable[[], int]] = None) -> None:
        """Wire the autoscale pillar's actuators: `spawn(index)` starts
        the worker for the next tail partition index, `drain(index)`
        drains the highest one (the membership-at-the-tail rule,
        docs/SCALING.md), `size()` reports the current fleet size —
        defaulting to the attached service's live-worker count. Call
        before start(); without hooks the pillar only observes."""
        self._spawn_hook = spawn
        self._drain_hook = drain
        self._size_hook = size

    def _run_worker(self, name: str, job: Callable[[], Optional[Dict]]
                    ) -> None:
        while not self._stop.wait(self._interval_s):
            with self._lock:
                paused = self._paused
            if paused:
                continue
            self._guarded_job(name, job)

    def _guarded_job(self, name: str, job: Callable[[], Optional[Dict]]
                     ) -> Optional[Dict]:
        """One supervised pillar pass: mutation lock held, exceptions
        counted and reported, never propagated into the worker loop."""
        with self._lock:
            self._busy += 1
        try:
            with self._mlock:
                res = job()
        except Exception as e:  # noqa: BLE001 — the worker must survive
            res = {"error": f"{type(e).__name__}: {e}"[:300]}
            faults.count(f"maintenance_{name}_errors")
            faults.warn(f"maintenance {name} pass failed "
                        f"({res['error']}); the worker keeps polling")
        finally:
            with self._lock:
                self._busy -= 1
        if res is not None:
            with self._lock:
                self._stats[name] = self._stats.get(name, 0) + 1
                self._last[name] = res
        return res

    def pause(self) -> None:
        """Stop triggering new jobs (in-flight ones finish; see drain)."""
        with self._lock:
            self._paused = True

    def resume(self) -> None:
        with self._lock:
            self._paused = False

    def drain(self, timeout_s: float = 60.0) -> bool:
        """Block until no pillar job is in flight. True when drained."""
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        while True:
            with self._lock:
                if self._busy == 0:
                    return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.02)

    def close(self) -> None:
        """Stop the workers and join them (drains in-flight jobs)."""
        self._stop.set()
        for t in self._threads:
            t.join()
        self._threads = []

    def run_once(self) -> Dict:
        """One synchronous pass of every pillar (janitor first so a
        crashed prior run's debris never confuses the triggers) — the
        `cli maintain --once` / loadgen-mutator / tests entry point.
        Works with or without the background threads running."""
        out: Dict[str, Dict] = {}
        with self._mlock:
            for name, job in (("janitor", self._janitor_once),
                              ("compaction", self._compact_once),
                              ("rebuild", self._rebuild_once),
                              ("migrate", self._migrate_once),
                              ("autoscale", self._autoscale_once)):
                res = self._guarded_job(name, job)
                if res is not None:
                    out[name] = res
        return out

    def stats(self) -> Dict:
        """Pass counts + each pillar's last result (telemetry snapshot)."""
        with self._lock:
            return {"passes": dict(self._stats),
                    "last": {k: dict(v) for k, v in self._last.items()}}

    # -- pillar: generation compaction -------------------------------------
    def _compact_once(self) -> Optional[Dict]:
        # trigger check on an unverified handle (a CRC sweep per poll
        # would re-read every shard's bytes every interval_s); the
        # compaction itself re-opens WITH the verify gate
        store = VectorStore(self._store_dir, verify=False)
        ms = store.maintenance_stats()
        reg = self.registry
        reg.gauge("maintenance.tombstone_density").set(
            ms["tombstone_density"])
        reg.gauge("maintenance.dead_rows").set(ms["dead_rows"])
        reg.gauge("maintenance.reclaimable_bytes").set(
            ms["reclaimable_bytes"])
        if (store.migration is not None
                or store.chain_generation <= store.compacted_through
                or ms["tombstone_density"] < self._density):
            # mid-migration, folding would mix stamps within one shard —
            # the migrate pillar owns the store until the completion flip
            return None
        store = VectorStore(self._store_dir)     # verified handle
        had_index = os.path.exists(os.path.join(
            store.directory, store.index_dirname, "manifest.json"))
        stats = compact_store(store, registry=reg)
        if stats.get("action") != "compacted":
            return stats
        if had_index:
            # rebuild over the compacted base BEFORE the serving refresh:
            # the view swap then lands store + index together, with no
            # degraded-to-exact window in between
            stats["index_rebuild"] = self._swap_index(
                store, reason=f"generation compaction epoch "
                              f"{stats['epoch']}", refresh=False)
        if self._svc is not None:
            info = self._svc.refresh()
            stats["refresh_swap_ms"] = info.get("swap_ms")
            if "partitions" in info:
                # partitioned service (docs/SCALING.md): the compacted
                # base rolled in partition by partition — queries on the
                # other partitions never waited on this one's restage
                stats["partitions_refreshed"] = len(info["partitions"])
        # reclaim only after the serving view moved over — in-flight
        # buckets on the old view finished during the refresh swap
        stats["purged"] = purge_stale(store, stats)
        stats.pop("stale_dirs", None)
        stats.pop("stale_files", None)
        return stats

    # -- pillar: off-path index rebuilds -----------------------------------
    def _rebuild_once(self) -> Optional[Dict]:
        svc = self._svc
        if VectorStore(self._store_dir, verify=False).migration is not None:
            # an index built mid-migration would span two encoders'
            # geometries; serving runs exact on mixed-stamp views and the
            # migrate pillar rebuilds at the completion flip
            return None
        reason = None
        if svc is not None:
            if svc._serve_index != "ivf":
                return None
            pending = svc.registry.gauge(
                "serve.index_rebuild_pending").value > 0
            err = svc._view.index_error
            store0 = VectorStore(self._store_dir, verify=False)
            has_manifest = os.path.exists(os.path.join(
                store0.directory, store0.index_dirname, "manifest.json"))
            if pending:
                reason = "drift rebuild deferred off the refresh path"
            elif err is not None and has_manifest:
                reason = f"live index degraded ({err[:120]})"
        else:
            store0 = VectorStore(self._store_dir, verify=False)
            mpath = os.path.join(store0.directory, store0.index_dirname,
                                 "manifest.json")
            if os.path.exists(mpath):
                reason = self._standalone_rebuild_reason(store0, mpath)
        if reason is None:
            return None
        store = VectorStore(self._store_dir)
        if store.num_vectors == 0:
            return None
        return self._swap_index(store, reason=reason)

    def _standalone_rebuild_reason(self, store,
                                   mpath: str) -> Optional[str]:
        """Without a live service, decide from the on-disk index: drift
        past updates.rebuild_drift, or structural staleness open() would
        reject (compaction, quarantine, re-stamp)."""
        from dnn_page_vectors_tpu.index.ivf import (
            IndexUnavailable, IVFIndex)
        try:
            with open(mpath) as f:
                man = json.load(f)
        except (OSError, ValueError):
            return "torn index manifest"
        drift = (int(man.get("appended_since_build", 0))
                 / max(store.num_vectors, 1))
        limit = getattr(getattr(self._cfg, "updates", None),
                        "rebuild_drift", 0.25)
        if drift > limit:
            return f"drift {drift:.3f} > rebuild_drift {limit}"
        try:
            IVFIndex.open(store, verify=True)
        except IndexUnavailable as e:
            return f"index unavailable ({str(e)[:120]})"
        except Exception as e:  # noqa: BLE001 — unreadable = rebuild
            return f"index unreadable ({type(e).__name__})"
        return None

    def _swap_index(self, store, reason: str,
                    refresh: bool = True) -> Dict:
        """Build the next index generation beside the live one, flip the
        store's index-dir pointer atomically, and (with a service
        attached) hot-swap the serving view. The old index directory is
        left on disk for the janitor — a reader on the previous view may
        still be mmap-ing its code files."""
        from dnn_page_vectors_tpu.index.ivf import IVFIndex
        faults.active().check("bg_rebuild")
        old_name = store.index_dirname
        old_man: Dict = {}
        mpath = os.path.join(store.directory, old_name, "manifest.json")
        if os.path.exists(mpath):
            try:
                with open(mpath) as f:
                    old_man = json.load(f)
            except (OSError, ValueError):
                old_man = {}
        next_name = _next_index_dirname(old_name)
        serve = self._cfg.serve
        pq_cfg = old_man.get("pq") or {}
        t0 = time.perf_counter()
        idx = IVFIndex.build(
            store, self._mesh, nlist=getattr(serve, "nlist", 0),
            iters=getattr(serve, "kmeans_iters", 8),
            seed=self._cfg.data.seed,
            init=getattr(serve, "kmeans_init", "kmeans++"),
            balance=old_man.get("balance",
                                getattr(serve, "kmeans_balance", 0.0)),
            pq_m=pq_cfg.get("m", 0), pq_iters=pq_cfg.get("iters", 8),
            opq_iters=pq_cfg.get("opq_iters", 3), dirname=next_name)
        build_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        store.set_index_dir(next_name)       # THE pointer flip
        rb = {"reason": reason[:200], "dirname": next_name,
              "nlist": idx.nlist, "build_seconds": round(build_s, 3)}
        if refresh and self._svc is not None:
            rinfo = self._svc.refresh()
            if "partitions" in rinfo:
                # each partition re-opened its restricted view of the new
                # index generation in turn (rolling swap, docs/SCALING.md)
                rb["partitions_refreshed"] = len(rinfo["partitions"])
        if self._svc is not None:
            self._svc._m_rebuilds.inc()
            self._svc.registry.gauge("serve.index_rebuild_pending").set(0.0)
        rb["swap_ms"] = round((time.perf_counter() - t1) * 1000.0, 3)
        self.registry.counter("maintenance.bg_rebuilds").inc()
        self.registry.gauge("maintenance.bg_rebuild_swap_ms").set(
            rb["swap_ms"])
        self.registry.event("index_rebuild_bg", rb)
        faults.count("index_bg_rebuilds")
        return rb

    # -- pillar: rolling model migration -----------------------------------
    def request_migration(self, to_step: int, corpus, embedder) -> None:
        """Arm the migrate pillar: re-embed the store to `to_step` with
        `embedder` (built over the NEW model's params) reading page text
        from `corpus`. The pillar then sweeps one unit per pass, hot-
        swapping the serving view between units; with a service attached
        its query path goes dual-stamp immediately (begin_migration) so
        queries route per shard stamp mid-sweep."""
        with self._mlock:
            self._migrate_req = {"to_step": int(to_step), "corpus": corpus,
                                 "embedder": embedder}
        if self._svc is not None:
            self._svc.begin_migration(embedder.params, int(to_step))

    def _migrate_once(self) -> Optional[Dict]:   # holds-lock: _mlock
        req = self._migrate_req
        if req is None:
            return None
        store = VectorStore(self._store_dir)      # verified handle
        plan = MigrationPlan(store, req["corpus"], req["embedder"],
                             req["to_step"], registry=self.registry,
                             batch_rows=self._mig_batch_rows)
        begun = plan.begin()
        if begun.get("action") == "noop":
            self._migrate_req = None
            return begun
        units = plan.pending_units()
        if units:
            out: Dict = {**begun, "action": "migrating"}
            out["units"], out["rows"], stale = [], 0, []
            for unit in units[: self._mig_units]:
                st = plan.migrate_unit(unit)
                out["units"].append(int(unit))
                out["rows"] += int(st.get("rows", 0))
                stale += st.get("stale_files", [])
            if self._svc is not None:
                # the fleet walks onto the re-embedded unit now — the
                # epoch bump rides the same refresh generation gate every
                # other manifest flip uses
                info = self._svc.refresh()
                out["refresh_swap_ms"] = info.get("swap_ms")
            if self._mig_purge:
                # superseded old-stamp bytes, reclaimed only after the
                # serving view moved over (same rule as compaction)
                out["purged"] = purge_stale(store, {"stale_files": stale})
            return out
        fin = plan.complete()
        if fin is None:
            return None
        had_index = os.path.exists(os.path.join(
            store.directory, store.index_dirname, "manifest.json"))
        if had_index:
            # rebuild over the NEW stamp before the final refresh: ANN ran
            # degraded-to-exact through the dual-stamp window, and the
            # completion swap lands stamp + index together
            fin["index_rebuild"] = self._swap_index(
                store, reason=f"model migration to step {req['to_step']}",
                refresh=False)
        if self._svc is not None:
            # this refresh adopts the new query tower and unloads the old
            # one (SearchService.refresh, docs/SERVING.md)
            info = self._svc.refresh()
            fin["refresh_swap_ms"] = info.get("swap_ms")
        self._migrate_req = None
        return fin

    # -- pillar: autoscale (docs/SCALING.md "Scale-out tier") --------------
    def _autoscale_once(self) -> Optional[Dict]:
        """One policy evaluation: read the windowed pressure signals off
        the attached service, ladder them against the thresholds, and —
        inside the fleet-size bounds, outside the cooldown — act through
        the attached hooks. Spawn targets the next tail partition index,
        drain the highest (membership changes at the TAIL, so the
        gateway's contiguity rule re-cuts the split); both emit their
        event whether or not a hook is attached."""
        if not self._as_on:
            return None
        svc = self._svc
        if svc is None:
            return None
        sig = svc.autoscale_signals()
        reg = self.registry
        reg.gauge("maintenance.autoscale_queue_p99_ms").set(
            sig["queue_wait_p99_ms"])
        reg.gauge("maintenance.autoscale_shed_rate").set(sig["shed_rate"])
        if self._size_hook is not None:
            size = int(self._size_hook())
        elif getattr(svc, "_fanout", None) is not None:
            size = len(svc._fanout.live_workers())
        else:
            return None       # no fleet to size
        # the queue-p99 trigger needs a populated window (the same >= 4
        # floor the admission door uses before trusting the percentile);
        # the shed-rate trigger is already evidence by itself
        queue_hot = (sig["queue_wait_samples"] >= 4
                     and sig["queue_wait_p99_ms"] >= self._as_up_queue)
        shed_hot = sig["shed_rate"] >= self._as_up_shed
        calm = (sig["queue_wait_p99_ms"] <= self._as_down_queue
                and sig["shed_rate"] == 0.0)
        decision = None
        if (queue_hot or shed_hot) and size < self._as_max:
            decision = "up"
        elif calm and size > self._as_min:
            decision = "down"
        if decision is None:
            return None
        now = self._clock()
        if (self._last_scale_t is not None
                and now - self._last_scale_t < self._as_cooldown_s):
            return None       # cooling down: the last resize must settle
        attrs = {"workers": size,
                 "queue_wait_p99_ms": sig["queue_wait_p99_ms"],
                 "shed_rate": sig["shed_rate"]}
        if decision == "up":
            acted = self._spawn_hook is not None
            if acted:
                self._spawn_hook(size)        # the next tail index
            reg.event("autoscale_up", dict(
                attrs, to_workers=size + 1, acted=acted,
                trigger="queue_wait" if queue_hot else "shed_rate"))
        else:
            acted = self._drain_hook is not None
            if acted:
                self._drain_hook(size - 1)    # the highest index drains
            reg.event("autoscale_down", dict(
                attrs, to_workers=size - 1, acted=acted))
        reg.counter("maintenance.autoscale_decisions").inc()
        self._last_scale_t = now
        return {"decision": decision, "workers": size, "acted": acted,
                **{k: sig[k] for k in ("queue_wait_p99_ms", "shed_rate")}}

    # -- pillar: janitor ---------------------------------------------------
    def _janitor_once(self) -> Optional[Dict]:
        store = VectorStore(self._store_dir, verify=False)
        out = {"lease_expired": False, "index_dirs_removed": 0,
               "migrate_dirs_removed": 0, "purged_dirs": 0,
               "purged_files": 0}
        if expire_stale_lease(store, registry=self.registry):
            out["lease_expired"] = True
            self.registry.counter("maintenance.leases_expired").inc()
        cur = store.index_dirname
        live_idx = os.path.join(store.directory, cur)
        for path in sorted(glob.glob(os.path.join(store.directory,
                                                  "ivf*"))):
            name = os.path.basename(path)
            if (path == live_idx or not os.path.isdir(path)
                    or not _INDEX_DIR_RE.match(name)):
                continue
            shutil.rmtree(path, ignore_errors=True)
            out["index_dirs_removed"] += 1
        # migration unit dirs no manifest references any more: a crashed
        # attempt's torn unit, or a unit a later migration/compaction
        # superseded (docs/MAINTENANCE.md "Rolling model migration")
        ref_dirs = {os.path.dirname(e[k]) for e in store.shards()
                    for k in ("vec", "ids", "scl") if k in e}
        for path in sorted(glob.glob(os.path.join(store.directory,
                                                  "migrate-*"))):
            if (os.path.isdir(path)
                    and os.path.basename(path) not in ref_dirs):
                shutil.rmtree(path, ignore_errors=True)
                out["migrate_dirs_removed"] += 1
        epoch = store.compacted_through
        if epoch:
            referenced = {os.path.dirname(e[k]) for e in store.shards()
                          for k in ("vec", "ids", "scl") if k in e}
            ref_files = {e[k] for e in store.shards()
                         for k in ("vec", "ids", "scl")
                         if k in e and os.path.dirname(e[k]) == ""}
            stale = {"stale_dirs": [], "stale_files": []}
            for path in glob.glob(os.path.join(store.directory, "gen-*")):
                m = re.match(r"^gen-(\d+)$", os.path.basename(path))
                if m and int(m.group(1)) <= epoch and os.path.isdir(path):
                    stale["stale_dirs"].append(path)
            for path in glob.glob(os.path.join(store.directory,
                                               "compact-*")):
                if (os.path.isdir(path)
                        and os.path.basename(path) not in referenced):
                    stale["stale_dirs"].append(path)
            for path in glob.glob(os.path.join(store.directory,
                                               "shard_*.npy")):
                if os.path.basename(path) not in ref_files:
                    stale["stale_files"].append(path)
            purged = purge_stale(store, stale)
            out["purged_dirs"] = purged["purged_dirs"]
            out["purged_files"] = purged["purged_files"]
        return out if any(out.values()) else None
