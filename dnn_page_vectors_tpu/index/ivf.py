"""IVF inverted-file ANN index over the vector store (docs/ANN.md).

Every retrieval path used to pay O(corpus) per query through
`ops/topk.py:topk_over_store`. This index makes retrieval sublinear the
canonical way (Jegou et al. 2011; Johnson et al. 2017 / faiss): a coarse
k-means quantizer (index/kmeans.py, trained on the MXU over streamed store
shards) partitions the store's rows into `nlist` inverted lists; a query
scores the tiny [nlist, D] centroid matrix on device, gathers only the
rows of its top-`nprobe` lists from the store's memory-mapped shards (int8
codes at stored width — dequant fuses into the re-rank matmul), and
exact-reranks that candidate block with `ops.topk.rerank_candidates`.
Recall-vs-exact is a measured contract (`evals.recall.recall_vs_exact`,
pinned by tests/test_ivf_index.py), not a hope.

Layout (next to the store, same manifest machinery as VectorStore):

  <store>/ivf/manifest.json     nlist, dim, model_step stamp, seed, per-file
                                byte sizes + CRC32s, per-shard posting table,
                                optional "pq" section (m, ksub, opq config)
  <store>/ivf/centroids.npy     [nlist, D] float32 unit-norm centroids
  <store>/ivf/posting_NNNNN.ord.npy   [count] int32 shard-row order, grouped
                                      by centroid (CSR values)
  <store>/ivf/posting_NNNNN.off.npy   [nlist+1] int64 CSR offsets
  <store>/ivf/pq_rotation.npy   [D, D] f32 OPQ rotation       (PQ builds)
  <store>/ivf/pq_codebooks.npy  [m, ksub, dsub] f32 codebooks (PQ builds)
  <store>/ivf/posting_NNNNN.pqc.npy   [count, m] uint8 PQ codes, SHARD ROW
                                      order (gathered through .ord like the
                                      store rows themselves)

Compressed payloads (index/pq.py, docs/ANN.md): a PQ build additionally
trains an OPQ rotation + per-subspace codebooks on the same streamed,
seeded k-means machinery and stores m-byte codes per row. `search` then
runs ADC — per-query lookup tables computed on device, candidates scored
from m-byte codes instead of stored-width rows, a running on-device top-r
per query — and keeps the EXACT re-rank from the store for the final
top-k (only the ~r surviving rows per query are gathered at stored
width), so the recall contract is measured on true scores while the
candidate gather moves ~m bytes/row. `stage_hot` pins the largest lists'
codes (plus their list/id metadata) in device memory so resident lists
skip the per-request host gather entirely; the non-resident tail still
reads the mmap (infer/serve.py wires the budget).

Validity contract (docs/ROBUSTNESS.md semantics): `open()` re-checks the
recorded model step against the store's stamp, the recorded shard table
(index, count) against the store's live one, and every file's bytes+CRC32.
A stale index (ensure_model_step re-stamp, re-embed, shard quarantine)
raises `IndexUnavailable`; a corrupt file is quarantined (renamed aside,
counted in the fault counters) and the index reports unavailable — callers
(SearchService, eval, mine) fall back to the exact brute-force path
per request, visibly, and `cli index` rebuilds.

Live updates (docs/UPDATES.md): a store APPEND (new generation of shards)
makes the recorded table a strict subset of the live one — `update()`
extends the index in O(new shards) by assigning only the unrecorded shards
to the existing centroids and appending their posting files, until the
drift (corpus fraction appended since the last full k-means,
`updates.rebuild_drift`) forces a fresh build. Tombstoned rows stay in
their posting lists; the store's read-time id masking turns them into
dead (-1) candidates the re-rank already drops.
"""
from __future__ import annotations

import json
import math
import os
import time
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from dnn_page_vectors_tpu.index.kmeans import assign_store, train_kmeans
from dnn_page_vectors_tpu.index.pq import PQCodec, adc_topr, train_pq
from dnn_page_vectors_tpu.infer.vector_store import crc_file
from dnn_page_vectors_tpu.ops.topk import (
    chunked_topk, rerank_candidates, rerank_positions)
from dnn_page_vectors_tpu.utils import faults, telemetry

DIRNAME = "ivf"
MANIFEST = "manifest.json"


class IndexUnavailable(RuntimeError):
    """The IVF index cannot serve (missing / stale / quarantined). Callers
    catch this and fall back to exact search — it is a routing signal, not
    a crash."""


def index_dir(store) -> str:
    """The LIVE index directory: the store manifest's `index_dir` pointer
    ("ivf" by default). A background rebuild (docs/MAINTENANCE.md) builds
    the next index generation into a sibling dir and flips the pointer
    atomically — readers never observe a half-written index."""
    return os.path.join(store.directory,
                        getattr(store, "index_dirname", DIRNAME))


def auto_nlist(num_vectors: int) -> int:
    """Default list count: ~sqrt(N) (the standard IVF operating point),
    clamped so tiny toy stores still get a few multi-row lists and huge
    stores don't pay a megarow centroid scan."""
    return max(4, min(int(math.isqrt(max(num_vectors, 1))), 65_536,
                      max(num_vectors, 1)))


def _bucket(n: int, lo: int) -> int:
    """Next power of two >= max(n, lo): one compiled shape per octave, so
    varying candidate/query counts don't retrace every call."""
    return 1 << max(int(math.ceil(math.log2(max(n, 1)))), int(lo - 1).bit_length())


def _write_npy(path: str, arr: np.ndarray) -> Tuple[int, int]:
    """Durable seeded-fault-aware array write (the write_shard pattern):
    bytes land + fsync, size+CRC recorded from the written bytes, and the
    post-fsync corruption hook fires AFTER the record — so injected rot is
    caught by the verify gate, not hidden by the writer."""
    plan = faults.active()

    def _w():
        plan.check("index_write")
        np.save(path, arr)
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)

    faults.retry(_w, op="index_write")
    rec = (os.path.getsize(path), crc_file(path))
    plan.corrupt("index_file", path)
    return rec


def _atomic_dump(obj, path: str) -> None:
    plan = faults.active()

    def _dump():
        plan.check("index_write")
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    faults.retry(_dump, op="index_write")


class IVFIndex:
    def __init__(self, store, manifest: Dict, centroids: np.ndarray,
                 postings: Dict[int, Tuple[np.ndarray, np.ndarray]],
                 pq: Optional[PQCodec] = None):
        self.store = store
        self.manifest = manifest
        self.centroids = centroids                 # [nlist, D] f32
        self._postings = postings                  # {shard: (order, offsets)}
        self._entries = {s["index"]: s for s in store.shards()}
        self._meta = {s["index"]: s for s in manifest["shards"]}
        self._raw: Dict[int, tuple] = {}           # lazy mmap cache
        self._codes: Dict[int, np.ndarray] = {}    # lazy PQ code mmaps
        self._attrs: Dict[int, np.ndarray] = {}    # lazy attr-word arrays
        self._dev_centroids = None
        self.pq = pq                               # OPQ+PQ codec or None
        self._hot = None                           # stage_hot device state
        # total rows per list across shards: candidate accounting without
        # touching the postings at search time
        sizes = np.zeros((self.nlist,), np.int64)
        for _, offsets in postings.values():
            sizes += np.diff(offsets)
        self.list_sizes = sizes
        self.stats = {"searches": 0, "lists_scanned": 0,
                      "candidates_reranked": 0, "gather_bytes": 0,
                      "reranked_rows": 0, "hot_rows_scored": 0,
                      "filter_escalations": 0}
        # windowed per-list popularity table (docs/ANN.md "Popularity
        # tiering"): every search adds its probed-list histogram here,
        # and stage_hot ranks by it — then HALVES it, so the resident
        # hot set tracks the current Zipf head instead of raw list size.
        # Approximate like `stats`: racing increments may drop a count,
        # never corrupt the ranking.
        # graftcheck: off=locks -- approximate telemetry, single array
        # rebind on decay; a lost increment only nudges the ranking
        self.scan_counts = np.zeros((self.nlist,), np.int64)

    # -- identity ----------------------------------------------------------
    @property
    def nlist(self) -> int:
        return int(self.manifest["nlist"])

    @property
    def model_step(self) -> Optional[int]:
        return self.manifest.get("model_step")

    @property
    def imbalance(self) -> float:
        return float(self.manifest.get("imbalance", 0.0))

    @property
    def index_generation(self) -> int:
        """Incremental updates applied since the last full k-means build
        (0 = freshly built; docs/UPDATES.md)."""
        return int(self.manifest.get("index_generation", 0))

    @property
    def pq_m(self) -> int:
        """PQ subspace count — bytes per posting code row (0 =
        uncompressed stored-width postings)."""
        return int((self.manifest.get("pq") or {}).get("m", 0))

    @property
    def hot_rows(self) -> int:
        """Rows resident in the staged hot posting set (0 = not staged)."""
        return 0 if self._hot is None else int(self._hot["rows"])

    # -- build -------------------------------------------------------------
    @staticmethod
    def _balance_assignments(tops: np.ndarray, nlist: int, cap: int
                             ) -> np.ndarray:
        """Deterministic capacity-capped assignment over the FULL row set
        (docs/ANN.md, the balanced-init ROADMAP item): every row starts on
        its best centroid; a list holding more than `cap` rows keeps its
        first `cap` (stable global row order) and spills the rest to each
        row's next-ranked choice, for choices-1 rounds. Rows that exhaust
        their choices stay where they are (soft cap) — recall never
        depends on the cap, only which list a row waits in. `tops` is
        [N, C] ranked centroid choices; returns the final [N] assignment."""
        n, n_choices = tops.shape
        cur = tops[:, 0].copy()
        level = np.zeros((n,), np.int64)
        for _ in range(max(1, n_choices - 1)):
            order = np.argsort(cur, kind="stable")      # group rows by list
            grouped = cur[order]
            starts = np.searchsorted(grouped, np.arange(nlist))
            rank = np.arange(n) - starts[grouped]
            overflow = order[rank >= cap]
            movable = overflow[level[overflow] < n_choices - 1]
            if movable.size == 0:
                break
            level[movable] += 1
            cur[movable] = tops[movable, level[movable]]
        return cur

    @classmethod
    def _assign_postings(cls, d: str, store, mesh, centroids: np.ndarray,
                         entries, chunk: int, balance_cap: int = 0,
                         choices: int = 4):
        """Assign `entries`' rows to `centroids` and persist their CSR
        posting files. Returns (shards_meta, postings, sizes [nlist],
        sizes_raw [nlist]) for exactly those entries — build runs it over
        the whole store, update() over only the new generation's shards.
        With `balance_cap` > 0 the sweep takes each row's top-`choices`
        centroids, rebalances globally (memory O(N * choices) host — the
        opt-in price of the cap), and sizes_raw reports the pre-balance
        first-choice counts so the imbalance delta is measurable."""
        nlist = centroids.shape[0]
        shards_meta = []
        postings: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        sizes = np.zeros((nlist,), np.int64)
        sizes_raw = np.zeros((nlist,), np.int64)
        nonzero = [e for e in entries if e["count"] > 0]
        per_shard = assign_store(
            store, mesh, centroids, chunk=chunk, entries=nonzero,
            choices=choices if balance_cap > 0 else 1)
        if balance_cap > 0:
            collected = list(per_shard)
            tops = (np.concatenate([a for _, a in collected])
                    if collected else np.zeros((0, choices), np.int32))
            sizes_raw += np.bincount(tops[:, 0], minlength=nlist) \
                if tops.size else 0
            flat = cls._balance_assignments(tops, nlist, balance_cap)
            out, lo = [], 0
            for entry, a in collected:
                out.append((entry, flat[lo: lo + a.shape[0]]))
                lo += a.shape[0]
            per_shard = out
        for entry, assign in per_shard:
            order = np.argsort(assign, kind="stable").astype(np.int32)
            counts = np.bincount(assign, minlength=nlist)
            offsets = np.zeros((nlist + 1,), np.int64)
            offsets[1:] = np.cumsum(counts)
            sizes += counts
            if balance_cap <= 0:
                sizes_raw += counts
            stem = f"posting_{entry['index']:05d}"
            ob, oc = _write_npy(os.path.join(d, stem + ".ord.npy"), order)
            fb, fc = _write_npy(os.path.join(d, stem + ".off.npy"), offsets)
            shards_meta.append({
                "index": entry["index"], "count": int(entry["count"]),
                "ord": stem + ".ord.npy", "off": stem + ".off.npy",
                "bytes": {"ord": ob, "off": fb},
                "crc": {"ord": oc, "off": fc}})
            postings[entry["index"]] = (order, offsets)
        # zero-count shards carry no postings but must stay in the recorded
        # table, or open() would read an honest store change into them
        for entry in entries:
            if entry["count"] == 0:
                shards_meta.append({"index": entry["index"], "count": 0})
        return shards_meta, postings, sizes, sizes_raw

    @staticmethod
    def _encode_codes(d: str, store, codec: PQCodec, shards_meta) -> None:
        """Encode each recorded shard's rows into its PQ code file
        (posting_NNNNN.pqc.npy, shard ROW order — gathered through the
        same .ord indices as the store rows) and extend the shard meta
        in place with the pqc byte/CRC record. Streams one shard at a
        time; update() calls this with only the new shards' meta."""
        entries = {s["index"]: s for s in store.shards()}
        for meta in shards_meta:
            if meta["count"] == 0 or "ord" not in meta:
                continue
            _, vecs = store._load_entry(entries[meta["index"]])
            codes = codec.encode(np.asarray(vecs, np.float32))
            name = f"posting_{meta['index']:05d}.pqc.npy"
            cb, cc = _write_npy(os.path.join(d, name), codes)
            meta["pqc"] = name
            meta["bytes"]["pqc"] = cb
            meta["crc"]["pqc"] = cc

    @classmethod
    def build(cls, store, mesh, nlist: int = 0, iters: int = 8,
              seed: int = 0, chunk: int = 8192,
              sample_per_shard: Optional[int] = None,
              init: str = "kmeans++", balance: float = 0.0,
              pq_m: int = 0, pq_iters: int = 8,
              opq_iters: int = 3,
              dirname: Optional[str] = None) -> "IVFIndex":
        """Train the quantizer, assign every store row, and persist the
        inverted file next to the store (atomic manifest last, so a crash
        mid-build leaves the previous index or none — never a torn one
        that passes verification). `balance` > 0 caps lists at
        ceil(balance * N / nlist) rows during the assignment sweep
        (overflow spills to the row's next-best centroid — docs/ANN.md).
        `pq_m` > 0 additionally trains the OPQ+PQ codec (index/pq.py) and
        persists m-byte codes per row for the ADC search path.

        `dirname` builds into an explicit sibling directory instead of
        the live pointer target — the background rebuilder's
        build-beside-then-flip protocol (docs/MAINTENANCE.md); the
        returned object should be re-opened after the pointer flip."""
        t0 = time.perf_counter()
        N = store.num_vectors
        if N == 0:
            raise ValueError("cannot build an IVF index over an empty store")
        nlist = int(nlist) if nlist and nlist > 0 else auto_nlist(N)
        nlist = min(nlist, N)
        centroids, kstats = train_kmeans(
            store, mesh, nlist, iters=iters, seed=seed, chunk=chunk,
            sample_per_shard=sample_per_shard, init=init)
        cap = (int(math.ceil(float(balance) * N / nlist))
               if balance and balance > 0 else 0)
        codec = None
        pq_stats: Optional[Dict] = None
        if pq_m:
            codec, pq_stats = train_pq(store, int(pq_m), iters=pq_iters,
                                       opq_iters=opq_iters, seed=seed)
        d = (os.path.join(store.directory, dirname) if dirname
             else index_dir(store))
        os.makedirs(d, exist_ok=True)
        cb, cc = _write_npy(os.path.join(d, "centroids.npy"), centroids)
        shards_meta, postings, sizes, sizes_raw = cls._assign_postings(
            d, store, mesh, centroids, store.shards(), chunk,
            balance_cap=cap)
        pq_section = None
        if codec is not None:
            rb, rc = _write_npy(os.path.join(d, "pq_rotation.npy"),
                                codec.rotation)
            kb, kc = _write_npy(os.path.join(d, "pq_codebooks.npy"),
                                codec.codebooks)
            cls._encode_codes(d, store, codec, shards_meta)
            pq_section = {
                **pq_stats,
                "rotation": {"file": "pq_rotation.npy",
                             "bytes": rb, "crc": rc},
                "codebooks": {"file": "pq_codebooks.npy",
                              "bytes": kb, "crc": kc},
            }
        shards_meta.sort(key=lambda s: s["index"])
        imbalance = float(nlist * np.square(sizes, dtype=np.float64).sum()
                          / max(N, 1) ** 2)
        imbalance_raw = float(
            nlist * np.square(sizes_raw, dtype=np.float64).sum()
            / max(N, 1) ** 2)
        manifest = {
            "version": 1, "nlist": nlist, "dim": store.dim,
            "dtype": store.manifest["dtype"],
            "model_step": store.model_step, "seed": int(seed),
            "iters": kstats["iters"], "reseeded": kstats["reseeded"],
            "init": kstats["init"],
            "init_imbalance": kstats["init_imbalance"],
            "num_vectors": int(N), "imbalance": round(imbalance, 4),
            # balanced-assignment record (docs/ANN.md): the cap applied in
            # the final sweep and the first-choice imbalance it improved
            # on (balance_cap 0 = pure argmax; imbalance_raw == imbalance)
            "balance": float(balance), "balance_cap": cap,
            "imbalance_raw": round(imbalance_raw, 4),
            # live-update bookkeeping (docs/UPDATES.md): rows covered by
            # the last full k-means vs rows appended incrementally since —
            # their ratio is the drift that triggers the next full rebuild
            "built_num_vectors": int(N),
            "appended_since_build": 0,
            "index_generation": 0,
            "build_seconds": round(time.perf_counter() - t0, 3),
            "centroids": {"file": "centroids.npy", "bytes": cb, "crc": cc},
            "shards": shards_meta,
        }
        if pq_section is not None:
            manifest["pq"] = pq_section
        _atomic_dump(manifest, os.path.join(d, MANIFEST))
        return cls(store, manifest, centroids, postings, pq=codec)

    # -- incremental update (docs/UPDATES.md) ------------------------------
    @classmethod
    def update(cls, store, mesh, rebuild_drift: float = 0.25,
               nlist: int = 0, iters: int = 8, seed: Optional[int] = None,
               chunk: int = 8192, init: str = "kmeans++",
               defer_rebuild: bool = False
               ) -> Tuple["IVFIndex", Dict]:
        """Bring the persisted index up to date with the store after an
        append: assign ONLY the shards the recorded table doesn't know to
        the EXISTING centroids and append their posting files — O(new
        shards), not O(corpus) — then atomically re-dump the manifest.

        Falls back to a FULL rebuild (fresh k-means) when the existing
        index can't be extended: missing/torn/corrupt files, a model-step
        re-stamp, a recorded shard that changed or vanished (quarantine /
        re-embed), or accumulated drift — the fraction of the corpus
        appended since the last full k-means — crossing `rebuild_drift`
        (stale centroids mis-assign enough new rows to erode recall).

        Returns (index, info) where info["action"] is "noop" |
        "incremental" | "rebuild" plus the decision inputs, so callers
        (SearchService.refresh, cli refresh) can count
        incremental_updates vs full_rebuilds. Raises (IOError etc.) only
        when the write path itself fails — the manifest is untouched then,
        so readers keep the previous index generation.

        PQ config is INHERITED: an index built with compressed payloads
        keeps them — incremental updates encode the new shards' codes
        with the existing rotation/codebooks (O(new shards), same as the
        posting append), and a drift rebuild retrains the codec with the
        recorded m/iters/opq settings. The balance factor is inherited
        the same way, though incremental appends assign new rows by
        plain argmax — the cap re-applies at the next full rebuild.

        `defer_rebuild` moves full rebuilds OFF this caller
        (docs/MAINTENANCE.md): a pure-drift overrun still runs the O(new
        shards) incremental append — new docs stay servable — and flags
        `info["rebuild_pending"]` for the background builder; a
        structural reason (missing/torn/stale index, changed shard table)
        raises IndexUnavailable instead of rebuilding inline, so the
        caller degrades to exact search, visibly, until the background
        rebuild hot-swaps a fresh index generation in."""
        t0 = time.perf_counter()
        d = index_dir(store)
        mpath = os.path.join(d, MANIFEST)

        def _rebuild(reason: str, man: Optional[Dict] = None
                     ) -> Tuple["IVFIndex", Dict]:
            if defer_rebuild:
                raise IndexUnavailable(
                    f"rebuild deferred to the background worker ({reason})")
            pq_cfg = (man or {}).get("pq") or {}
            idx = cls.build(store, mesh, nlist=nlist, iters=iters,
                            seed=0 if seed is None else seed, chunk=chunk,
                            init=init,
                            balance=(man or {}).get("balance", 0.0),
                            pq_m=pq_cfg.get("m", 0),
                            pq_iters=pq_cfg.get("iters", 8),
                            opq_iters=pq_cfg.get("opq_iters", 3))
            faults.count("index_full_rebuilds")
            # lifecycle event (docs/OBSERVABILITY.md): a full rebuild is
            # the expensive transition operators watch for
            telemetry.default_registry().event(
                "ivf_rebuild", {"reason": reason[:200],
                                "nlist": idx.nlist})
            return idx, {"action": "rebuild", "reason": reason,
                         "seconds": round(time.perf_counter() - t0, 3)}

        if not os.path.exists(mpath):
            return _rebuild("no index on disk")
        try:
            with open(mpath) as f:
                man = json.load(f)
        except (json.JSONDecodeError, ValueError):
            return _rebuild("torn index manifest")
        if (man.get("model_step") != store.model_step
                or man.get("dim") != store.dim):
            return _rebuild("model step / dim changed", man)
        live = store.shards()
        live_by_idx = {s["index"]: s["count"] for s in live}
        recorded = {s["index"]: s["count"] for s in man.get("shards", [])}
        if any(recorded.get(i) != c for i, c in live_by_idx.items()
               if i in recorded) or any(i not in live_by_idx
                                        for i in recorded):
            return _rebuild("recorded shards changed (quarantine/re-embed)",
                            man)
        new_entries = [e for e in live if e["index"] not in recorded]
        if not new_entries:
            return (cls.open(store),
                    {"action": "noop",
                     "seconds": round(time.perf_counter() - t0, 3)})
        try:
            cls._verify_files(d, man)      # don't extend corrupt postings
        except IndexUnavailable as e:
            return _rebuild(f"existing index unhealthy ({e})", man)
        total = store.num_vectors
        appended = (int(man.get("appended_since_build", 0))
                    + sum(e["count"] for e in new_entries))
        drift = appended / max(total, 1)
        rebuild_pending = False
        if drift > rebuild_drift:
            if not defer_rebuild:
                return _rebuild(
                    f"drift {drift:.3f} > rebuild_drift {rebuild_drift}",
                    man)
            # deferred: extend anyway (new docs must serve NOW; the stale
            # centroids cost bounded recall until the background rebuild)
            rebuild_pending = True
        centroids = np.asarray(
            np.load(os.path.join(d, man["centroids"]["file"])), np.float32)
        new_meta, _, new_sizes, _ = cls._assign_postings(
            d, store, mesh, centroids, new_entries, chunk)
        if man.get("pq"):
            # incremental CODE append: new shards encode with the existing
            # rotation/codebooks — O(new shards), like the posting append
            codec = PQCodec(
                np.load(os.path.join(d, man["pq"]["rotation"]["file"])),
                np.load(os.path.join(d, man["pq"]["codebooks"]["file"])))
            cls._encode_codes(d, store, codec, new_meta)
        man["shards"] = sorted(man["shards"] + new_meta,
                               key=lambda s: s["index"])
        man["num_vectors"] = int(total)
        man["appended_since_build"] = appended
        man["index_generation"] = int(man.get("index_generation", 0)) + 1
        # imbalance over the FULL posting set: old sizes from the small
        # [nlist+1] offset files, new from the assignment just done
        sizes = new_sizes.astype(np.float64)
        for s in man["shards"]:
            if s["count"] == 0 or s["index"] in {m["index"]
                                                 for m in new_meta}:
                continue
            off = np.load(os.path.join(d, s["off"]))
            sizes += np.diff(off)
        man["imbalance"] = round(
            float(man["nlist"] * np.square(sizes).sum()
                  / max(total, 1) ** 2), 4)
        _atomic_dump(man, mpath)
        faults.count("index_incremental_updates")
        return (cls.open(store, verify=False),
                {"action": "incremental", "new_shards": len(new_entries),
                 "appended_rows": sum(e["count"] for e in new_entries),
                 "drift": round(drift, 4),
                 "rebuild_pending": rebuild_pending,
                 "index_generation": man["index_generation"],
                 "seconds": round(time.perf_counter() - t0, 3)})

    # -- open / verify -----------------------------------------------------
    @classmethod
    def open(cls, store, verify: bool = True) -> "IVFIndex":
        """Load the persisted index, re-checking stamp, shard table, and
        bytes+CRC32. Raises IndexUnavailable (with the reason) on any
        mismatch — corrupt files are quarantined first."""
        d = index_dir(store)
        mpath = os.path.join(d, MANIFEST)
        if not os.path.exists(mpath):
            raise IndexUnavailable(
                f"no IVF index at {d} (run the 'index' command to build)")
        try:
            with open(mpath) as f:
                man = json.load(f)
        except (json.JSONDecodeError, ValueError):
            q = mpath + ".quarantined"
            os.replace(mpath, q)
            faults.count("quarantined_index_manifests")
            faults.warn(f"IVF manifest {mpath} is torn (invalid JSON); "
                        f"moved aside to {q}")
            raise IndexUnavailable(f"torn IVF manifest (quarantined to {q})")
        if man.get("model_step") != store.model_step:
            raise IndexUnavailable(
                f"stale IVF index: built at model step "
                f"{man.get('model_step')}, store is stamped "
                f"{store.model_step} (rebuild after re-embedding)")
        if man.get("dim") != store.dim:
            raise IndexUnavailable(
                f"stale IVF index: built for {man.get('dim')}-d vectors, "
                f"store holds {store.dim}-d")
        live = {s["index"]: s["count"] for s in store.shards()}
        recorded = {s["index"]: s["count"] for s in man.get("shards", [])}
        if live != recorded:
            raise IndexUnavailable(
                "stale IVF index: store shard table changed since the "
                f"build ({len(recorded)} recorded vs {len(live)} live "
                "shards or row counts differ); rebuild")
        if verify:
            cls._verify_files(d, man)
        plan = faults.active()
        centroids = np.load(os.path.join(d, man["centroids"]["file"]))
        postings: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for s in man["shards"]:
            if s["count"] == 0:
                continue
            plan.check("index_read")
            postings[s["index"]] = (
                np.load(os.path.join(d, s["ord"])),
                np.load(os.path.join(d, s["off"])))
        codec = None
        if man.get("pq"):
            codec = PQCodec(
                np.load(os.path.join(d, man["pq"]["rotation"]["file"])),
                np.load(os.path.join(d, man["pq"]["codebooks"]["file"])))
        return cls(store, man, np.asarray(centroids, np.float32), postings,
                   pq=codec)

    @staticmethod
    def _verify_files(d: str, man: Dict) -> None:
        files = [(man["centroids"]["file"], man["centroids"]["bytes"],
                  man["centroids"]["crc"])]
        for key in ("rotation", "codebooks"):
            rec = man.get("pq", {}).get(key)
            if rec is not None:
                files.append((rec["file"], rec["bytes"], rec["crc"]))
        for s in man["shards"]:
            if s["count"] == 0:
                continue
            for key in ("ord", "off") + (("pqc",) if "pqc" in s else ()):
                files.append((s[key], s["bytes"][key], s["crc"][key]))
        for name, want_bytes, want_crc in files:
            path = os.path.join(d, name)
            err = None
            if not os.path.exists(path):
                err = "missing"
            elif os.path.getsize(path) != want_bytes:
                err = (f"{os.path.getsize(path)} bytes, manifest records "
                       f"{want_bytes} (truncated?)")
            elif crc_file(path) != want_crc:
                err = "CRC mismatch (corrupt)"
            if err is None:
                continue
            if err != "missing":
                os.replace(path, path + ".quarantined")
                faults.count("quarantined_index_files")
                faults.warn(f"quarantined IVF index file {path} ({err}); "
                            "exact search serves until a rebuild")
            raise IndexUnavailable(
                f"IVF index file {name} {err}; rebuild the index")

    # -- partitioned serving (infer/partition.py, docs/SCALING.md) ---------
    def partition_view(self, shard_indices) -> "IVFIndex":
        """A serving view of this index restricted to one partition's
        shard range: the same manifest, centroids, and PQ codec, but ONLY
        the listed shards' posting files — so `search` gathers candidates
        from exactly the partition's slice of the inverted file and
        `stage_hot` pins only its rows. The centroid scan stays global
        (the [nlist, D] matrix is tiny and identical everywhere); the
        per-list candidate accounting (`list_sizes`, `stats`) is fresh
        and partition-local. Mmap caches are lazy per view, so a
        partition never touches a sibling's shard files."""
        keep = {int(s) for s in shard_indices}
        return IVFIndex(self.store, self.manifest, self.centroids,
                        {s: p for s, p in self._postings.items()
                         if s in keep}, pq=self.pq)

    # -- search ------------------------------------------------------------
    def _shard_raw(self, sidx: int):
        raw = self._raw.get(sidx)
        if raw is None:
            raw = self._raw[sidx] = self.store._load_entry(
                self._entries[sidx], raw=True)
        return raw

    def _codes_raw(self, sidx: int) -> np.ndarray:
        arr = self._codes.get(sidx)
        if arr is None:
            arr = self._codes[sidx] = np.load(
                os.path.join(index_dir(self.store),
                             self._meta[sidx]["pqc"]), mmap_mode="r")
        return arr

    def _shard_attrs(self, sidx: int) -> np.ndarray:
        """One shard's packed attribute words (uint32 [count]; zeros for
        shards written before the store's attribute table existed) —
        the filtered-retrieval prefilter's input (index/attrs.py)."""
        arr = self._attrs.get(sidx)
        if arr is None:
            arr = self._attrs[sidx] = self.store.load_attrs(
                self._entries[sidx])
        return arr

    def _gather_codes(self, cents: np.ndarray, predicate=None):
        """Candidate block for one probed-list union at CODE width: m
        bytes per row off the mmap'd pqc files instead of the stored row
        width. Returns (codes [C, m] u8, page_ids [C] i64, cand_cent [C]
        i32, src_shard [C] i32, src_row [C] i32) — the source coordinates
        let the exact re-rank fetch only the ADC survivors' rows later.
        Tombstoned rows get centroid -2 (matches no probed list), the
        same dead-slot convention as _gather. A `predicate`
        (index/attrs.py) prefilters each shard's posting rows against its
        attribute words BEFORE the code gather, so a filtered query moves
        selectivity-proportional bytes instead of post-filtering top-k."""
        c_parts, i_parts, n_parts, sh_parts, rw_parts = [], [], [], [], []
        for sidx in sorted(self._postings):
            order, offsets = self._postings[sidx]
            rows = [order[offsets[c]: offsets[c + 1]] for c in cents]
            lens = np.array([r.shape[0] for r in rows], np.int64)
            if lens.sum() == 0:
                continue
            take = np.concatenate(rows)
            cent = np.repeat(np.asarray(cents, np.int32), lens)
            if predicate is not None:
                keep = predicate.matches(self._shard_attrs(sidx)[take])
                if not keep.any():
                    continue
                take, cent = take[keep], cent[keep]
            ids, _, _ = self._shard_raw(sidx)
            taken_ids = np.asarray(ids[take], np.int64)
            c_parts.append(np.asarray(self._codes_raw(sidx)[take]))
            i_parts.append(taken_ids)
            n_parts.append(np.where(taken_ids >= 0, cent, np.int32(-2)))
            sh_parts.append(np.full((take.shape[0],), sidx, np.int32))
            rw_parts.append(take.astype(np.int32))
        if not c_parts:
            return (np.zeros((0, self.pq.m), np.uint8),
                    np.zeros((0,), np.int64), np.zeros((0,), np.int32),
                    np.zeros((0,), np.int32), np.zeros((0,), np.int32))
        return tuple(np.concatenate(p) for p in
                     (c_parts, i_parts, n_parts, sh_parts, rw_parts))

    def _fetch_rows(self, src_shard: np.ndarray, src_row: np.ndarray):
        """Stored-width rows (+ int8 scales) for an explicit (shard, row)
        set — the exact re-rank's gather: only the per-query ADC
        survivors pay row-width bytes off the store mmaps."""
        U = src_shard.shape[0]
        rows = None
        scales = None
        for sidx in np.unique(src_shard):
            _, vecs, scl = self._shard_raw(int(sidx))
            mask = src_shard == sidx
            part = np.asarray(vecs[src_row[mask]])
            if rows is None:
                rows = np.zeros((U, part.shape[1]), part.dtype)
            rows[mask] = part
            if scl is not None:
                if scales is None:
                    scales = np.zeros((U,), np.float16)
                scales[mask] = np.asarray(scl[src_row[mask]])
        return rows, scales

    # -- HBM-resident hot posting set (docs/ANN.md, infer/serve.py) --------
    def stage_hot(self, budget_bytes: float) -> Dict:
        """Pin the largest posting lists' PQ codes — plus the per-row list
        ids the ADC mask needs and the page-id / source tables the re-rank
        needs — in device memory, biggest lists first until `budget_bytes`
        runs out. Resident lists then score against the staged codes with
        ZERO per-request host gather; non-resident lists keep the mmap
        path, and results are identical either way (test-pinned,
        tests/test_pq.py). Tombstones are masked at staging time (dead
        rows get centroid -2), so restaging follows the same refresh
        cadence as the serving HBM shards."""
        if self.pq is None:
            raise ValueError("stage_hot needs a PQ index (build with pq_m)")
        per_row = self.pq.m + 4                 # code bytes + centroid id
        resident = np.zeros((self.nlist,), bool)
        used = 0
        # popularity-driven ranking (docs/ANN.md "Popularity tiering"):
        # with measured probe counts, pin the HOTTEST lists (size breaks
        # ties, deterministically); a cold table — fresh build, restart —
        # degrades to the original biggest-first order. The table is
        # halved after ranking, so each restage sees a decayed window of
        # recent traffic, not all-time totals.
        counts = np.asarray(self.scan_counts)
        by_popularity = bool(counts.sum() > 0)
        if by_popularity:
            order = np.lexsort((-self.list_sizes, -counts))
        else:
            order = np.argsort(-self.list_sizes, kind="stable")
        self.scan_counts = counts >> 1
        for c in order:
            need = int(self.list_sizes[c]) * per_row
            if self.list_sizes[c] == 0 or used + need > budget_bytes:
                continue                        # smaller lists may still fit
            resident[int(c)] = True
            used += need
        cents = np.nonzero(resident)[0]
        codes, ids, cent, sh, rw = self._gather_codes(cents)
        n = codes.shape[0]
        if n == 0:
            self._hot = None
            return {"hot_lists": 0, "hot_rows": 0, "hot_bytes": 0,
                    "hot_by_popularity": by_popularity}
        # per-row attribute words ride along so a filtered query can mask
        # resident rows ON DEVICE (index/attrs.py matches_device) instead
        # of forcing hot lists back onto the host gather path
        words = np.zeros((n,), np.uint32)
        for sidx in np.unique(sh):
            m_ = sh == sidx
            words[m_] = self._shard_attrs(int(sidx))[rw[m_]]
        pad = _bucket(n, lo=512)
        if pad > n:
            codes = np.concatenate(
                [codes, np.zeros((pad - n, self.pq.m), np.uint8)])
            cent = np.concatenate([cent, np.full((pad - n,), -1, np.int32)])
            words = np.concatenate([words, np.zeros((pad - n,), np.uint32)])
        self._hot = {
            "lists": resident, "rows": n, "bytes": used,
            "codes": jnp.asarray(codes), "cent": jnp.asarray(cent),
            "attrs": jnp.asarray(words),
            "chunk": min(2048, pad), "ids": ids, "shard": sh, "row": rw}
        return {"hot_lists": int(resident.sum()), "hot_rows": n,
                "hot_bytes": used, "hot_by_popularity": by_popularity}

    def _gather(self, cents: np.ndarray, predicate=None):
        """Candidate block for one probed-list union: rows of every listed
        centroid across every shard, at STORED width (int8 codes / fp16
        rows straight off the mmap — the rerank matmul widens on device).
        Returns (vecs [C, D], scales [C]|None, page_ids [C] i64,
        cand_cent [C] i32). Tombstoned rows (id -1 after the store's
        read-time masking, docs/UPDATES.md) get centroid -2 — matching no
        probed list — so a dead vector can never OCCUPY a top-k slot, not
        merely be filtered after winning one. A `predicate`
        (index/attrs.py) drops non-matching rows against the shard's
        attribute words BEFORE the row gather — the filtered path's
        scan-byte reduction happens exactly here."""
        v_parts, s_parts, i_parts, c_parts = [], [], [], []
        for sidx in sorted(self._postings):
            order, offsets = self._postings[sidx]
            rows = [order[offsets[c]: offsets[c + 1]] for c in cents]
            lens = np.array([r.shape[0] for r in rows], np.int64)
            if lens.sum() == 0:
                continue
            take = np.concatenate(rows)
            cent = np.repeat(cents.astype(np.int32), lens)
            if predicate is not None:
                keep = predicate.matches(self._shard_attrs(sidx)[take])
                if not keep.any():
                    continue
                take, cent = take[keep], cent[keep]
            ids, vecs, scl = self._shard_raw(sidx)
            taken_ids = np.asarray(ids[take], np.int64)
            v_parts.append(np.asarray(vecs[take]))
            i_parts.append(taken_ids)
            if scl is not None:
                s_parts.append(np.asarray(scl[take]))
            c_parts.append(np.where(taken_ids >= 0, cent, np.int32(-2)))
        if not v_parts:
            return (np.zeros((0, self.store.dim), np.float16), None,
                    np.zeros((0,), np.int64), np.zeros((0,), np.int32))
        return (np.concatenate(v_parts),
                np.concatenate(s_parts) if s_parts else None,
                np.concatenate(i_parts), np.concatenate(c_parts))

    def search(self, qvecs: np.ndarray, k: int, nprobe: Optional[int] = None,
               block: int = 256, rerank: Optional[int] = None,
               predicate=None, escalate: float = 4.0
               ) -> Tuple[np.ndarray, np.ndarray, Dict[str, int]]:
        """ANN top-k: (scores [Nq, k] f32, page_ids [Nq, k] i64 -1-padded,
        stats) — see _search_once for the scoring machinery. `predicate`
        (index/attrs.py Predicate) restricts results to matching rows,
        intersected with the posting gathers BEFORE any candidate bytes
        move. A filtered probe set can under-fill k (the matching rows
        may live in un-probed lists): `escalate` > 1 re-searches the
        under-filled queries with nprobe multiplied per round until they
        fill or the probe set reaches nlist — the drain-more-lists
        escalation, counted in stats["filter_escalations"]."""
        out_s, out_i, stats = self._search_once(
            qvecs, k, nprobe=nprobe, block=block, rerank=rerank,
            predicate=predicate)
        if predicate is None or not escalate or escalate <= 1:
            return out_s, out_i, stats
        np_eff = int(min(max(1, nprobe or 1), self.nlist))
        k = int(min(k, max(out_i.shape[1], 1)))
        while np_eff < self.nlist:
            need = (out_i >= 0).sum(axis=1) < k
            if not need.any():
                break
            np_eff = int(min(self.nlist,
                             max(np_eff + 1, math.ceil(np_eff * escalate))))
            s2, i2, st2 = self._search_once(
                np.asarray(qvecs, np.float32)[need], k, nprobe=np_eff,
                block=block, rerank=rerank, predicate=predicate)
            out_s[need], out_i[need] = s2, i2
            n_esc = int(need.sum())
            stats["filter_escalations"] = (
                stats.get("filter_escalations", 0) + n_esc)
            self.stats["filter_escalations"] = (
                self.stats.get("filter_escalations", 0) + n_esc)
            telemetry.default_registry().counter(
                "ivf.filter_escalations").inc(n_esc)
            for key in ("lists_scanned", "candidates_reranked",
                        "gather_bytes", "reranked_rows", "hot_rows_scored"):
                if key in st2:
                    stats[key] = stats.get(key, 0) + st2[key]
        return out_s, out_i, stats

    def _search_once(self, qvecs: np.ndarray, k: int,
                     nprobe: Optional[int] = None, block: int = 256,
                     rerank: Optional[int] = None, predicate=None
                     ) -> Tuple[np.ndarray, np.ndarray, Dict[str, int]]:
        """One ANN pass: (scores [Nq, k] f32, page_ids [Nq, k] i64
        -1-padded, stats). Centroid scoring runs on device through
        `chunked_topk` (queries padded to a power-of-two bucket, one
        compiled program per octave); queries are then processed in
        `block`-sized sub-blocks — per sub-block ONE gathered candidate
        matmul via `rerank_candidates`, dispatched async so sub-block
        i+1's host gather overlaps sub-block i's device re-rank.

        On a PQ index (manifest "pq" section) the sub-blocks route
        through the ADC path instead (_search_adc): candidates score from
        m-byte codes, and only each query's top-`rerank` ADC survivors
        (default max(8k, 64)) are gathered at stored width for the exact
        final top-k. stats["gather_bytes"] reports the store payload
        bytes either path actually moved — with a `predicate`, the
        posting rows it rejects are dropped before the gather, so this
        number falls in proportion to selectivity."""
        qvecs = np.asarray(qvecs, np.float32)
        nq = qvecs.shape[0]
        k = int(k)
        out_s = np.full((nq, k), -np.inf, np.float32)
        out_i = np.full((nq, k), -1, np.int64)
        if nq == 0:
            return out_s, out_i, {}
        nprobe = int(min(max(1, nprobe or 1), self.nlist))
        if self._dev_centroids is None:
            self._dev_centroids = jnp.asarray(self.centroids)
        qb = _bucket(nq, lo=8)
        qpad = np.concatenate(
            [qvecs, np.zeros((qb - nq, qvecs.shape[1]), np.float32)]) \
            if qb > nq else qvecs
        _, sel = chunked_topk(jnp.asarray(qpad), self._dev_centroids,
                              k=nprobe, chunk=8192)
        sel = np.asarray(sel, np.int32)[:nq]
        # feed the popularity table: one count per (query, probed list).
        # bincount over the flat selection is one vectorized pass — the
        # per-search cost of popularity tiering is this line.
        self.scan_counts += np.bincount(sel.ravel(), minlength=self.nlist)
        stats = {"searches": nq, "lists_scanned": nq * nprobe,
                 "candidates_reranked":
                     int(self.list_sizes[sel].sum()),
                 "gather_bytes": 0}
        # index-level instruments (docs/OBSERVABILITY.md): windowed search
        # rate + probe volume regardless of which service routed here
        reg = telemetry.default_registry()
        reg.counter("ivf.searches",
                    window_s=telemetry.DEFAULT_WINDOW_S).inc(nq)
        reg.counter("ivf.lists_scanned").inc(nq * nprobe)
        if self.pq is not None:
            return self._search_adc(qvecs, sel, k, block, rerank,
                                    out_s, out_i, stats,
                                    predicate=predicate)
        pending = []
        for s in range(0, nq, block):
            e = min(s + block, nq)
            sel_b = sel[s:e]
            cents = np.unique(sel_b)
            cand, scl, cids, ccent = self._gather(cents,
                                                  predicate=predicate)
            C = cand.shape[0]
            stats["gather_bytes"] += C * self.store.row_bytes
            if C == 0:
                pending.append((s, e, None, None))
                continue
            cp = _bucket(C, lo=max(512, k))
            if cp > C:
                cand = np.concatenate(
                    [cand, np.zeros((cp - C, cand.shape[1]), cand.dtype)])
                ccent = np.concatenate(
                    [ccent, np.full((cp - C,), -1, np.int32)])
                if scl is not None:
                    scl = np.concatenate(
                        [scl, np.zeros((cp - C,), scl.dtype)])
            # pow-2 query bucket: a lone serve bucket of 8 must not pad to
            # the full mining block width (32x wasted matmul rows)
            bq = min(_bucket(e - s, lo=8), _bucket(block, lo=8))
            qblk = qvecs[s:e]
            if bq > e - s:
                qblk = np.concatenate(
                    [qblk, np.zeros((bq - (e - s), qvecs.shape[1]),
                                    np.float32)])
                sel_b = np.concatenate(
                    [sel_b, np.full((bq - (e - s), nprobe), -1, np.int32)])
            packed = rerank_candidates(
                jnp.asarray(qblk), jnp.asarray(cand),
                None if scl is None else jnp.asarray(scl),
                jnp.asarray(ccent), jnp.asarray(sel_b), k)
            pending.append((s, e, packed, cids))
        for s, e, packed, cids in pending:
            if packed is None:
                continue
            top_s, pos = (np.asarray(packed[0]), np.asarray(packed[1]))
            top_s, pos = top_s[: e - s], pos[: e - s]
            kk = pos.shape[1]
            out_i[s:e, :kk] = np.where(
                pos >= 0, cids[np.clip(pos, 0, None)], -1)
            out_s[s:e, :kk] = np.where(pos >= 0, top_s, -np.inf)
        for key, val in stats.items():
            self.stats[key] = self.stats.get(key, 0) + val
        return out_s, out_i, stats

    def _search_adc(self, qvecs: np.ndarray, sel: np.ndarray, k: int,
                    block: int, rerank: Optional[int],
                    out_s: np.ndarray, out_i: np.ndarray, stats: Dict,
                    predicate=None
                    ) -> Tuple[np.ndarray, np.ndarray, Dict[str, int]]:
        """The compressed-payload block loop (docs/ANN.md): per sub-block,
        gather the probed lists' m-byte CODES (mmap — resident lists skip
        the gather entirely and score against the staged device codes),
        compute per-query ADC lookup tables on device (`pq.lut`), run the
        running top-r over code scores (`adc_topr`, masked per query to
        its probed lists), then fetch ONLY the union of per-query
        survivors' rows at stored width and exact re-rank them
        (`rerank_positions`) for the final top-k. ADC ties and the
        survivor cut are deterministic (stable sorts, lax.top_k)."""
        nq = qvecs.shape[0]
        nprobe = sel.shape[1]
        r = max(int(rerank) if rerank else max(8 * k, 64), k)
        hot = self._hot
        m = self.pq.m
        for s in range(0, nq, block):
            e = min(s + block, nq)
            sel_b = sel[s:e]
            cents = np.unique(sel_b)
            cold_cents = (cents[~hot["lists"][cents]] if hot is not None
                          else cents)
            codes, cids, ccent, csh, crw = self._gather_codes(
                cold_cents, predicate=predicate)
            C = codes.shape[0]
            stats["gather_bytes"] += C * m
            # pow-2 query bucket (same rule as the uncompressed path)
            bq = min(_bucket(e - s, lo=8), _bucket(block, lo=8))
            qblk = qvecs[s:e]
            sel_pad = sel_b
            if bq > e - s:
                qblk = np.concatenate(
                    [qblk, np.zeros((bq - (e - s), qvecs.shape[1]),
                                    np.float32)])
                sel_pad = np.concatenate(
                    [sel_b, np.full((bq - (e - s), nprobe), -1, np.int32)])
            q_dev = jnp.asarray(qblk)
            lut = self.pq.lut(q_dev)
            sel_dev = jnp.asarray(sel_pad)
            parts = []            # (scores, page ids, src shard, src row)
            if C:
                cp = _bucket(C, lo=512)
                if cp > C:
                    codes = np.concatenate(
                        [codes, np.zeros((cp - C, m), np.uint8)])
                    ccent = np.concatenate(
                        [ccent, np.full((cp - C,), -1, np.int32)])
                cs, cpos = adc_topr(lut, jnp.asarray(codes),
                                    jnp.asarray(ccent), sel_dev, r=r,
                                    chunk=min(2048, cp))
                cs, cpos = np.asarray(cs), np.asarray(cpos)
                # a PADDING query (probed set all -1) "matches" padding
                # candidates (cent -1): clip + mask so its garbage rows
                # never reach the union gather
                ok = (cpos >= 0) & (cpos < C)
                idx = np.clip(cpos, 0, C - 1)
                parts.append((np.where(ok, cs, -np.inf),
                              np.where(ok, cids[idx], -1),
                              np.where(ok, csh[idx], -1),
                              np.where(ok, crw[idx], -1)))
            if hot is not None and hot["rows"]:
                # filtered queries mask resident rows ON DEVICE: attribute
                # words staged next to the codes, one and+compare per
                # predicate alternative, non-matching rows -> centroid -2
                # (matches no probed list) before the ADC scan
                hcent = hot["cent"]
                if predicate is not None:
                    hcent = jnp.where(
                        predicate.matches_device(hot["attrs"]),
                        hcent, jnp.int32(-2))
                hs, hpos = adc_topr(lut, hot["codes"], hcent,
                                    sel_dev, r=r, chunk=hot["chunk"])
                hs, hpos = np.asarray(hs), np.asarray(hpos)
                ok = (hpos >= 0) & (hpos < hot["rows"])
                idx = np.clip(hpos, 0, hot["rows"] - 1)
                parts.append((np.where(ok, hs, -np.inf),
                              np.where(ok, hot["ids"][idx], -1),
                              np.where(ok, hot["shard"][idx], -1),
                              np.where(ok, hot["row"][idx], -1)))
                res = hot["lists"][sel_b]
                stats["hot_rows_scored"] = stats.get(
                    "hot_rows_scored", 0) + int(
                        self.list_sizes[sel_b][res].sum())
            if not parts:
                continue                        # out stays -inf / -1
            scores = np.concatenate([p[0] for p in parts], axis=1)
            pids = np.concatenate([p[1] for p in parts], axis=1)
            shm = np.concatenate([p[2] for p in parts], axis=1)
            rwm = np.concatenate([p[3] for p in parts], axis=1)
            if scores.shape[1] > r:             # merge hot + cold survivors
                ordx = np.argsort(-scores, axis=1, kind="stable")[:, :r]
                take = lambda a: np.take_along_axis(a, ordx, axis=1)  # noqa: E731
                scores, pids = take(scores), take(pids)
                shm, rwm = take(shm), take(rwm)
            ok = np.isfinite(scores) & (pids >= 0)
            ok[e - s:] = False                  # padding queries: no gather
            key = np.where(
                ok, shm.astype(np.int64) * (1 << 32) + rwm.astype(np.int64),
                np.int64(-1))
            uniq = np.unique(key[ok])
            if uniq.size == 0:
                continue
            rows, scl = self._fetch_rows(
                (uniq >> 32).astype(np.int32),
                (uniq & 0xFFFFFFFF).astype(np.int32))
            stats["gather_bytes"] += int(uniq.size) * self.store.row_bytes
            stats["reranked_rows"] = stats.get(
                "reranked_rows", 0) + int(ok[: e - s].sum())
            U = uniq.size
            up = _bucket(U, lo=max(64, k))
            if up > U:
                rows = np.concatenate(
                    [rows, np.zeros((up - U, rows.shape[1]), rows.dtype)])
                if scl is not None:
                    scl = np.concatenate(
                        [scl, np.zeros((up - U,), scl.dtype)])
            pos = np.where(ok, np.searchsorted(uniq, key), -1).astype(
                np.int32)
            uids = np.full((up,), -1, np.int64)
            uids[pos[ok]] = pids[ok]            # union row -> page id
            top_s, top_pos = rerank_positions(
                q_dev, jnp.asarray(rows),
                None if scl is None else jnp.asarray(scl),
                jnp.asarray(pos), k)
            top_s = np.asarray(top_s)[: e - s]
            top_pos = np.asarray(top_pos)[: e - s]
            kk = top_pos.shape[1]
            out_i[s:e, :kk] = np.where(
                top_pos >= 0, uids[np.clip(top_pos, 0, None)], -1)
            out_s[s:e, :kk] = np.where(top_pos >= 0, top_s, -np.inf)
        for key_, val in stats.items():
            self.stats[key_] = self.stats.get(key_, 0) + val
        return out_s, out_i, stats
