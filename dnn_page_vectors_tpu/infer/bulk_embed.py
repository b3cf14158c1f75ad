"""Corpus->vector bulk-embed job (SURVEY.md §3 #19; call stack §4.2).

The reference's batch-inference job ran data-parallel on GPUs
(BASELINE.json:5); here the forward pass is one jitted `encode_page` with
the batch sharded over the mesh 'data' axis and params HBM-resident, so every
chip embeds its batch shard and results stream back to the host (overlapped
with the next batch via the prefetch queue) into the resumable vector store.
Throughput metric: pages/sec/chip (BASELINE.json:2).
"""
from __future__ import annotations

import queue as queue_mod
import threading
import time
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dnn_page_vectors_tpu.config import Config
from dnn_page_vectors_tpu.data.loader import iter_corpus_batches, prefetch_to_device
from dnn_page_vectors_tpu.data.toy import ToyCorpus
from dnn_page_vectors_tpu.infer.vector_store import VectorStore
from dnn_page_vectors_tpu.models.glm_moe import STATS as MOE_STATS
from dnn_page_vectors_tpu.models.glm_moe import _EXPERT_TILE
from dnn_page_vectors_tpu.models.losses import l2_normalize
from dnn_page_vectors_tpu.parallel.sharding import (
    _path_str, batch_sharding, replicated, shard_params,
    stacked_batch_sharding)
from dnn_page_vectors_tpu.utils import faults
from dnn_page_vectors_tpu.utils.logging import MetricsLogger
from dnn_page_vectors_tpu.utils.profiling import PipelineProfiler


class _ShardWriter:
    """Background store writeback: the shard-level np.concatenate +
    write_shard runs on this thread, so disk writeback of shard i overlaps
    device compute of shard i+1 instead of stalling the device loop between
    shards.

    Contract:
      * bounded pending budget (`max_pending` queued shards) — host memory
        for not-yet-written shards stays O(budget), and a dead disk
        backpressures the device loop instead of buffering forever;
      * the resume manifest records a shard only AFTER write_shard returns
        (data files synced, then the manifest flush — vector_store.py), so
        killing the job mid-shard never marks an unwritten shard complete;
      * the first writer exception is re-raised consumer-side AS ITSELF
        (the caller's `except SomeError` still matches — writeback moving
        off-thread must not change the exception surface): submit() raises
        it promptly (the device loop stops instead of racing ahead), and
        close() joins the thread and re-raises so embed_corpus can never
        return with a swallowed write failure.
    """

    _SENTINEL = object()

    def __init__(self, store: VectorStore, q8: bool, max_pending: int = 2,
                 profiler: Optional[PipelineProfiler] = None,
                 log: Optional[MetricsLogger] = None,
                 n_dev: int = 1, t0: Optional[float] = None):
        self._store = store
        self._q8 = q8
        self._prof = profiler
        self._log = log
        self._n_dev = n_dev
        self._t0 = time.perf_counter() if t0 is None else t0
        self._q: "queue_mod.Queue[object]" = queue_mod.Queue(
            maxsize=max(1, max_pending))
        self._err: Optional[BaseException] = None
        self._t = threading.Thread(target=self._run, daemon=True,
                                   name="shard-writer")
        self._t.start()

    def submit(self, index: int, ids_acc, vec_acc, scl_acc,
               pages_so_far: int) -> None:
        """Queue one finished shard (accumulator lists, concatenated on the
        writer thread). Blocks while the pending budget is full; raises the
        writer's error as soon as one exists."""
        item = (index, ids_acc, vec_acc, scl_acc, pages_so_far)
        t0 = time.perf_counter()
        try:
            while True:
                if self._err is not None:
                    raise self._err
                try:
                    self._q.put(item, timeout=0.1)
                    return
                except queue_mod.Full:
                    continue
        finally:
            if self._prof is not None:
                self._prof.add("write_wait", time.perf_counter() - t0)

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is self._SENTINEL:
                return
            if self._err is not None:
                continue   # drain after failure so submit/close never hang
            try:
                index, ids_acc, vec_acc, scl_acc, pages = item
                t0 = time.perf_counter()
                ids = np.concatenate(ids_acc)
                if self._q8:
                    self._store.write_shard(index, ids,
                                            codes=np.concatenate(vec_acc),
                                            scales=np.concatenate(scl_acc))
                else:
                    self._store.write_shard(index, ids,
                                            np.concatenate(vec_acc))
                now = time.perf_counter()
                if self._prof is not None:
                    self._prof.add("write", now - t0)
                if self._log is not None:
                    self._log.write({
                        "bulk_embed_shard": index,
                        "pages_per_sec_per_chip":
                            pages / (now - self._t0) / self._n_dev})
            except BaseException as e:
                self._err = e

    def close(self, raise_error: bool = True) -> None:
        """Join the writer (flushing queued shards) and re-raise its first
        error. raise_error=False is the unwind path when the device loop
        already holds the primary exception."""
        if self._t.is_alive():
            self._q.put(self._SENTINEL)
            self._t.join()
        if raise_error and self._err is not None:
            raise self._err


def _stack_batches(it, k: int):
    """Group k consecutive {page, page_id} batches into one [k, B, ...]
    stacked batch for the fused lax.map sweep; the tail group is padded
    with page_id=-1 zero batches (dropped by the store like any padding)."""
    group = []

    def _emit(g):
        return {key: np.stack([b[key] for b in g]) for key in g[0]}

    for b in it:
        group.append(b)
        if len(group) == k:
            yield _emit(group)
            group = []
    if group:
        pad = {key: np.zeros_like(group[0][key]) for key in group[0]}
        pad["page_id"] = np.full_like(group[0]["page_id"], -1)
        yield _emit(group + [pad] * (k - len(group)))


# What a tower computes with in float32 whatever its weights are held in:
# every 1-D leaf (norm scales, biases, a state-space layer's A_log / D /
# dt_bias, the selection bias), the router (a rounding there moves the
# selection) and the float32 output projection.
_KEPT_FLOAT32 = ("router", "proj")


def hold_weights(params, dtype: str):
    """The tree as inference holds it: with `dtype` "bfloat16" every float32
    matrix (embedding, projections, stacked expert kernels) is cast ONCE,
    here; a leaf that already arrives in bfloat16 stays the array it is, and
    the leaves named above stay float32. "float32": the tree as it is."""
    if dtype == "float32":
        return params
    if dtype != "bfloat16":
        raise ValueError(f"model.weights_dtype {dtype!r}: want float32 or "
                         "bfloat16")

    def one(path, leaf):
        if leaf.dtype != jnp.float32 or leaf.ndim < 2 or any(
                k in _KEPT_FLOAT32 for k in _path_str(path).split("/")):
            return leaf
        return leaf.astype(jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(one, params)


def _encode_counters(ids, stats):
    """What one encode call counted, on the device: ([4] int32 in
    ENCODE_COUNTERS' order: non-pad tokens, assignments on the experts held,
    tiles of the grouped product in use, assignments dropped, summed over
    the layers; [layers, experts held] int32, the assignments per held
    expert those sums were made from). `stats` is what the tower sowed into
    `moe_stats`; None, from a tower without routed layers, leaves the three
    sums at 0 and the second value None."""
    if stats is None:
        return jnp.zeros(4, jnp.int32).at[0].set((ids > 0).sum()), None
    held = sum(stats["held"]).astype(jnp.int32)
    tiles = jnp.maximum(-(-held // _EXPERT_TILE), 1)
    sums = jnp.stack([(ids > 0).sum(), held.sum(), tiles.sum(),
                      sum(stats["dropped"]).sum()]).astype(jnp.int32)
    return sums, held


class BulkEmbedder:
    ENCODE_COUNTERS = ("tokens", "moe_assignments_held", "moe_tiles_used",
                       "moe_dropped")

    def __init__(self, cfg: Config, model, params, page_tok, mesh,
                 query_tok=None):
        self.cfg = cfg
        self.model = model
        # (re-)place params for THIS mesh — training may have run on a
        # different mesh shape than the embed job (call stack §4.2 restores
        # from checkpoint anyway). Under multi-host, `mesh` is process-LOCAL
        # (parallel/multihost.py) and params trained on the global mesh are
        # pulled to host first (replicated DP params: every host has a copy).
        if any(isinstance(x, jax.Array) and not x.is_fully_addressable
               for x in jax.tree_util.tree_leaves(params)):
            from dnn_page_vectors_tpu.parallel.multihost import (
                host_replicated_copy)
            params = host_replicated_copy(params)
        self.params = shard_params(
            hold_weights(params, cfg.model.weights_dtype), mesh)
        self.page_tok = page_tok
        self.query_tok = query_tok
        self.mesh = mesh
        out_sh = batch_sharding(mesh)

        def _encode(params, ids, method):
            vecs = model.apply(params, ids, deterministic=True, method=method)
            return l2_normalize(vecs)

        # Page vectors leave the device as fp16: the store persists fp16 (or
        # int8 quantized FROM the fp16-rounded values) either way, so casting
        # on device halves the device->host bytes of the bulk-embed job — the
        # job's whole output is D2H traffic (~0.5 GB/M pages at D=256).
        # Normalization still runs in fp32; the cast is the store's own
        # rounding, just applied before the wire instead of after. Query
        # vectors stay fp32 (they feed the fp32 top-k scorer directly).
        self._encode_page = jax.jit(
            lambda p, x: _encode(p, x, "encode_page").astype(jnp.float16),
            in_shardings=(None, batch_sharding(mesh)), out_shardings=out_sh)
        self._encode_query = jax.jit(
            lambda p, x: _encode(p, x, "encode_query"),
            in_shardings=(None, batch_sharding(mesh)), out_shardings=out_sh)
        # A tower that asks to have its encode counted (it says so itself:
        # `counts_encode_tokens`, or `sows_moe_stats` where it sows its
        # routed layers' counters into `moe_stats`) gets the counts handed
        # back beside the vectors, reduced on the device (_encode_counters):
        # the tokens always, the routed layers' three where sown. Decided
        # here, once; every other tower keeps the program above. Callers go
        # through encode_query_call, which gives all kinds one shape.
        sows = getattr(model.query_tower, "sows_moe_stats", False)
        self.counts_encode = sows or getattr(
            model.query_tower, "counts_encode_tokens", False)
        if self.counts_encode:
            def _encode_counted(params, ids):
                vecs, sown = model.apply(params, ids, deterministic=True,
                                         method="encode_query",
                                         mutable=[MOE_STATS])
                return l2_normalize(vecs), _encode_counters(
                    ids, sown[MOE_STATS]["query_tower"] if sows else None)

            self._encode_query = jax.jit(
                _encode_counted, in_shardings=(None, batch_sharding(mesh)),
                out_shardings=(out_sh, replicated(mesh)))
        # Fused sweep: E batches per dispatch ([E, B, ...] -> [E, B, D] via
        # lax.map). Same per-batch compute, so vectors are identical to the
        # per-batch path. embed_corpus dispatches eval.embed_stack batches
        # at a time through this (+8% measured on v5e at E=8, round 4 —
        # dispatch amortization on the forward-only sweep).
        stk = stacked_batch_sharding(mesh)

        def _encode_stack(params, stacked):
            return jax.lax.map(
                lambda x: _encode(params, x, "encode_page").astype(
                    jnp.float16), stacked)

        self._encode_page_stack = jax.jit(
            _encode_stack, in_shardings=(None, stk), out_shardings=stk)

        # int8-store wire (round 5): quantize ON DEVICE with exactly the
        # math VectorStore.write_shard applies on host — per-row scale from
        # the fp16-rounded vector, fp16-rounded scale with the underflow
        # floor, rint codes — so the job ships 1 B/dim codes + 2 B scales
        # instead of 2 B/dim fp16 rows (another 2x off the bulk-embed D2H
        # wire on top of the fp16 cast; the store bytes are unchanged).
        def _quantize(v16):
            v = v16.astype(jnp.float32)
            scale = jnp.max(jnp.abs(v), axis=-1) / 127.0
            floor = jnp.float32(jnp.float16(6.2e-5))  # exact fp16 value
            safe = jnp.maximum(
                scale.astype(jnp.float16).astype(jnp.float32), floor)
            codes = jnp.clip(jnp.rint(v / safe[..., None]),
                             -127, 127).astype(jnp.int8)
            return codes, safe.astype(jnp.float16)

        self._encode_page_q8 = jax.jit(
            lambda p, x: _quantize(_encode(p, x, "encode_page").astype(
                jnp.float16)),
            in_shardings=(None, batch_sharding(mesh)),
            out_shardings=(out_sh, out_sh))

        def _encode_stack_q8(params, stacked):
            return jax.lax.map(
                lambda x: _quantize(_encode(params, x, "encode_page").astype(
                    jnp.float16)), stacked)

        self._encode_page_stack_q8 = jax.jit(
            _encode_stack_q8, in_shardings=(None, stk),
            out_shardings=(stk, stk))

    # -- single batches ---------------------------------------------------
    def _put(self, ids: np.ndarray) -> jax.Array:
        # jit under process_count>1 refuses numpy args with non-replicated
        # in_shardings (it can't tell global from process-local values), so
        # place the batch explicitly; the mesh here is fully addressable
        # (local under multi-host, global single-process).
        return jax.device_put(ids, batch_sharding(self.mesh))

    def embed_pages(self, ids: np.ndarray) -> np.ndarray:
        """[B, L(, K)] token ids -> [B, D] L2-normalized page vectors.

        Returns FLOAT16 rows (ADVICE r5): the page tower casts to fp16 on
        device — the store's own rounding applied before the D2H wire, so
        the bulk job ships half the bytes; normalization still runs fp32.
        The query tower (embed_queries) stays fp32: it feeds the fp32 top-k
        scorer directly and is never bulk traffic."""
        return np.asarray(self._encode_page(self.params, self._put(ids)))

    def encode_query_call(self, ids: np.ndarray, params=None):
        """One call of the compiled query encode on host ids [B, L], left on
        the device: (unit vectors [B, D], what the call counted). The second
        is _encode_counters' pair for a tower that asks to be counted and
        None for every other. `params`: the serving step's tree (default:
        the embedder's own)."""
        out = self._encode_query(self.params if params is None else params,
                                 self._put(ids))
        return out if self.counts_encode else (out, None)

    def embed_queries(self, ids: np.ndarray) -> np.ndarray:
        return np.asarray(self.encode_query_call(ids)[0])

    def embed_texts(self, texts, tower: str = "query",
                    batch_size: Optional[int] = None) -> np.ndarray:
        """Tokenize + embed a list of texts, padding each batch to the
        compiled batch shape (one XLA program regardless of len(texts)).
        Shared by the recall eval and the ANN miner.

        Return dtype is per-tower (ADVICE r5): tower="page" yields FLOAT16
        rows (the on-device store-rounding cast, see embed_pages) while
        tower="query" yields fp32 — callers mixing towers must not assume
        a common dtype."""
        tok = self.query_tok if tower == "query" else self.page_tok
        run = self.embed_queries if tower == "query" else self.embed_pages
        bs = batch_size or self.cfg.eval.embed_batch_size
        chunks = []
        for s in range(0, len(texts), bs):
            part = texts[s: s + bs]
            enc = tok.encode_batch(part)
            if enc.shape[0] < bs:
                pad = bs - enc.shape[0]
                enc = np.concatenate(
                    [enc, np.zeros((pad,) + enc.shape[1:], enc.dtype)])
            chunks.append(run(enc)[: len(part)])
        return (np.concatenate(chunks) if chunks
                else np.zeros((0, self.cfg.model.out_dim), np.float32))

    # -- the bulk job -----------------------------------------------------
    # graftcheck: hot
    def embed_corpus(self, corpus: ToyCorpus, store: VectorStore,
                     batch_size: Optional[int] = None, resume: bool = True,
                     log: Optional[MetricsLogger] = None,
                     start: int = 0, stop: Optional[int] = None,
                     workers: Optional[int] = None,
                     write_pending: Optional[int] = None,
                     profiler: Optional[PipelineProfiler] = None
                     ) -> VectorStore:
        """Sweep the corpus into the store, one store-shard at a time.

        Host pipeline: `workers` tokenizer workers (default
        cfg.data.tokenize_workers) read+tokenize batch id-ranges
        concurrently, reassembled in order — vectors are byte-identical to
        the serial path; store writeback runs on a background writer thread
        with a bounded pending budget (`write_pending`, default
        cfg.eval.writeback_depth), so the disk write of shard i overlaps
        device compute of shard i+1. The writer joins — and re-raises —
        before this method returns; the manifest records a shard only after
        its files are durably written, so a killed job never resumes past
        an unwritten shard.

        `profiler` (one is created when omitted) collects the per-stage
        wall-time breakdown (produce_wait / read / tokenize / h2d / compute
        / d2h / write / write_wait); the summary lands in the metrics log
        when `log` is given.

        Resume: completed shards are recorded in the store manifest and
        skipped on restart (SURVEY.md §5.3 fault recovery).

        Multi-host (SURVEY.md §4.2 "each host reads its file shards"): when
        jax.process_count() > 1, each process embeds only the store shards
        with ``si % process_count == process_index`` on its process-LOCAL
        mesh — the forward pass has no collectives, so hosts run fully
        independently and a straggler never stalls the others — and records
        them under its own writer manifest; after a barrier, process 0 folds
        the writer manifests into the main one.

        `start`/`stop` restrict the sweep to a page range (both must be
        store-shard-aligned so resume bookkeeping stays per-shard exact);
        this is the manual variant of the same sharding for fleets launched
        WITHOUT jax.distributed — one process per corpus slice, each with
        ``writer_id=start // shard_size`` (docs/SCALING.md recipe).
        """
        bs = batch_size or self.cfg.eval.embed_batch_size
        if store.manifest.get("compacted_through"):
            # a compacted base re-shards rows by id order under new shard
            # indices (docs/MAINTENANCE.md): the index-based resume
            # bookkeeping below would re-embed — and double-assign — the
            # whole base range. Compaction only ever runs on a completed
            # store, so a base sweep here is a caller error.
            raise ValueError(
                f"store at {store.directory} has been compacted (through "
                f"generation {store.manifest['compacted_through']}); the "
                "base embed is complete — append new pages with "
                "append_corpus / `cli append` instead")
        shard_size = store.manifest["shard_size"]
        assert shard_size % bs == 0 or shard_size >= corpus.num_pages, (
            "shard_size must be a batch multiple for resumable sweeps")
        stop = corpus.num_pages if stop is None else min(stop, corpus.num_pages)
        if start % shard_size:
            raise ValueError(f"start={start} must be a multiple of the store "
                             f"shard_size {shard_size}")
        if stop % shard_size and stop != corpus.num_pages:
            raise ValueError(f"stop={stop} must be shard-aligned (multiple of "
                             f"{shard_size}) or the corpus end "
                             f"{corpus.num_pages}")
        if resume:
            # integrity gate before trusting the manifest (docs/
            # ROBUSTNESS.md): a shard whose bytes no longer match their
            # recorded checksum/size is quarantined HERE, so `done` below
            # excludes it and exactly its id-range is re-embedded — resume
            # never skips over silently corrupt vectors
            bad = store.verify()
            if bad and log:
                log.write({"bulk_embed_quarantined_shards": bad})
        pi, pc = jax.process_index(), jax.process_count()
        if pc > 1:
            from dnn_page_vectors_tpu.parallel.multihost import is_local_mesh
            if not is_local_mesh(self.mesh):
                raise ValueError(
                    "multi-process embed_corpus requires a process-local "
                    "mesh (parallel.multihost.local_mesh): a global mesh "
                    "would deadlock on per-process shard loops")
            if store.writer_id != pi:
                raise ValueError(
                    f"multi-process embed_corpus needs "
                    f"writer_id=process_index ({pi}), got {store.writer_id}")
        done = store.completed_shards() if resume else set()
        n_dev = self.mesh.devices.size
        # int8 stores quantize ON DEVICE (codes + fp16 scales over the wire,
        # 1 B/dim instead of 2 — see the q8 encode paths above); fp16 stores
        # ship fp16 rows. Either way the wire carries the stored width.
        q8 = store.manifest["dtype"] == "int8"
        workers = (self.cfg.data.tokenize_workers if workers is None
                   else workers)
        write_pending = (self.cfg.eval.writeback_depth if write_pending is None
                         else write_pending)
        prof = (PipelineProfiler(prefix="embed.") if profiler is None
                else profiler)
        # embed-sweep throughput as registry instruments (docs/
        # OBSERVABILITY.md): the windowed pages counter answers "what is
        # the rate RIGHT NOW" mid-sweep, the end-of-job gauge mirrors the
        # metrics line
        from dnn_page_vectors_tpu.utils import telemetry
        _reg = telemetry.default_registry()
        _m_pages = _reg.counter("embed.pages",
                                window_s=telemetry.DEFAULT_WINDOW_S)
        t0 = time.perf_counter()
        pages = 0
        writer = _ShardWriter(store, q8, max_pending=write_pending,
                              profiler=prof, log=log, n_dev=n_dev, t0=t0)
        try:
            for si in range(start // shard_size, -(-stop // shard_size)):
                if si in done or si % pc != pi:
                    continue
                lo = si * shard_size
                hi = min(lo + shard_size, corpus.num_pages)
                ids_acc, vec_acc, scl_acc = [], [], []
                batches = iter_corpus_batches(corpus, self.page_tok, bs,
                                              start=lo, stop=hi,
                                              workers=workers, profiler=prof)
                # clamp to the shard's batch count: a 2-batch shard must not
                # pad an 8-slot dispatch with 6 all-zero batches
                E = min(max(1, self.cfg.eval.embed_stack),
                        -(-(hi - lo) // bs))
                if E > 1:
                    # fuse E batches per dispatch (lax.map; +8% measured at
                    # E=8): the tail group is padded with page_id=-1 batches,
                    # which write_shard drops like any batch padding
                    batches = _stack_batches(batches, E)
                    sharding = stacked_batch_sharding(self.mesh)
                    encode = (self._encode_page_stack_q8 if q8
                              else self._encode_page_stack)
                else:
                    sharding = batch_sharding(self.mesh)
                    encode = self._encode_page_q8 if q8 else self._encode_page
                # Output is double-buffered (VERDICT r1 #8): dispatch batch
                # i's encode (async under JAX's deferred execution), THEN
                # materialize batch i-1's vectors — the device->host copy of
                # the previous batch overlaps the current batch's compute
                # instead of serializing after it.
                pending = None

                def _collect(p):
                    nonlocal pages
                    with prof.stage("d2h"):
                        # ONE packed drain per dispatch: ids + vectors
                        # (+ scales) materialize together instead of a
                        # sequence of per-array np.asarray syncs — each
                        # sync is a round trip, and the drain rate (stage_d2h_bytes
                        # over stage_d2h_s, reported as
                        # embed_d2h_mbytes_per_sec) is what bounds the
                        # from-text sweep (docs/MFU.md "host pipeline").
                        host = jax.device_get(p)  # graftcheck: off=host-sync -- the one packed d2h drain per dispatch
                    ids = host[0].reshape(-1)
                    if q8:
                        codes, scl = host[1]
                        vec_acc.append(codes.reshape(-1, codes.shape[-1]))
                        scl_acc.append(scl.reshape(-1))
                        prof.add_bytes("d2h", ids.nbytes + codes.nbytes
                                       + scl.nbytes)
                    else:
                        vecs = host[1]
                        vec_acc.append(vecs.reshape(-1, vecs.shape[-1]))
                        prof.add_bytes("d2h", ids.nbytes + vecs.nbytes)
                    ids_acc.append(ids)
                    real = (ids >= 0).sum()
                    pages += int(real)
                    _m_pages.inc(int(real))

                for batch in prefetch_to_device(batches, sharding=sharding,
                                                profiler=prof):
                    with prof.stage("compute"):
                        vecs = encode(self.params, batch["page"])
                    if pending is not None:
                        _collect(pending)
                    pending = (batch["page_id"], vecs)
                if pending is not None:
                    _collect(pending)
                # hand the shard to the writer thread: its concat + disk
                # write overlaps the next shard's device compute; resume
                # bookkeeping happens inside write_shard after the data is
                # durably on disk
                writer.submit(si, ids_acc, vec_acc,
                              scl_acc if q8 else None, pages)
        except BaseException:
            writer.close(raise_error=False)  # primary exception wins
            raise
        writer.close()   # join + re-raise any write failure
        _reg.gauge("embed.pages_per_sec_per_chip").set(
            pages / max(time.perf_counter() - t0, 1e-9) / n_dev)
        # measured drain rate of the packed d2h transfers — the transport
        # number the from-text sweep is bounded by (docs/MFU.md)
        d2h_s = prof.stages().get("d2h", 0.0)
        d2h_rate = (prof.stage_bytes().get("d2h", 0) / d2h_s / 1e6
                    if d2h_s > 0 else 0.0)
        _reg.gauge("embed.d2h_mbytes_per_sec").set(d2h_rate)
        if log:
            rec = {"bulk_embed_pages": pages,
                   "embed_d2h_mbytes_per_sec": round(d2h_rate, 2),
                   **prof.summary()}
            fc = faults.counters()
            if fc:     # recovery-path activity belongs next to the rate
                rec["fault_counters"] = fc
            log.write(rec)
        if pc > 1:
            from dnn_page_vectors_tpu.parallel.multihost import barrier
            barrier("embed_corpus_written")
            if pi == 0:
                store.merge_writers()
            barrier("embed_corpus_merged")
            store.reload()
        return store
