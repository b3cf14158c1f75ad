"""Background maintenance (maintenance/, docs/MAINTENANCE.md): online
generation compaction (byte-deterministic fold, id preservation, exact
result parity, crash-mid-swap old-chain serving), off-path IVF rebuilds
hot-swapped under a concurrent query hammer, and multi-writer append
leases (two-writer contention with no double-assign, steal, fail-fast).

Presence checks query with the STORED vectors themselves (self-similarity
1 under the unit-norm invariant), mirroring tests/test_updates.py — they
pin the maintenance machinery, not the tiny model's generalization."""
import json
import os
import shutil
import threading
import time

import numpy as np
import pytest

from dnn_page_vectors_tpu.config import get_config
from dnn_page_vectors_tpu.data.toy import ToyCorpus
from dnn_page_vectors_tpu.evals.recall import recall_vs_exact
from dnn_page_vectors_tpu.index.ivf import IVFIndex
from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
from dnn_page_vectors_tpu.infer.serve import SearchService
from dnn_page_vectors_tpu.infer.vector_store import VectorStore
from dnn_page_vectors_tpu.maintenance import (
    AppendLease, LeaseHeld, LeaseLost, MaintenanceService, compact_store,
    purge_stale)
from dnn_page_vectors_tpu.ops.topk import topk_over_store
from dnn_page_vectors_tpu.train.loop import Trainer
from dnn_page_vectors_tpu.updates import append_corpus
from dnn_page_vectors_tpu.utils import faults, telemetry

pytestmark = pytest.mark.maint

_OV = {
    "data.num_pages": 300,
    "data.trigram_buckets": 2048,
    "model.embed_dim": 48,
    "model.conv_channels": 96,
    "model.out_dim": 48,
    "train.batch_size": 64,
    "train.steps": 60,
    "train.warmup_steps": 10,
    "train.learning_rate": 2e-3,
    "train.log_every": 1000,
    "eval.embed_batch_size": 100,
    "eval.store_shard_size": 100,   # 3 base shards; appends add gen shards
    # the two-writer contention test queues writer B on writer A's lease
    # for the WHOLE of A's append — give slow CI headroom over the 5s
    # production default
    "updates.lease_wait_s": 30.0,
}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    telemetry.reset_default()
    yield
    faults.reset()
    telemetry.reset_default()


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    """One trained model + embedded 3-shard base store for the module;
    every mutating test works on a private copy."""
    wd = tmp_path_factory.mktemp("maint_env")
    cfg = get_config("cdssm_toy", _OV)
    trainer = Trainer(cfg, workdir=str(wd))
    state, _ = trainer.train()
    emb = BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                       trainer.mesh, query_tok=trainer.query_tok)
    store = VectorStore(os.path.join(str(wd), "store"),
                        dim=cfg.model.out_dim, shard_size=100)
    store.ensure_model_step(int(state.step))
    emb.embed_corpus(trainer.corpus, store)
    from dnn_page_vectors_tpu.train.checkpoint import CheckpointManager
    mgr = CheckpointManager(os.path.join(str(wd), "ckpt"))
    mgr.save(int(state.step), state, wait=True)
    mgr.close()
    return {"cfg": cfg, "trainer": trainer, "emb": emb, "store": store,
            "wd": str(wd)}


def _grown(corpus: ToyCorpus, num_pages: int) -> ToyCorpus:
    return ToyCorpus(num_pages=num_pages, seed=corpus.seed,
                     num_topics=corpus.num_topics, page_len=corpus.page_len,
                     query_len=corpus.query_len, languages=corpus.languages)


def _copy_store(env, tmp_path):
    dst = os.path.join(str(tmp_path), "store")
    shutil.copytree(env["store"].directory, dst)
    shutil.rmtree(os.path.join(dst, "ivf"), ignore_errors=True)
    return VectorStore(dst)


def _cfg(env, **over):
    import dataclasses
    cfg = env["cfg"]
    for section, kw in over.items():
        cfg = cfg.replace(**{section: dataclasses.replace(
            getattr(cfg, section), **kw)})
    return cfg


def _stored_vecs(store, ids):
    all_ids, all_vecs = store.load_all()
    lut = {int(i): np.asarray(v, np.float32)
           for i, v in zip(all_ids, all_vecs) if i >= 0}
    return np.stack([lut[i] for i in ids])


def _self_hits(store, mesh, ids, k=10):
    _, got = topk_over_store(_stored_vecs(store, ids), store, mesh, k=k)
    return {i: row.tolist() for i, row in zip(ids, got)}


def _grow_and_tombstone(env, store, total=450, tombs=(7, 12, 399)):
    """Two generations on top of the base: +100 pages with two deletions,
    then +50 more deleting an appended page — the chain a compaction
    folds."""
    emb, trainer = env["emb"], env["trainer"]
    append_corpus(emb, _grown(trainer.corpus, 400), store,
                  tombstone=[t for t in tombs if t < 300])
    append_corpus(emb, _grown(trainer.corpus, total), store,
                  tombstone=[t for t in tombs if 300 <= t < 400])
    return _grown(trainer.corpus, total)


def test_compaction_is_byte_deterministic_and_preserves_ids(env, tmp_path):
    """Two identical chains compact to byte-identical bases (data files
    AND manifest); live ids are preserved, dead rows dropped, the append
    cursor survives a tombstoned top id, and the next append chains past
    the folded epoch."""
    emb = env["emb"]
    stores = []
    for sub in ("a", "b"):
        store = _copy_store(env, tmp_path / sub)
        _grow_and_tombstone(env, store)
        assert store.generation == 2 and store.num_vectors == 450
        stats = compact_store(store)
        assert stats["action"] == "compacted"
        assert stats["epoch"] == 2 and stats["dead_rows_dropped"] == 3
        assert stats["rows"] == 447 and stats["bytes_reclaimed"] > 0
        stores.append(store)
    da = os.path.join(stores[0].directory, "compact-0002")
    db = os.path.join(stores[1].directory, "compact-0002")
    names = sorted(os.listdir(da))
    assert names == sorted(os.listdir(db)) and names
    for n in names:
        with open(os.path.join(da, n), "rb") as f:
            ba = f.read()
        with open(os.path.join(db, n), "rb") as f:
            bb = f.read()
        assert ba == bb, f"{n} differs between identical compactions"
    with open(os.path.join(stores[0].directory, "manifest.json"), "rb") as f:
        ma = f.read()
    with open(os.path.join(stores[1].directory, "manifest.json"), "rb") as f:
        mb = f.read()
    assert ma == mb, "compacted manifests differ"
    store = stores[0]
    # id preservation: exactly the live set, nothing renamed
    ids, _ = store.load_all()
    live = sorted(int(i) for i in ids if i >= 0)
    assert live == sorted(set(range(450)) - {7, 12, 399})
    assert store.num_vectors == 447
    # dead-byte accounting reset with the fold
    ms = store.maintenance_stats()
    assert ms["tombstone_density"] == 0.0 and ms["dead_rows"] == 0
    assert ms["compacted_through"] == 2
    # the tombstoned TOP id (399) must not be re-issued: cursor pinned
    assert store.next_page_id() == 450
    # sampled live rows still serve as their own top-1; dead rows gone
    hits = _self_hits(store, emb.mesh, [0, 150, 320, 449])
    for qi in (0, 150, 320, 449):
        assert hits[qi][0] == qi
    dead_vec = _stored_vecs(VectorStore(env["store"].directory), [7])
    _, got = topk_over_store(dead_vec, store, emb.mesh, k=10)
    assert 7 not in got[0].tolist()
    # the chain continues PAST the folded epoch: next append is gen 3
    stats = append_corpus(emb, _grown(env["trainer"].corpus, 500), store)
    assert stats["generation"] == 3
    assert os.path.isdir(os.path.join(store.directory, "gen-0003"))
    assert store.num_vectors == 497 and store.generation == 3
    # a cold re-open sees the same world
    cold = VectorStore(store.directory)
    assert cold.generation == 3 and cold.compacted_through == 2
    assert cold.num_vectors == 497


def test_compaction_exact_results_parity(env, tmp_path):
    """Search results over the compacted base are identical to the
    pre-compaction chain (tombstones were already masked at read time —
    compaction only reclaims their bytes), and a base re-embed over a
    compacted store is refused (it would double-assign)."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    corpus2 = _grow_and_tombstone(env, store)
    cfg = env["cfg"]
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    queries = [corpus2.query_text(i) for i in range(0, 450, 23)]
    before = [[r["page_id"] for r in res]
              for res in svc.search_many(queries, k=10)]
    stats = compact_store(store)
    info = svc.refresh()
    assert info["store_generation"] == 2       # monotonic across the fold
    after = [[r["page_id"] for r in res]
             for res in svc.search_many(queries, k=10)]
    assert after == before, "compaction changed exact search results"
    # metrics surface the (now clean) dead-byte accounting
    met = svc.metrics()
    assert met["tombstone_density"] == 0.0 and met["dead_rows"] == 0
    assert met["reclaimable_bytes"] == 0
    svc.close()
    # purge reclaims the old chain once the view moved over
    purged = purge_stale(store, stats)
    assert purged["purged_dirs"] >= 2 and purged["purged_files"] >= 3
    assert not os.path.isdir(os.path.join(store.directory, "gen-0001"))
    fresh = SearchService(cfg, emb, trainer.corpus,
                          VectorStore(store.directory), preload_hbm_gb=4.0)
    again = [[r["page_id"] for r in res]
             for res in fresh.search_many(queries, k=10)]
    assert again == before
    fresh.close()
    with pytest.raises(ValueError, match="has been compacted"):
        emb.embed_corpus(trainer.corpus, VectorStore(store.directory))


def test_crash_mid_compaction_keeps_old_chain_byte_identical(env, tmp_path):
    """Seeded faults tear a compaction before and AT the swap: both leave
    the old chain serving byte-identical results, and a later fault-free
    compaction succeeds."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    corpus2 = _grow_and_tombstone(env, store)
    cfg = env["cfg"]
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    queries = [corpus2.query_text(i) for i in (3, 77, 320, 449)]
    before = [[r["page_id"] for r in res]
              for res in svc.search_many(queries, k=10)]
    # crash during the data-file writes: the manifest never flipped
    faults.install(faults.FaultPlan.parse("compact_write:io_error:1", seed=0))
    with pytest.raises(IOError):
        compact_store(VectorStore(store.directory))
    # crash AT the swap itself (persistent, so the retry wrapper can't
    # save it): same outcome — the flip is the commit point
    faults.install(faults.FaultPlan.parse("compact_swap_dump:io_error:0:*",
                                          seed=0))
    with pytest.raises(IOError):
        compact_store(VectorStore(store.directory))
    faults.install(faults.FaultPlan())
    cold = VectorStore(store.directory)
    assert cold.compacted_through == 0 and cold.generation == 2
    assert cold.num_vectors == 450
    info = svc.refresh()
    assert info["store_generation"] == 2
    after = [[r["page_id"] for r in res]
             for res in svc.search_many(queries, k=10)]
    assert after == before, "torn compaction changed serving results"
    svc.close()
    # the torn attempt's debris does not block the fault-free retry
    stats = compact_store(VectorStore(store.directory))
    assert stats["action"] == "compacted" and stats["rows"] == 447
    assert VectorStore(store.directory).compacted_through == 2


def test_two_writer_lease_contention_never_double_assigns(env, tmp_path):
    """Two concurrent append_corpus writers on one store: the lease
    serializes the cursor — one appends the range, the other queues and
    finds nothing left (noop), and no page id is ever assigned twice."""
    emb, trainer = env["emb"], env["trainer"]
    store_dir = _copy_store(env, tmp_path).directory
    corpus2 = _grown(trainer.corpus, 400)
    results, errors = [], []
    gate = threading.Barrier(2)

    def _writer(wid):
        try:
            gate.wait()
            store = VectorStore(store_dir)
            results.append(append_corpus(emb, corpus2, store))
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=_writer, args=(w,)) for w in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, f"leased concurrent appends raised: {errors[:2]}"
    appended = sorted(r["appended"] for r in results)
    assert appended == [0, 100], appended   # one wrote, one found a noop
    store = VectorStore(store_dir)
    assert store.generation == 1 and store.num_vectors == 400
    ids, _ = store.load_all()
    live = [int(i) for i in ids if i >= 0]
    assert len(live) == len(set(live)) == 400, "double-assigned page ids"
    evs = telemetry.default_registry().events("lease_acquired")
    assert len(evs) >= 2


def test_lease_fail_fast_steal_and_lost_renew(env, tmp_path):
    """The lease protocol's edges: a held lease fails a zero-wait second
    writer fast; an EXPIRED lease is stolen (event recorded); the original
    holder's renew then reports LeaseLost."""
    store = _copy_store(env, tmp_path)
    a = AppendLease(store, owner="writer-a", ttl_s=0.4, wait_s=0.0).acquire()
    assert a.held and a.stole_from is None
    with pytest.raises(LeaseHeld, match="held by writer-a"):
        AppendLease(store, owner="writer-b", ttl_s=0.4,
                    wait_s=0.0).acquire()
    time.sleep(0.5)                         # writer-a's ttl runs out
    b = AppendLease(store, owner="writer-b", ttl_s=5.0, wait_s=0.0).acquire()
    assert b.held and b.stole_from == "writer-a"
    reg = telemetry.default_registry()
    assert len(reg.events("lease_stolen")) == 1
    with pytest.raises(LeaseLost):
        a.renew()
    b.renew()                               # the live holder renews fine
    b.release()
    assert not os.path.exists(os.path.join(store.directory,
                                           "append.lease.json"))
    # a queued writer acquires as soon as the holder releases
    c = AppendLease(store, owner="writer-c", ttl_s=1.0, wait_s=2.0)
    assert c.acquire().held
    c.release()


def test_background_rebuild_hot_swap_under_query_hammer(env, tmp_path):
    """The off-path rebuild pin (docs/MAINTENANCE.md): a drift overrun
    defers off the refresh() caller (incremental append still lands,
    full_rebuilds stays 0), then the background worker builds the next
    index generation beside the live one and pointer-flips it in while a
    concurrent query hammer observes zero errors and zero mixed result
    sets; full_rebuilds moves exactly once — in the worker."""
    import dataclasses
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    IVFIndex.build(store, emb.mesh, nlist=8, iters=3, seed=0)
    cfg = _cfg(env, serve={"index": "ivf", "nlist": 8, "nprobe": 8,
                           "batch_window_ms": 2.0, "max_batch": 8},
               updates={"rebuild_drift": 0.05})
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    assert svc._index is not None
    maint = svc.start_maintenance(threads=False)
    assert svc._defer_rebuilds
    svc.start_batcher()
    corpus2 = _grown(trainer.corpus, 400)
    append_corpus(emb, corpus2, store)      # 100/400 = 0.25 drift > 0.05
    info = svc.refresh()
    # deferred: the incremental append served the new docs, no inline
    # rebuild ran, and the pending flag is the hand-off to the worker
    assert info["index_update"]["action"] == "incremental"
    assert info["index_update"]["rebuild_pending"] is True
    assert svc.full_rebuilds == 0 and svc.incremental_updates == 1
    assert svc.registry.gauge("serve.index_rebuild_pending").value == 1.0
    qids = [3, 42, 250, 320]
    queries = {qi: corpus2.query_text(qi) for qi in qids}
    before = {qi: tuple(r["page_id"] for r in svc.search(queries[qi], k=10))
              for qi in qids}
    stop = threading.Event()
    errors, observed = [], {qi: set() for qi in qids}

    def hammer(qi):
        while not stop.is_set():
            try:
                observed[qi].add(tuple(
                    r["page_id"] for r in svc.search(queries[qi], k=10)))
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=hammer, args=(qi,))
               for qi in qids for _ in range(2)]
    for t in threads:
        t.start()
    out = maint.run_once()                  # the background rebuild
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    after = {qi: tuple(r["page_id"] for r in svc.search(queries[qi], k=10))
             for qi in qids}
    assert not errors, f"bg rebuild hot-swap raised: {errors[:3]}"
    for qi in qids:
        extra = observed[qi] - {before[qi], after[qi]}
        assert not extra, (f"query {qi} saw a mixed result set during the "
                           f"bg swap: {extra}")
    rb = out["rebuild"]
    assert rb["dirname"] == "ivf-0001" and rb["swap_ms"] >= 0
    # the rebuild happened ONLY in the worker, and the swap took
    assert svc.full_rebuilds == 1
    assert svc.registry.gauge("serve.index_rebuild_pending").value == 0.0
    assert svc.store.index_dirname == "ivf-0001"
    assert svc._index is not None and svc._index.index_generation == 0
    assert len(svc.registry.events("index_rebuild_bg")) == 1
    # recall contract on the merged corpus through the swapped index
    qv = np.asarray(emb.embed_texts(
        [corpus2.query_text(i) for i in range(0, 400, 13)],
        tower="query"), np.float32)
    r = recall_vs_exact(svc._index, svc.store, qv, emb.mesh, k=10, nprobe=8)
    assert r >= 0.95, f"post-bg-rebuild recall {r:.3f} < 0.95"
    # the janitor reclaims the superseded index generation
    out2 = maint.run_once()
    assert out2.get("janitor", {}).get("index_dirs_removed") == 1
    assert not os.path.isdir(os.path.join(store.directory, "ivf"))
    svc.close()


def test_maintenance_service_compaction_end_to_end(env, tmp_path):
    """The compactor pillar through the service: tombstone past the
    threshold, one run_once folds the chain, rebuilds the index over the
    compacted base, hot-swaps the serving view, and purges the old chain
    — results identical throughout, accounting visible in metrics()."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    IVFIndex.build(store, emb.mesh, nlist=8, iters=3, seed=0)
    cfg = _cfg(env, serve={"index": "ivf", "nlist": 8, "nprobe": 8},
               maintenance={"compact_tombstone_density": 0.05})
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    maint = svc.start_maintenance(threads=False)
    # 30 dead of 300 = 10% > 5% threshold
    append_corpus(emb, trainer.corpus, store,
                  tombstone=list(range(40, 70)))
    svc.refresh()
    met = svc.metrics()
    assert met["dead_rows"] == 30 and met["tombstone_density"] == 0.1
    assert met["reclaimable_bytes"] > 0
    queries = [trainer.corpus.query_text(i) for i in (2, 99, 222)]
    before = [[r["page_id"] for r in res]
              for res in svc.search_many(queries, k=10)]
    out = maint.run_once()
    comp = out["compaction"]
    assert comp["action"] == "compacted"
    assert comp["dead_rows_dropped"] == 30 and comp["bytes_reclaimed"] > 0
    assert comp["index_rebuild"]["dirname"] == "ivf-0001"
    after = [[r["page_id"] for r in res]
             for res in svc.search_many(queries, k=10)]
    assert after == before
    met = svc.metrics()
    assert met["dead_rows"] == 0 and met["tombstone_density"] == 0.0
    assert met["store_generation"] == 1      # monotonic across the fold
    assert svc.full_rebuilds == 1            # the compaction's bg rebuild
    assert svc.ann_fallbacks == 0
    assert len(svc.registry.events("compaction")) == 1
    # the old chain's bytes are gone (purged after the view swap)
    assert not os.path.isdir(os.path.join(store.directory, "gen-0001"))
    assert not os.path.exists(os.path.join(store.directory,
                                           "shard_00000.vec.npy"))
    # quiescent second pass: nothing to do
    out2 = maint.run_once()
    assert "compaction" not in out2 and "rebuild" not in out2
    # pause/drain API surface
    maint.pause()
    maint.resume()
    assert maint.drain(timeout_s=1.0)
    assert maint.stats()["passes"]["compaction"] >= 1
    svc.close()


def test_maintenance_under_fire_loadgen_pin(env, tmp_path):
    """The end-to-end acceptance pin (docs/MAINTENANCE.md): a seeded
    loadgen trial with the compaction+rebuild mutator active — tombstone
    bursts alternate with full maintenance passes — keeps serving with
    zero errors and a bounded windowed p99 vs the quiescent trial;
    compaction measurably reclaims bytes, every full rebuild happens in
    the background worker (none inline), and post-compaction recall@10
    vs exact holds the 0.95 contract on the merged corpus."""
    from dnn_page_vectors_tpu.loadgen import (Mutator, make_workload,
                                              run_trial)
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    IVFIndex.build(store, emb.mesh, nlist=8, iters=3, seed=0)
    cfg = _cfg(env, serve={"index": "ivf", "nlist": 8, "nprobe": 8,
                           "batch_window_ms": 2.0, "max_batch": 8},
               obs={"window_s": 2.5},
               maintenance={"compact_tombstone_density": 0.02})
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    maint = svc.start_maintenance(threads=False)
    svc.start_batcher()
    queries = [trainer.corpus.query_text(i) for i in range(16)]
    wl = make_workload("poisson", seed=3, distinct=16)
    quiet = run_trial(svc, wl, 25.0, queries, duration_s=2.5,
                      warmup_s=0.0, workers=4)
    assert quiet["errors"] == 0 and quiet["p99_ms"] > 0

    tomb = {"next": 0}

    def _tombstone_refresh():
        ids = list(range(tomb["next"], tomb["next"] + 12))
        tomb["next"] += 12
        append_corpus(emb, trainer.corpus, svc.store, tombstone=ids)
        svc.refresh()

    mut = Mutator(ops=[("tombstone_refresh", _tombstone_refresh),
                       ("maintain", maint.run_once)], period_s=0.8)
    fire = run_trial(svc, wl, 25.0, queries, duration_s=2.5,
                     warmup_s=0.0, workers=4, mutator=mut)
    assert not mut.errors, mut.errors
    assert fire["errors"] == 0
    assert fire["mutator_calls_by_op"]["tombstone_refresh"] >= 1
    assert fire["mutator_calls_by_op"]["maintain"] >= 1
    reg = svc.registry
    # the compactor really fired and reclaimed bytes, under load
    assert len(reg.events("compaction")) >= 1
    reclaimed = reg.counter("maintenance.compact_bytes_reclaimed").value
    assert reclaimed > 0
    assert svc.store.compacted_through >= 1
    # full rebuilds happened ONLY in the background worker: every one is
    # an index_rebuild_bg event, and the inline drift_rebuild path never
    # ran (the deferral gauge mechanism, docs/MAINTENANCE.md)
    assert svc.full_rebuilds == len(reg.events("index_rebuild_bg")) >= 1
    assert len(reg.events("drift_rebuild")) == 0
    # serving stayed within the maintenance SLO envelope of the quiescent
    # trial (25% + a small toy-scale noise floor; p99 under compaction on
    # a real store has no cell yet: PERF.md section 7)
    budget = 1.25 * quiet["p99_ms"] + 5.0
    assert fire["p99_ms"] <= budget, (
        f"p99 under maintenance {fire['p99_ms']:.2f} ms vs quiescent "
        f"{quiet['p99_ms']:.2f} ms (budget {budget:.2f} ms)")
    # recall contract through the swapped-in post-compaction index
    assert svc._index is not None and svc.ann_fallbacks == 0
    qv = np.asarray(emb.embed_texts(
        [trainer.corpus.query_text(i) for i in range(0, 300, 11)],
        tower="query"), np.float32)
    r = recall_vs_exact(svc._index, svc.store, qv, emb.mesh, k=10, nprobe=8)
    assert r >= 0.95, f"post-compaction recall {r:.3f} < 0.95"
    svc.close()


def test_cli_maintain_once_json(env, tmp_path, capsys):
    """`cli maintain --once` over a tombstoned store: one JSON line whose
    compaction block reports the fold; a second pass is quiescent."""
    from dnn_page_vectors_tpu import cli
    wd = os.path.join(str(tmp_path), "wd")
    shutil.copytree(env["wd"], wd)
    base = ["--config", "cdssm_toy", "--workdir", wd] + [
        x for key, val in _OV.items() for x in ("--set", f"{key}={val}")]
    low = ["--set", "maintenance.compact_tombstone_density=0.05"]
    cli.main(["append"] + base + ["--tombstone",
                                  ",".join(str(i) for i in range(40, 70))])
    capsys.readouterr()
    cli.main(["maintain", "--once"] + base + low)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["compaction"]["action"] == "compacted"
    assert out["compaction"]["dead_rows_dropped"] == 30
    assert out["compaction"]["bytes_reclaimed"] > 0
    cli.main(["maintain", "--once"] + base + low)
    out2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "compaction" not in out2
    store = VectorStore(os.path.join(wd, "store"))
    assert store.compacted_through == 1 and store.num_vectors == 270
