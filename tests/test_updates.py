"""Live corpus updates (updates/, docs/UPDATES.md): append-only store
generations with tombstones, byte-deterministic appends, incremental IVF
refresh in O(new shards) with drift-triggered rebuilds, zero-downtime
serving hot-swap under concurrent queries, fault-injection on the new
write paths, and the no-double-assign contract after shard quarantine.

Presence checks query with the STORED vectors themselves (self-similarity
1 under the store's unit-norm invariant), so they pin the update
machinery — are appended rows servable, are tombstoned rows dead — rather
than the tiny test model's generalization to pages it never trained on."""
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
from dnn_page_vectors_tpu.mine.ann import mine_hard_negatives
from dnn_page_vectors_tpu.ops.topk import topk_over_store
from dnn_page_vectors_tpu.train.loop import Trainer
from dnn_page_vectors_tpu.updates import append_corpus
from dnn_page_vectors_tpu.utils import faults

pytestmark = pytest.mark.updates

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
}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    """One trained model + embedded 3-shard base store for the module;
    every mutating test works on a private copy."""
    wd = tmp_path_factory.mktemp("updates_env")
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
    """The same deterministic corpus with more pages: page i's text is a
    pure function of (seed, i), so growth never rewrites history."""
    return ToyCorpus(num_pages=num_pages, seed=corpus.seed,
                     num_topics=corpus.num_topics, page_len=corpus.page_len,
                     query_len=corpus.query_len, languages=corpus.languages)


def _copy_store(env, tmp_path):
    dst = os.path.join(str(tmp_path), "store")
    shutil.copytree(env["store"].directory, dst)
    shutil.rmtree(os.path.join(dst, "ivf"), ignore_errors=True)
    return VectorStore(dst)


def _ivf_cfg(env, **serve_kw):
    import dataclasses
    serve = dataclasses.replace(env["cfg"].serve, index="ivf", **serve_kw)
    return env["cfg"].replace(serve=serve)


def _stored_vecs(store, ids):
    """The live stored vectors for `ids` (fp32, unit-norm)."""
    all_ids, all_vecs = store.load_all()
    lut = {int(i): np.asarray(v, np.float32)
           for i, v in zip(all_ids, all_vecs) if i >= 0}
    return np.stack([lut[i] for i in ids])


def _self_hits(store, mesh, ids, k=10):
    """Exact top-k per id, queried with its OWN stored vector: a live row
    must come back top-1 (self-similarity 1); a tombstoned one must not
    come back at all."""
    _, got = topk_over_store(_stored_vecs(store, ids), store, mesh, k=k)
    return {i: row.tolist() for i, row in zip(ids, got)}


def test_append_covers_new_pages_and_is_byte_deterministic(env, tmp_path):
    """An append embeds only the new id-range into gen-0001, exact search
    serves the appended rows, and two fault-free appends of the same range
    are byte-identical (generation files AND manifest)."""
    emb, trainer = env["emb"], env["trainer"]
    corpus2 = _grown(trainer.corpus, 400)
    stores = []
    for sub in ("a", "b"):
        store = _copy_store(env, tmp_path / sub)
        stats = append_corpus(emb, corpus2, store)
        assert stats["generation"] == 1
        assert stats["appended"] == 100 and stats["tombstoned"] == 0
        assert store.num_vectors == 400 and store.generation == 1
        assert store.next_page_id() == 400
        stores.append(store)
    ga = os.path.join(stores[0].directory, "gen-0001")
    gb = os.path.join(stores[1].directory, "gen-0001")
    names = sorted(os.listdir(ga))
    assert names == sorted(os.listdir(gb)) and "manifest.json" in names
    for n in names:
        with open(os.path.join(ga, n), "rb") as f:
            ba = f.read()
        with open(os.path.join(gb, n), "rb") as f:
            bb = f.read()
        assert ba == bb, f"{n} differs between identical appends"
    # every sampled appended row is servable through the exact sweep
    hits = _self_hits(stores[0], emb.mesh, [310, 350, 399, 5])
    for qi in (310, 350, 399, 5):
        assert hits[qi][0] == qi, f"stored row {qi} not its own top-1"
    # a second append chains gen-0002 past the new cursor
    stats = append_corpus(emb, _grown(trainer.corpus, 450), stores[0])
    assert stats["generation"] == 2 and stats["appended"] == 50
    assert stores[0].num_vectors == 450


def test_tombstone_deletes_and_update_reembeds(env, tmp_path):
    """A tombstoned page vanishes from exact search; an updated page keeps
    serving (exactly once) from its new-generation row."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    stats = append_corpus(emb, trainer.corpus, store,
                          tombstone=[7], update_ids=[12])
    assert stats["appended"] == 0 and stats["updated"] == 1
    assert stats["tombstoned"] == 2       # the delete + the update's old row
    assert store.num_vectors == 301       # 300 base + 1 re-embedded row
    # query with page 7's OLD stored vector (pre-tombstone copy): the row
    # itself must be dead — absent even from its own neighborhood
    pristine = VectorStore(env["store"].directory)
    dead_vec = _stored_vecs(pristine, [7])
    _, got = topk_over_store(dead_vec, store, emb.mesh, k=10)
    assert 7 not in got[0].tolist(), "tombstoned row still servable"
    # the updated page serves exactly once, from the new generation
    hits = _self_hits(store, emb.mesh, [12])
    assert hits[12][0] == 12 and hits[12].count(12) == 1
    # masking survives a cold re-open
    _, got2 = topk_over_store(dead_vec, VectorStore(store.directory),
                              emb.mesh, k=10)
    assert 7 not in got2[0].tolist()
    with pytest.raises(ValueError, match="not an existing page"):
        append_corpus(emb, trainer.corpus, store, tombstone=[500])


def test_incremental_ivf_update_is_o_new_shards(env, tmp_path):
    """IVFIndex.update after an append assigns ONLY the new generation's
    shards (info says so), keeps full-probe == exact on the merged corpus,
    and a drift overrun forces a rebuild instead."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    IVFIndex.build(store, emb.mesh, nlist=8, iters=3, seed=0)
    corpus2 = _grown(trainer.corpus, 400)
    append_corpus(emb, corpus2, store, tombstone=[5])
    idx, info = IVFIndex.update(store, emb.mesh, rebuild_drift=0.5)
    assert info["action"] == "incremental"
    assert info["new_shards"] == 1 and info["appended_rows"] == 100
    assert idx.index_generation == 1
    assert int(idx.list_sizes.sum()) == 400
    # full probe == exact on the merged corpus, tombstone absent from both
    qv = np.asarray(emb.embed_texts(
        [corpus2.query_text(i) for i in (5, 50, 250, 320, 399)],
        tower="query"), np.float32)
    _, ann_ids, _ = idx.search(qv, k=10, nprobe=8)
    _, exact_ids = topk_over_store(qv, store, emb.mesh, k=10)
    for a, e in zip(ann_ids, exact_ids):
        assert set(a.tolist()) == set(e.tolist())
    # the tombstoned row is dead through the ANN path too (queried with
    # its own old vector, full probe)
    dead_vec = _stored_vecs(VectorStore(env["store"].directory), [5])
    _, ann_dead, _ = idx.search(dead_vec, k=10, nprobe=8)
    assert 5 not in ann_dead[0].tolist()
    # appended rows servable through the index at the default nprobe
    _, ann_new, _ = idx.search(_stored_vecs(store, [320, 399]), k=10,
                               nprobe=env["cfg"].serve.nprobe)
    assert ann_new[0][0] == 320 and ann_new[1][0] == 399
    # recall-vs-exact contract holds at the default nprobe
    r = recall_vs_exact(idx, store, qv, emb.mesh, k=10,
                        nprobe=env["cfg"].serve.nprobe)
    assert r >= 0.95, f"post-append ANN recall {r:.3f} < 0.95"
    # another append pushing drift over a tiny threshold -> full rebuild
    append_corpus(emb, _grown(trainer.corpus, 430), store)
    idx2, info2 = IVFIndex.update(store, emb.mesh, rebuild_drift=0.01)
    assert info2["action"] == "rebuild"
    assert idx2.index_generation == 0
    assert int(idx2.list_sizes.sum()) == 430


def test_refresh_hot_swap_under_concurrent_queries(env, tmp_path):
    """The e2e acceptance run: an IVF service under a concurrent query
    hammer (through the micro-batcher) while append + refresh() swap in a
    new generation — zero exceptions, every observed result set is exactly
    the old view's or the new view's (never a mix), appended pages become
    servable, the tombstoned page disappears, recall@10 vs exact stays
    >= 0.95 on the merged corpus, and the update cost was O(new shards)
    (full_rebuilds == 0)."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    IVFIndex.build(store, emb.mesh, seed=0)          # auto nlist (~sqrt N)
    # nprobe 12 of ~17 lists: the toy corpus is tiny, so the recall>=0.95
    # contract needs a wider probe than the production default of 8 —
    # still sublinear, and the drift/O(new shards) accounting is identical
    cfg = _ivf_cfg(env, batch_window_ms=2.0, max_batch=8, nprobe=12)
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    assert svc._index is not None
    svc.start_batcher()
    cand = list(range(0, 300, 13))
    queries = {qi: trainer.corpus.query_text(qi) for qi in cand}
    first = {qi: tuple(r["page_id"] for r in svc.search(queries[qi], k=10))
             for qi in cand}
    # tombstone a page the service demonstrably RETRIEVES for its gold
    # query, so its disappearance is observable service-side
    victims = [qi for qi in cand if qi in first[qi]]
    assert victims, "test model retrieves no gold at all; cannot proceed"
    victim = victims[0]
    qids = [victim] + [qi for qi in cand if qi != victim][:3]
    before = {qi: first[qi] for qi in qids}
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
    corpus2 = _grown(trainer.corpus, 400)
    append_corpus(emb, corpus2, store, tombstone=[victim])
    info = svc.refresh()
    time.sleep(0.3)                       # let queries land on the new view
    stop.set()
    for t in threads:
        t.join()
    after = {qi: tuple(r["page_id"] for r in svc.search(queries[qi], k=10))
             for qi in qids}
    assert not errors, f"hot-swap raised: {errors[:3]}"
    for qi in qids:
        extra = observed[qi] - {before[qi], after[qi]}
        assert not extra, (f"query {qi} saw a mixed result set during the "
                           f"swap: {extra}")
    # the swap took effect: tombstone out (service-level), appended rows
    # servable (vector-level, through the live service's index)
    assert victim not in after[victim]
    _, ann_new, _ = svc._index.search(
        _stored_vecs(svc.store, [320, 399]), k=10, nprobe=cfg.serve.nprobe)
    assert ann_new[0][0] == 320 and ann_new[1][0] == 399
    # O(new shards): the index was extended, never rebuilt
    assert info["index_update"]["action"] == "incremental"
    assert svc.incremental_updates == 1 and svc.full_rebuilds == 0
    assert svc.ann_fallbacks == 0
    met = svc.metrics()
    assert met["store_generation"] == 1
    assert met["index_generation"] == 1
    assert met["docs_appended"] == 100
    assert met["tombstoned"] == 1
    assert met["refreshes"] == 1
    assert met["incremental_updates"] == 1 and met["full_rebuilds"] == 0
    # recall@10 vs exact >= 0.95 on the merged corpus through the live index
    qv = np.asarray(emb.embed_texts(
        [corpus2.query_text(i) for i in range(0, 400, 13)],
        tower="query"), np.float32)
    r = recall_vs_exact(svc._index, svc.store, qv, emb.mesh, k=10,
                        nprobe=cfg.serve.nprobe)
    assert r >= 0.95, f"post-swap ANN recall {r:.3f} < 0.95"
    svc.close()


def test_torn_generation_manifest_quarantined_keeps_prev_generation(
        env, tmp_path):
    """A seeded fault tears the generation manifest mid-append: readers
    quarantine that generation (counted) and a serving refresh keeps
    answering from the previous one — results identical to pre-append."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    svc = SearchService(env["cfg"], emb, trainer.corpus, store,
                        preload_hbm_gb=4.0)
    q = trainer.corpus.query_text(42)
    before = [r["page_id"] for r in svc.search(q, k=10)]
    faults.install(faults.FaultPlan.parse("gen_manifest_file:truncate:0",
                                          seed=3))
    corpus2 = _grown(trainer.corpus, 400)
    append_corpus(emb, corpus2, store, tombstone=[42])   # manifest lands torn
    faults.install(faults.FaultPlan())    # stop injecting, keep counters
    info = svc.refresh()
    assert faults.counters().get("quarantined_generations") == 1
    assert info["store_generation"] == 0 and info["new_docs"] == 0
    assert svc.metrics()["store_generation"] == 0
    assert svc.metrics()["tombstoned"] == 0
    after = [r["page_id"] for r in svc.search(q, k=10)]
    assert after == before                # previous generation still serves
    svc.close()
    # the next append REUSES the quarantined number and serves normally
    store2 = VectorStore(store.directory)
    stats = append_corpus(emb, corpus2, store2)
    assert stats["generation"] == 1 and store2.num_vectors == 400


def test_posting_append_fault_degrades_to_exact_with_counters(env, tmp_path):
    """A persistent injected fault on the posting-append write path makes
    the index update fail: the service keeps serving (exact fallback over
    the NEW generation — appended rows servable), the index manifest
    stays untouched, and the failure surfaces in metrics()."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    IVFIndex.build(store, emb.mesh, nlist=8, iters=3, seed=0)
    cfg = _ivf_cfg(env)
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    assert svc._index is not None
    corpus2 = _grown(trainer.corpus, 400)
    append_corpus(emb, corpus2, store)
    faults.install(faults.FaultPlan.parse("index_write:io_error:0:*", seed=0))
    info = svc.refresh()
    faults.install(faults.FaultPlan())
    assert svc._index is None and "index_error" in info
    assert svc.fault_counters.get("serve_index_update_failures") == 1
    met = svc.metrics()
    assert met["store_generation"] == 1   # the STORE swap still happened
    assert met["index_generation"] is None
    assert "serve_index_update_failures" in met["fault_counters"]
    # exact fallback serves the new generation: an appended row queried
    # with its own stored vector comes back top-1, counted as a fallback
    res = svc.search_many(
        [corpus2.query_text(i) for i in (350, 399)], k=10)
    assert all(len(r) == 10 for r in res)
    assert svc.ann_fallbacks >= 2
    hits = _self_hits(svc.store, emb.mesh, [350, 399])
    assert hits[350][0] == 350 and hits[399][0] == 399
    # a later fault-free refresh repairs the index incrementally (the
    # on-disk manifest was never touched by the failed update)
    info2 = svc.refresh()
    assert info2["index_update"]["action"] == "incremental"
    assert svc._index is not None and svc._index.index_generation == 1
    svc.close()


def test_tombstone_aware_restage_policy(env, tmp_path):
    """The restage policy (updates.restage_tombstone_density,
    docs/UPDATES.md): a refresh after a SMALL tombstone burst reuses the
    staged device shards (restage_skipped counted, dead rows masked in
    the id table — the victim never surfaces), while a burst past the
    density threshold forces a compacted restage (restage_forced) whose
    results match a fresh exact service bit for bit."""
    import dataclasses
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    cfg = env["cfg"].replace(updates=dataclasses.replace(
        env["cfg"].updates, restage_tombstone_density=0.05))
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    assert svc.preloaded
    # 1 dead row of 100 in shard 0 (1% <= 5%): reuse with masking
    append_corpus(emb, trainer.corpus, store, tombstone=[7])
    svc.refresh()
    assert svc.restage_skipped >= 1 and svc.restage_forced == 0
    met = svc.metrics()
    assert met["restage_skipped"] == svc.restage_skipped
    # the dead row's device copy was NOT restaged — the id-table masking
    # alone must keep it from ever surfacing, even for its gold query
    res = svc.search(trainer.corpus.query_text(7), k=10)
    assert all(r["page_id"] != 7 for r in res)
    # 10 more dead rows in shard 0 (11% > 5%): forced compacted restage
    append_corpus(emb, trainer.corpus, store,
                  tombstone=list(range(10, 20)))
    svc.refresh()
    assert svc.restage_forced >= 1
    fresh = SearchService(cfg, emb, trainer.corpus,
                          VectorStore(store.directory), preload_hbm_gb=4.0)
    queries = [trainer.corpus.query_text(i) for i in (2, 77, 290)]
    got = svc.search_many(queries, k=10)
    want = fresh.search_many(queries, k=10)
    assert [[r["page_id"] for r in g] for g in got] == \
        [[r["page_id"] for r in w] for w in want]
    svc.close()


def _spans(shards):
    return [np.asarray(s.span).tolist() for s in shards]


def test_reused_shards_keep_their_device_row_count(env, tmp_path):
    """The scan's `span` argument (row count, first combined id) is staged
    with the shard (SearchService._stage_view): a refresh that appends a
    generation, and one that only adds tombstones under the restage
    threshold, hand every reused shard on with the SAME device array (and
    device rows) it had, the appended shard gets its own, and the masked
    shard keeps the count of the rows its device copy still holds — the
    tombstoned page never surfaces."""
    import dataclasses
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    cfg = env["cfg"].replace(updates=dataclasses.replace(
        env["cfg"].updates, restage_tombstone_density=0.05))
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    base = svc._view.shards
    assert _spans(base) == [[100, 0], [100, 100], [100, 200]]

    append_corpus(emb, _grown(trainer.corpus, 350), store)
    svc.refresh()
    grown = svc._view.shards
    assert len(grown) == 4
    for old, new in zip(base, grown):
        assert new.span is old.span and new.pages is old.pages
    assert grown[3].n == 50 and _spans(grown)[3] == [50, 300]
    assert grown[3].span.dtype == np.int32

    dead_vec = _stored_vecs(store, [7, 340])
    append_corpus(emb, _grown(trainer.corpus, 350), store, tombstone=[7])
    svc.refresh()
    assert svc.restage_skipped >= 1 and svc.restage_forced == 0
    masked = svc._view.shards
    for old, new in zip(grown, masked):
        assert new.span is old.span and new.pages is old.pages
    assert masked[0].n == 100 and _spans(masked)[0] == [100, 0]
    assert 7 not in masked[0].ids and 7 in grown[0].ids
    _, got = svc.topk_vectors(dead_vec, k=10)
    assert 7 not in got[0].tolist(), "tombstoned row still servable"
    assert got[1][0] == 340             # the appended shard serves
    svc.close()


def test_reused_shard_in_a_new_slot_answers_with_that_slots_ids(
        env, tmp_path):
    """A shard's id offset belongs to its SLOT in the view, not to its
    bytes: once a shard ahead of it has left the view, a shard reused
    across an append and across a masked-tombstone refresh keeps its
    device rows and its row count, takes the offset of the slot it now
    stands in, and the carried scan's combined ids still resolve to its
    own pages through the view's id table."""
    import dataclasses
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    cfg = env["cfg"].replace(updates=dataclasses.replace(
        env["cfg"].updates, restage_tombstone_density=0.05))
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    base = svc._view.shards
    probe = _stored_vecs(store, [150, 250, 207])
    # shard 0 leaves the view (corrupt bytes: quarantined at the next
    # open) in the refresh that appends a generation
    victim = os.path.join(store.directory, "shard_00000.vec.npy")
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    append_corpus(emb, _grown(trainer.corpus, 350),
                  VectorStore(store.directory))
    svc.refresh()
    moved = svc._view.shards
    assert _spans(moved) == [[100, 0], [100, 100], [50, 200]]
    for old, new in zip(base[1:], moved):
        assert new.pages is old.pages and new.n == old.n
        assert new.span is not old.span
    _, got = svc.topk_vectors(probe, k=10)
    assert [int(r[0]) for r in got] == [150, 250, 207]
    assert not (got < 100).any()         # the quarantined range is gone
    # a tombstone under the threshold: masked in place, in the new slots
    append_corpus(emb, _grown(trainer.corpus, 350),
                  VectorStore(store.directory), tombstone=[207])
    svc.refresh()
    assert svc.restage_skipped >= 1 and svc.restage_forced == 0
    masked = svc._view.shards
    for old, new in zip(moved, masked):
        assert new.pages is old.pages and new.span is old.span
    assert _spans(masked)[:2] == [[100, 0], [100, 100]]
    assert 207 not in masked[1].ids and 207 in moved[1].ids
    _, got = svc.topk_vectors(probe, k=10)
    assert [int(r[0]) for r in got[:2]] == [150, 250]
    assert 207 not in got[2].tolist(), "tombstoned row still servable"
    svc.close()


def test_quarantine_plus_append_never_double_assigns(env, tmp_path):
    """The no-double-assign contract: a quarantined base shard leaves its
    id-range discoverable (missing_id_ranges), the append cursor skips it,
    and the range comes back through embed resume — never through new
    documents."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    victim = os.path.join(store.directory, "shard_00001.vec.npy")
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    store = VectorStore(store.directory)          # verify -> quarantine
    assert store.missing_id_ranges() == [(100, 200)]
    assert store.num_vectors == 200
    assert store.next_page_id() == 300            # NOT 300-100
    corpus2 = _grown(trainer.corpus, 350)
    stats = append_corpus(emb, corpus2, store)
    assert stats["id_start"] == 300 and stats["id_end"] == 350
    gen_ids = store.load_ids(
        {s["index"]: s for s in store.shards()}[3])
    assert gen_ids.min() == 300, "append re-issued a quarantined id"
    # the appended shard index also skipped the quarantined one's slot
    assert sorted(s["index"] for s in store.shards()) == [0, 2, 3]
    # embed resume re-embeds exactly the quarantined range
    emb.embed_corpus(trainer.corpus, store)
    assert store.missing_id_ranges() == []
    assert store.num_vectors == 350
    hits = _self_hits(store, emb.mesh, [150, 320])
    assert hits[150][0] == 150 and hits[320][0] == 320


def test_mine_incremental_start_extends_table(env, tmp_path):
    """After an append, mine_hard_negatives(start=N) mines only the new
    queries against the grown store and splices them onto the existing
    table — old rows byte-identical, new rows valid."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    out = os.path.join(str(tmp_path), "negs.npy")
    negs = mine_hard_negatives(emb, trainer.corpus, store, num_negatives=4,
                               search_k=20, out_path=out)
    base = np.array(negs.table)
    assert base.shape == (300, 4)
    corpus2 = _grown(trainer.corpus, 380)
    append_corpus(emb, corpus2, store)
    negs2 = mine_hard_negatives(emb, corpus2, store, num_negatives=4,
                                search_k=20, out_path=out, start=300)
    assert negs2.table.shape == (380, 4)
    np.testing.assert_array_equal(np.array(negs2.table[:300]), base)
    fresh = np.array(negs2.table[300:])
    assert (fresh >= 0).all() and (fresh < 380).all()
    gold = np.arange(300, 380)[:, None]
    assert not (fresh == gold).any(), "a gold page leaked into its negatives"
    with pytest.raises(ValueError, match="existing mined table"):
        mine_hard_negatives(emb, corpus2, store, num_negatives=4,
                            search_k=20, out_path=out + ".missing",
                            start=300)


def test_cli_append_refresh_and_index_json(env, tmp_path, capsys):
    """`cli index` reports the k-means++ seeding and imbalance delta;
    `cli append` grows the corpus into a generation and auto-updates the
    index; `cli refresh` is then a no-op; `cli search` serves the
    generational store through the index with the tombstone masked."""
    from dnn_page_vectors_tpu import cli
    wd = os.path.join(str(tmp_path), "wd")
    shutil.copytree(env["wd"], wd)
    base = ["--config", "cdssm_toy", "--workdir", wd] + [
        x for key, val in _OV.items() for x in ("--set", f"{key}={val}")]
    cli.main(["index"] + base + ["--set", "serve.nlist=16"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["kmeans_init"] == "kmeans++"
    assert out["imbalance_init"] >= 1.0 and out["imbalance"] >= 1.0
    assert round(out["imbalance_init"] - out["imbalance"], 4) == \
        out["imbalance_delta"]
    grown = ["--set", "data.num_pages=360"]
    cli.main(["append"] + base + grown + ["--tombstone", "3"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["store_generation"] == 1 and out["appended"] == 60
    assert out["tombstoned"] == 1
    assert out["index_update"]["action"] == "incremental"
    cli.main(["refresh"] + base + grown)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["action"] == "noop" and out["index_generation"] == 1
    assert out["store_generation"] == 1
    # search over the generational store through the index: full result
    # set, and the tombstoned page can never surface
    query = env["trainer"].corpus.query_text(3)
    cli.main(["search", "--query", query, "--nprobe", "8"] + base + grown)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(res["results"]) == 10
    assert 3 not in [r["page_id"] for r in res["results"]]


@pytest.mark.slow
def test_large_append_drift_rebuild_recall(env, tmp_path):
    """Large-corpus rebuild variant: an append big enough to cross the
    default drift threshold rebuilds the quantizer over the merged corpus
    and full probe stays exact."""
    emb, trainer = env["emb"], env["trainer"]
    store = _copy_store(env, tmp_path)
    IVFIndex.build(store, emb.mesh, nlist=16, iters=4, seed=0)
    corpus2 = _grown(trainer.corpus, 600)         # +100% > rebuild_drift
    append_corpus(emb, corpus2, store)
    idx, info = IVFIndex.update(store, emb.mesh)  # default drift 0.25
    assert info["action"] == "rebuild"
    assert int(idx.list_sizes.sum()) == 600
    qv = np.asarray(emb.embed_texts(
        [corpus2.query_text(i) for i in range(0, 600, 29)],
        tower="query"), np.float32)
    r = recall_vs_exact(idx, store, qv, emb.mesh, k=10, nprobe=idx.nlist)
    assert r == 1.0
