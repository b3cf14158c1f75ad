"""The concurrent serving layer (docs/SERVING.md): search_many must return
exactly what per-query search() returns on BOTH the HBM-resident and
streaming paths (batching is an optimization, not a different algorithm) —
including on a degraded store under a seeded FaultPlan — and the
micro-batcher must coalesce concurrent callers, flush partial buckets after
its window, isolate a poisoned request's failure to its own future, and the
query-embedding cache must hit on repeats and invalidate on a model-step
re-stamp."""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from dnn_page_vectors_tpu.config import get_config
from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
from dnn_page_vectors_tpu.infer.serve import SearchService
from dnn_page_vectors_tpu.infer.vector_store import VectorStore
from dnn_page_vectors_tpu.train.loop import Trainer
from dnn_page_vectors_tpu.utils import faults

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
    "eval.store_shard_size": 100,   # 3 shards: exercises the shard merge
}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One trained model + embedded 3-shard store for the whole module
    (training dominates test cost; services stage cheaply per test)."""
    wd = str(tmp_path_factory.mktemp("serve_batching"))
    cfg = get_config("cdssm_toy", _OV)
    trainer = Trainer(cfg, workdir=wd)
    state, _ = trainer.train()
    emb = BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                       trainer.mesh, query_tok=trainer.query_tok)
    store = VectorStore(wd + "/store", dim=cfg.model.out_dim, shard_size=100)
    emb.embed_corpus(trainer.corpus, store)
    return cfg, trainer, emb, store


def _assert_same(a, b):
    assert [r["page_id"] for r in a] == [r["page_id"] for r in b]
    np.testing.assert_allclose([r["score"] for r in a],
                               [r["score"] for r in b], atol=1e-4)


def test_search_many_matches_sequential_on_both_paths(served):
    cfg, trainer, emb, store = served
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    stream = SearchService(cfg, emb, trainer.corpus, store,
                           preload_hbm_gb=0.0)
    assert svc.preloaded and not stream.preloaded
    # 20 queries > the compiled bucket (8): exercises full-bucket tiling
    # plus a ragged final bucket
    qis = [0, 7, 42, 123, 299, 5, 13, 77, 200, 250,
           1, 2, 3, 4, 6, 8, 9, 10, 11, 12]
    queries = [trainer.corpus.query_text(qi) for qi in qis]
    many = svc.search_many(queries, k=10)
    many_stream = stream.search_many(queries, k=10)
    assert len(many) == len(queries)
    hits = 0
    for qi, query, batched, batched_s in zip(qis, queries, many, many_stream):
        seq = svc.search(query, k=10)
        _assert_same(batched, seq)
        _assert_same(batched_s, stream.search(query, k=10))
        _assert_same(batched, batched_s)        # HBM == streaming, batched
        scores = [r["score"] for r in batched]
        assert scores == sorted(scores, reverse=True)
        hits += qi in [r["page_id"] for r in batched]
    assert hits >= 12, f"only {hits}/20 gold pages retrieved"
    assert svc.search_many([], k=10) == []


def test_a_narrower_encode_width_gives_the_same_answers(served):
    """`serve.encode_batch`: the encode's one compiled width. Five misses
    at width 2 are three calls (the last padded by one row), and nothing
    about the answers changes; 0 keeps the scan's bucket."""
    cfg, trainer, emb, store = served
    wide = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    narrow = SearchService(
        get_config("cdssm_toy", dict(_OV, **{"serve.encode_batch": 2})),
        emb, trainer.corpus, store, preload_hbm_gb=4.0)
    assert wide._encode_batch == wide.query_batch == 8
    queries = [trainer.corpus.query_text(qi) for qi in (0, 7, 42, 123, 299)]
    calls = []
    real = emb.encode_query_call
    emb.encode_query_call = lambda ids, params=None: (
        calls.append(ids.shape[0]), real(ids, params))[1]
    try:
        got = narrow.search_many(queries, k=10)
    finally:
        del emb.encode_query_call
    assert calls == [2, 2, 2]
    for a, b in zip(got, wide.search_many(queries, k=10)):
        _assert_same(a, b)
    with pytest.raises(ValueError, match="encode_batch"):
        SearchService(
            get_config("cdssm_toy", dict(_OV, **{"serve.encode_batch": -1})),
            emb, trainer.corpus, store, preload_hbm_gb=0.0)


def test_search_many_degraded_matches_streaming_under_faults(served,
                                                             tmp_path):
    """A quarantined shard (corrupt bytes) + a staging fault (seeded
    FaultPlan) leave the service half-resident; batched search over the
    degraded service must equal a fault-free streaming service on the
    surviving store — and the degraded tail folds once per bucket."""
    import os
    cfg, trainer, emb, _ = served
    # a fresh store so quarantine doesn't disturb the shared fixture
    dstore = VectorStore(str(tmp_path / "store"), dim=cfg.model.out_dim,
                         shard_size=100)
    emb.embed_corpus(trainer.corpus, dstore)
    victim = os.path.join(dstore.directory, "shard_00001.vec.npy")
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    faults.install(faults.FaultPlan.parse("hbm_stage:io_error:2", seed=0))
    svc = SearchService(cfg, emb, trainer.corpus, dstore, preload_hbm_gb=4.0)
    assert svc.degraded
    assert svc.fault_counters["serve_quarantined_shards"] == 1
    assert svc.fault_counters["serve_stage_faults"] == 1
    assert len(svc._stream_entries) == 1
    faults.reset()
    stream = SearchService(cfg, emb, trainer.corpus, dstore,
                           preload_hbm_gb=0.0)
    queries = [trainer.corpus.query_text(qi)
               for qi in (0, 42, 100, 150, 200, 250, 280, 299, 1, 2)]
    many = svc.search_many(queries, k=10)
    for query, batched in zip(queries, many):
        _assert_same(batched, stream.search(query, k=10))
        _assert_same(batched, svc.search(query, k=10))


def test_search_many_dedups_repeats_within_a_batch(served):
    """Duplicate queries in one coalesced batch encode once (intra-batch
    dedup) and every duplicate row gets the identical result."""
    cfg, trainer, emb, store = served
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    q = trainer.corpus.query_text(9)
    other = trainer.corpus.query_text(17)
    res = svc.search_many([q, other, q, " " + q + "  ", other], k=10)
    assert res[0] == res[2] == res[3]
    assert res[1] == res[4]
    _assert_same(res[0], svc.search(q, k=10))


def test_microbatcher_coalesces_concurrent_callers(served):
    cfg, trainer, emb, store = served
    cfg = get_config("cdssm_toy", dict(_OV, **{
        "serve.batch_window_ms": 150, "serve.max_batch": 8}))
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    direct = {qi: svc.search(trainer.corpus.query_text(qi), k=10)
              for qi in range(12)}
    svc.start_batcher()
    assert svc.batching
    # a lone caller: the window expires and the PARTIAL bucket dispatches
    res = svc.search(trainer.corpus.query_text(0), k=10)
    _assert_same(res, direct[0])
    assert svc._batcher.batch_sizes[-1] == 1
    # 12 concurrent callers with a long window coalesce into shared
    # dispatches (max_batch 8 forces at least two)
    before = len(svc._batcher.batch_sizes)
    with ThreadPoolExecutor(12) as ex:
        results = list(ex.map(
            lambda qi: svc.search(trainer.corpus.query_text(qi), k=10),
            range(12)))
    for qi, r in enumerate(results):
        _assert_same(r, direct[qi])
    sizes = list(svc._batcher.batch_sizes)[before:]
    assert sum(sizes) == 12
    assert max(sizes) > 1, "concurrent callers never coalesced"
    assert max(sizes) <= 8                  # serve.max_batch respected
    svc.close()
    assert not svc.batching
    # after close, search() falls back to the direct path
    _assert_same(svc.search(trainer.corpus.query_text(0), k=10), direct[0])


def test_microbatcher_isolates_failing_request(served):
    """A poisoned query (not a string) coalesced with healthy ones must
    fail ONLY its own future; batch-mates still get results."""
    cfg, trainer, emb, store = served
    cfg = get_config("cdssm_toy", dict(_OV, **{
        "serve.batch_window_ms": 200, "serve.max_batch": 8}))
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    good_direct = svc.search(trainer.corpus.query_text(5), k=10)
    svc.start_batcher()
    results, errors = {}, {}

    def _call(tag, query):
        try:
            results[tag] = svc.search(query, k=10)
        except Exception as e:  # noqa: BLE001
            errors[tag] = e

    threads = [
        threading.Thread(target=_call, args=("good1", trainer.corpus.query_text(5))),
        threading.Thread(target=_call, args=("poison", None)),
        threading.Thread(target=_call, args=("good2", trainer.corpus.query_text(7))),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    svc.close()
    assert set(results) == {"good1", "good2"}
    assert set(errors) == {"poison"}
    _assert_same(results["good1"], good_direct)


def test_query_cache_hits_and_model_step_invalidation(served, tmp_path):
    cfg, trainer, emb, _ = served
    store = VectorStore(str(tmp_path / "store"), dim=cfg.model.out_dim,
                        shard_size=100)
    emb.embed_corpus(trainer.corpus, store)
    store.ensure_model_step(1)
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    q = trainer.corpus.query_text(3)
    first = svc.search(q, k=10)
    assert svc.cache_misses == 1 and svc.cache_hits == 0
    second = svc.search(q, k=10)
    assert svc.cache_hits == 1
    assert first == second          # a hit returns IDENTICAL results
    # whitespace-normalized key: surrounding/internal runs of spaces hit
    third = svc.search("  " + q.replace(" ", "  ") + " ", k=10)
    assert svc.cache_hits == 2
    assert third == first
    # a store re-stamp (model reload) changes the key -> miss, not stale hit
    store.ensure_model_step(2)
    svc.search(q, k=10)
    assert svc.cache_misses == 2
    met = svc.metrics()
    assert met["serve_cache_hits"] == 2
    assert met["serve_cache_misses"] == 2
    assert met["serve_cache_hit_rate"] == 0.5
    # the serving stage breakdown is in the metrics
    assert any(key.startswith("serve_stage_") for key in met)


def test_cache_lru_eviction_and_disable(served):
    cfg, trainer, emb, store = served
    cfg = get_config("cdssm_toy", dict(_OV, **{"serve.query_cache_size": 2}))
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    q0, q1, q2 = (trainer.corpus.query_text(i) for i in (0, 1, 2))
    svc.search(q0, k=5)
    svc.search(q1, k=5)
    svc.search(q2, k=5)             # evicts q0 (capacity 2, LRU)
    svc.search(q0, k=5)
    assert svc.cache_hits == 0 and svc.cache_misses == 4
    svc.search(q2, k=5)             # still resident
    assert svc.cache_hits == 1
    off = get_config("cdssm_toy", dict(_OV, **{"serve.query_cache_size": 0}))
    nsvc = SearchService(off, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    nsvc.search(q0, k=5)
    nsvc.search(q0, k=5)
    assert nsvc.cache_hits == 0 and nsvc.cache_misses == 0


def test_warmup_reports_median_and_bypasses_cache(served):
    cfg, trainer, emb, store = served
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=4.0)
    svc.warmup(k=10, timing_iters=3)
    assert svc.warm_latency_ms and svc.warm_latency_ms > 0
    # the timed iterations must NOT have come from the cache: only the
    # compile call may have populated it
    assert svc.cache_hits == 0
