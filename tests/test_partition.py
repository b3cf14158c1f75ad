"""Partitioned + replicated serving (docs/SCALING.md "Partitioned
serving"): the scatter-gather must be an OPTIMIZATION, not a different
algorithm — partitioned results byte-identical to the single-partition
exact path at every tested (P, R), including tombstoned rows, PQ/ADC +
exact-fallback partitions mixed, and under a concurrent refresh hammer
(the PR-5 no-mixed-result-sets pin extended to P views) — plus the
availability half: health-based routing sheds on restage / degraded /
queue budget, and a partition whose replicas are ALL degraded still
answers (never an empty slice), with the counters and events asserted.
The host-simulation accounting of `PartitionSet.simulate`
(critical-path seconds, per-partition scan bytes) is pinned here
too."""
import threading
import time

import numpy as np
import pytest

from dnn_page_vectors_tpu.config import get_config
from dnn_page_vectors_tpu.data.toy import ToyCorpus
from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
from dnn_page_vectors_tpu.infer.serve import SearchService
from dnn_page_vectors_tpu.infer.vector_store import VectorStore
from dnn_page_vectors_tpu.train.loop import Trainer
from dnn_page_vectors_tpu.utils import faults

pytestmark = pytest.mark.part

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
    "eval.embed_batch_size": 50,
    "eval.store_shard_size": 50,    # 6 shards: room for P in {2, 3, 4}
}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One trained model + embedded 6-shard store for the whole module."""
    wd = str(tmp_path_factory.mktemp("partition_serve"))
    cfg = get_config("cdssm_toy", _OV)
    trainer = Trainer(cfg, workdir=wd)
    state, _ = trainer.train()
    emb = BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                       trainer.mesh, query_tok=trainer.query_tok)
    store = VectorStore(wd + "/store", dim=cfg.model.out_dim, shard_size=50)
    emb.embed_corpus(trainer.corpus, store)
    return cfg, trainer, emb, store


def _cfg(**serve_over):
    import dataclasses
    cfg = get_config("cdssm_toy", _OV)
    if serve_over:
        cfg = cfg.replace(
            serve=dataclasses.replace(cfg.serve, **serve_over))
    return cfg


def _fresh_store(served, tmp_path):
    cfg, trainer, emb, _ = served
    store = VectorStore(str(tmp_path / "store"), dim=cfg.model.out_dim,
                        shard_size=50)
    store.ensure_model_step(0)          # appends require a stamped store
    emb.embed_corpus(trainer.corpus, store)
    return store


# ---------------------------------------------------------------------------
# the split
# ---------------------------------------------------------------------------

def test_partition_split_contiguous_balanced():
    from dnn_page_vectors_tpu.parallel.multihost import (
        partition_shard_ranges)
    counts = [64] * 6
    assert partition_shard_ranges(counts, 1) == [(0, 6)]
    assert partition_shard_ranges(counts, 2) == [(0, 3), (3, 6)]
    assert partition_shard_ranges(counts, 3) == [(0, 2), (2, 4), (4, 6)]
    # more partitions than shards: clamp, one shard each
    assert partition_shard_ranges(counts, 99) == [
        (i, i + 1) for i in range(6)]
    assert partition_shard_ranges([], 4) == [(0, 0)]
    # uneven counts: cuts land closest to the row-balanced targets, and
    # the ranges always tile [0, n) contiguously with no empty slice
    for counts in ([100, 1, 1, 1, 1, 100], [5, 90, 5, 90, 5, 90],
                   [1, 2, 3, 4, 5, 6, 7, 8]):
        for parts in (2, 3, 4):
            r = partition_shard_ranges(counts, parts)
            assert r[0][0] == 0 and r[-1][1] == len(counts)
            assert all(lo < hi for lo, hi in r)
            assert all(r[i][1] == r[i + 1][0] for i in range(len(r) - 1))
    r = partition_shard_ranges([100, 1, 1, 1, 1, 100], 2)
    assert r == [(0, 3), (3, 6)]        # 102 | 102, not 100 | 104


def test_partition_specs_cover_store_and_cut_hot_budget():
    from dnn_page_vectors_tpu.infer.partition import make_partition_specs
    entries = [{"index": i, "count": c}
               for i, c in enumerate([50, 50, 100, 50, 50])]
    specs = make_partition_specs(entries, 3, hot_gb=3.0)
    assert [s.pid for s in specs] == [0, 1, 2]
    assert sum(s.rows for s in specs) == 300
    flat = [i for s in specs for i in s.shard_indices]
    assert flat == [0, 1, 2, 3, 4]      # contiguous, disjoint, in order
    # hot budget cut proportional to rows
    assert abs(sum(s.hot_gb for s in specs) - 3.0) < 1e-9
    for s in specs:
        assert abs(s.hot_gb - 3.0 * s.rows / 300) < 1e-9


# ---------------------------------------------------------------------------
# byte-identity with the single-partition exact path
# ---------------------------------------------------------------------------

def test_partitioned_matches_single_partition_exact(served):
    cfg, trainer, emb, store = served
    svc1 = SearchService(_cfg(), emb, trainer.corpus, store,
                         preload_hbm_gb=4.0)
    qis = [0, 7, 42, 123, 299, 5, 13, 77, 200, 250]
    queries = [trainer.corpus.query_text(qi) for qi in qis]
    base = svc1.search_many(queries, k=10)
    for P, R in ((2, 1), (4, 1), (2, 2), (1, 2), (4, 2)):
        svc = SearchService(_cfg(partitions=P, replicas=R), emb,
                            trainer.corpus, store, preload_hbm_gb=4.0)
        assert svc.partition_set is not None
        assert svc.search_many(queries, k=10) == base, f"P={P} R={R}"
        assert svc.search_many([], k=10) == []
        met = svc.metrics()
        assert met["serve_partitions"] == P
        assert met["serve_replicas"] == R
        parts = met["partitions"]
        assert len(parts) == P
        assert sum(p["rows"] for p in parts) == 300
        shards = [s for p in parts for s in p["shards"]]
        assert shards == list(range(6))  # contiguous cover, in order
        for p in parts:
            assert len(p["replicas"]) == R
        svc.close()
    # a partitioned STREAMING service (no HBM staging) agrees too
    stream = SearchService(_cfg(partitions=3), emb, trainer.corpus, store,
                           preload_hbm_gb=0.0)
    assert stream.search_many(queries, k=10) == base
    stream.close()
    svc1.close()


def test_partitioned_tombstones_identical(served, tmp_path):
    cfg, trainer, emb, _ = served
    from dnn_page_vectors_tpu.updates import append_corpus
    store = _fresh_store(served, tmp_path)
    dead = [3, 42, 123, 250]
    append_corpus(emb, trainer.corpus, store, tombstone=dead)
    store = VectorStore(store.directory)
    svc1 = SearchService(_cfg(), emb, trainer.corpus, store,
                         preload_hbm_gb=4.0)
    svcp = SearchService(_cfg(partitions=3, replicas=2), emb,
                         trainer.corpus, store, preload_hbm_gb=4.0)
    queries = [trainer.corpus.query_text(qi)
               for qi in (3, 42, 123, 250, 0, 7, 200)]
    base = svc1.search_many(queries, k=10)
    res = svcp.search_many(queries, k=10)
    assert res == base
    for r in res:
        assert not set(x["page_id"] for x in r) & set(dead)
    svcp.close()
    svc1.close()


def test_partitioned_pq_adc_and_exact_fallback_mixed(served, tmp_path):
    """Mixed retrieval modes across partitions: a full-probe PQ/ADC
    partition and an index-degraded exact-fallback partition must still
    fold to results byte-identical to the single-partition exact path
    (full probe + full rerank makes the ADC path exact — the PR-4/PR-6
    contract — so partitioning must not perturb it)."""
    from dnn_page_vectors_tpu.index.ivf import IVFIndex
    cfg, trainer, emb, _ = served
    store = _fresh_store(served, tmp_path)
    IVFIndex.build(store, emb.mesh, seed=0, pq_m=6)
    exact = SearchService(_cfg(), emb, trainer.corpus, store,
                          preload_hbm_gb=4.0)
    queries = [trainer.corpus.query_text(qi)
               for qi in (0, 7, 42, 123, 299, 200)]
    base = exact.search_many(queries, k=10)
    svc = SearchService(
        _cfg(partitions=2, index="ivf", nprobe=10_000, pq_rerank=300),
        emb, trainer.corpus, store, preload_hbm_gb=4.0)
    pset = svc.partition_set
    for reps in pset._parts:            # both partitions ANN-capable
        assert reps[0].view.index is not None
        # each partition's index view is restricted to ITS shard slice
        assert set(reps[0].view.index._postings) == \
            set(reps[0].spec.shard_indices)
    assert svc.search_many(queries, k=10) == base
    assert svc.ann_fallbacks == 0
    # degrade partition 1's index: THAT partition serves the exact
    # fallback while partition 0 stays on ADC — mixed, still identical
    for rep in pset._parts[1]:
        rep.view.index = None
    assert svc.search_many(queries, k=10) == base
    assert svc.ann_fallbacks > 0
    svc.close()
    exact.close()


def test_over_the_wire_tombstones_and_pq_mixed_identical(served, tmp_path):
    """The PR-12 byte-identity pin extended over the socket
    (docs/SERVING.md "Network front end"): with tombstoned rows AND a
    full-probe PQ/ADC index, results through real partition-worker
    sockets — including one partition degraded to the exact fallback
    and one answering from the front end's LOCAL view after its worker
    dies — stay byte-identical to the single-partition exact path."""
    import threading

    from dnn_page_vectors_tpu.index.ivf import IVFIndex
    from dnn_page_vectors_tpu.infer.partition_host import (
        PartitionWorker, WorkerGateway)
    from dnn_page_vectors_tpu.updates import append_corpus
    cfg, trainer, emb, _ = served
    store = _fresh_store(served, tmp_path)
    dead = [3, 42, 123]
    append_corpus(emb, trainer.corpus, store, tombstone=dead)
    store = VectorStore(store.directory)
    IVFIndex.build(store, emb.mesh, seed=0, pq_m=6)
    exact = SearchService(_cfg(), emb, trainer.corpus, store,
                          preload_hbm_gb=4.0)
    queries = [trainer.corpus.query_text(qi)
               for qi in (3, 42, 123, 0, 7, 200)]
    base = exact.search_many(queries, k=10)
    svc = SearchService(
        _cfg(partitions=2, index="ivf", nprobe=10_000, pq_rerank=300),
        emb, trainer.corpus, store, preload_hbm_gb=4.0)
    gw = WorkerGateway(svc, heartbeat_s=0.25)
    svc.attach_gateway(gw)
    workers = []
    try:
        for p in range(2):
            w = PartitionWorker(svc.cfg, store.directory,
                                ("127.0.0.1", gw.port), partition=p,
                                partitions=2, replica=0, mesh=emb.mesh)
            threading.Thread(target=w.run, daemon=True).start()
            workers.append(w)
        assert gw.wait_for_workers(2, timeout_s=60.0)
        res = svc.search_many(queries, k=10)
        assert res == base
        assert gw.stats()["rpc_fallbacks"] == 0
        for r in res:
            assert not set(x["page_id"] for x in r) & set(dead)
        # partition 1's WORKER degrades to the exact fallback (its index
        # dropped) while partition 0 stays on ADC over the wire — mixed
        # retrieval modes across the RPC hop, still identical
        workers[1].view.index = None
        assert svc.search_many(queries, k=10) == base
        # kill partition 0's worker: its slice folds from the front
        # end's local view — identical again, kill -9 semantics
        workers[0].stop()
        deadline = time.perf_counter() + 2.0
        while gw.worker_alive(0, 0) and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert svc.search_many(queries, k=10) == base
        assert gw.stats()["rpc_fallbacks"] >= 0
    finally:
        for w in workers:
            w.stop()
        gw.close()
        svc.close()
        exact.close()


# ---------------------------------------------------------------------------
# health-based replica routing
# ---------------------------------------------------------------------------

def _degrade(view) -> None:
    """Push a view's staged shards onto the streaming disk path — the
    state a staging failure leaves behind (docs/ROBUSTNESS.md)."""
    view.stream_entries = list(view.entries)
    view.shards = None


def test_replica_shed_and_degraded_local_fallback(served):
    cfg, trainer, emb, store = served
    svc = SearchService(_cfg(partitions=2, replicas=2), emb,
                        trainer.corpus, store, preload_hbm_gb=4.0)
    pset = svc.partition_set
    q = [trainer.corpus.query_text(7)]
    base = svc.search_many(q, k=10)
    # 1) primary mid-restage -> shed to the replica
    pset._parts[0][0].set_restaging(True)
    assert svc.search_many(q, k=10) == base
    assert svc.replica_shed == 1
    evs = [e for e in svc.registry.events()
           if e["event"] == "replica_shed"]
    assert evs and evs[-1]["attrs"]["reason"] == "restaging"
    assert evs[-1]["attrs"]["partition"] == 0
    pset._parts[0][0].set_restaging(False)
    # 2) primary degraded, replica healthy -> shed, reason degraded
    _degrade(pset._parts[0][0].view)
    assert svc.search_many(q, k=10) == base
    assert svc.replica_shed == 2
    assert svc.partition_degraded_serves == 0
    evs = [e for e in svc.registry.events()
           if e["event"] == "replica_shed"]
    assert evs[-1]["attrs"]["reason"] == "degraded"
    # 3) replica ALSO degraded -> serve degraded locally: identical,
    # NON-EMPTY results (the availability pin), counter + event move
    _degrade(pset._parts[0][1].view)
    res = svc.search_many(q, k=10)
    assert res == base and res[0]
    assert svc.partition_degraded_serves >= 1
    assert any(e["event"] == "partition_degraded"
               for e in svc.registry.events())
    met = svc.metrics()
    assert met["replica_shed"] >= 2
    assert met["partition_degraded"] >= 1
    p0 = met["partitions"][0]
    assert p0["sheds"] >= 2 and p0["degraded_serves"] >= 1
    assert p0["replicas"][0]["degraded"] and p0["replicas"][1]["degraded"]
    svc.close()


def test_shed_on_queue_budget(served):
    cfg, trainer, emb, store = served
    svc = SearchService(_cfg(partitions=1, replicas=2,
                             replica_shed_queue=0),
                        emb, trainer.corpus, store, preload_hbm_gb=4.0)
    pset = svc.partition_set
    base = svc.search_many([trainer.corpus.query_text(3)], k=10)
    rep0 = pset._parts[0][0]
    with rep0._lock:                    # simulate a stuck backlog
        rep0._outstanding = 5
    assert svc.search_many([trainer.corpus.query_text(3)], k=10) == base
    assert svc.replica_shed == 1
    evs = [e for e in svc.registry.events()
           if e["event"] == "replica_shed"]
    assert evs[-1]["attrs"]["reason"] == "queue"
    with rep0._lock:
        rep0._outstanding = 0
    # healthy again: traffic returns to the primary, no new sheds
    assert svc.search_many([trainer.corpus.query_text(3)], k=10) == base
    assert svc.replica_shed == 1
    svc.close()


# ---------------------------------------------------------------------------
# the PR-5 pin, extended: zero mixed result sets under partitioned refresh
# ---------------------------------------------------------------------------

def test_no_mixed_result_sets_under_partitioned_refresh(served, tmp_path):
    """Concurrent queries through the micro-batcher while append +
    refresh() restage a P=2 service partition by partition: zero
    exceptions, every observed result set is exactly the old table's or
    the new table's — never a cross-partition mix — the tombstoned page
    disappears, and the refresh info carries the per-partition restage
    record."""
    cfg, trainer, emb, _ = served
    from dnn_page_vectors_tpu.updates import append_corpus
    store = _fresh_store(served, tmp_path)
    svc = SearchService(_cfg(partitions=2, batch_window_ms=2.0,
                             max_batch=8),
                        emb, trainer.corpus, store, preload_hbm_gb=4.0)
    svc.start_batcher()
    cand = list(range(0, 300, 13))
    queries = {qi: trainer.corpus.query_text(qi) for qi in cand}
    first = {qi: tuple(r["page_id"] for r in svc.search(queries[qi], k=10))
             for qi in cand}
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
    try:
        grown = ToyCorpus(num_pages=400, seed=trainer.corpus.seed,
                          num_topics=trainer.corpus.num_topics,
                          page_len=trainer.corpus.page_len,
                          query_len=trainer.corpus.query_len,
                          languages=trainer.corpus.languages)
        append_corpus(emb, grown, store, tombstone=[victim])
        info = svc.refresh()
        time.sleep(0.3)                 # let queries land on the new table
    finally:
        stop.set()                      # a failed append must not leave
        for t in threads:               # the hammers spinning forever
            t.join()
    after = {qi: tuple(r["page_id"] for r in svc.search(queries[qi], k=10))
             for qi in qids}
    assert not errors, f"partitioned hot-swap raised: {errors[:3]}"
    for qi in qids:
        extra = observed[qi] - {before[qi], after[qi]}
        assert not extra, (f"query {qi} saw a mixed result set during the "
                           f"partitioned swap: {extra}")
    assert victim not in after[victim]
    # per-partition restage record: both partitions restaged, with the
    # new generation's shards split contiguously between them
    parts = info["partitions"]
    assert len(parts) == 2
    assert all(p["restage_ms"] for p in parts)
    # spec rows count RAW shard rows (tombstones mask at read time)
    assert sum(p["rows"] for p in parts) == 400
    met = svc.metrics()
    assert met["refreshes"] == 1
    assert met["store_generation"] == 1
    svc.close()


# ---------------------------------------------------------------------------
# host-simulation accounting (PartitionSet.simulate)
# ---------------------------------------------------------------------------

def test_host_simulation_critical_path_and_scan_bytes(served):
    cfg, trainer, emb, store = served
    qv = np.asarray(emb.embed_texts([trainer.corpus.query_text(5)],
                                    tower="query"), np.float32)
    svc1 = SearchService(_cfg(partitions=1, replicas=2), emb,
                         trainer.corpus, store, preload_hbm_gb=4.0)
    svc4 = SearchService(_cfg(partitions=4), emb, trainer.corpus, store,
                         preload_hbm_gb=4.0)
    sim1 = svc1.partition_set.simulate(qv, 1, 10)
    sim4 = svc4.partition_set.simulate(qv, 1, 10)
    assert np.array_equal(sim1["ids"], sim4["ids"])
    assert np.array_equal(sim1["scores"], sim4["scores"])
    assert len(sim4["partition_seconds"]) == 4
    assert sim4["critical_path_seconds"] >= max(sim4["partition_seconds"])
    # the acceptance geometry: per-query critical-path scan bytes at P=4
    # are <= 1/3 of the single-partition scan (6 equal shards -> 1/3)
    assert sum(sim1["scan_bytes"]) == 300 * store.row_bytes
    assert max(sim4["scan_bytes"]) * 3 <= max(sim1["scan_bytes"])
    # topk_vectors drives the same paths by raw vectors
    s1, i1 = svc1.topk_vectors(qv, k=10)
    s4, i4 = svc4.topk_vectors(qv, k=10)
    assert np.array_equal(i1, i4) and np.array_equal(s1, s4)
    svc4.close()
    svc1.close()


def test_trial_record_carries_partition_block(served):
    from dnn_page_vectors_tpu.loadgen import make_workload, run_trial
    cfg, trainer, emb, store = served
    svc = SearchService(_cfg(partitions=2), emb, trainer.corpus, store,
                        preload_hbm_gb=4.0)
    svc.start_batcher()
    wl = make_workload("poisson", seed=3, distinct=4)
    queries = [trainer.corpus.query_text(i) for i in range(4)]
    tr = run_trial(svc, wl, 40.0, queries, duration_s=0.4, warmup_s=0.0,
                   workers=4)
    assert tr["errors"] == 0
    assert len(tr["partitions"]) == 2
    for p in tr["partitions"]:
        for key in ("partition", "shards", "rows", "qps", "p99_ms",
                    "sheds", "degraded_serves", "replicas"):
            assert key in p, key
    assert tr["replica_shed"] == 0 and tr["partition_degraded"] == 0
    svc.close()
