"""Retrieval eval: Recall@10 query->page (SURVEY.md §3 #22; BASELINE.json:2).

Shares the top-k substrate with the ANN miner (call stack §4.3): the store
streams shard-by-shard through `ops.topk.topk_over_store`, each shard
row-sharded over the mesh 'data' axis, scored on the MXU, per-shard top-k
all-gathered over ICI, running merge on host — so eval memory stays
O(one store shard) no matter the corpus size (the 1B-page requirement,
BASELINE.md:16; VERDICT r1 #2).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
from dnn_page_vectors_tpu.infer.vector_store import VectorStore
from dnn_page_vectors_tpu.data.toy import ToyCorpus
from dnn_page_vectors_tpu.ops.topk import chunked_topk, topk_over_store


def recall_at_k(query_vecs: np.ndarray, page_ids: np.ndarray,
                page_vecs: np.ndarray, gold_ids: np.ndarray,
                k: int = 10, query_batch: int = 1024,
                chunk: int = 8192) -> float:
    """Fraction of queries whose gold page id is in the top-k, for
    in-memory page vectors (single device). The store-scale path is
    `recall_from_store`.

    query_vecs [Nq, D] and page_vecs [N, D] must be L2-normalized (the
    store's invariant); page_ids maps store rows -> page ids.
    """
    hits = 0
    nq = query_vecs.shape[0]
    pages = jnp.asarray(page_vecs, jnp.float32)
    for s in range(0, nq, query_batch):
        q = jnp.asarray(query_vecs[s: s + query_batch], jnp.float32)
        _, idx = chunked_topk(q, pages, k=k, chunk=chunk)
        idx = np.asarray(idx)
        # -1 padding (store smaller than k) must not wrap to the last row
        retrieved = np.where(idx >= 0, page_ids[np.clip(idx, 0, None)], -1)
        gold = gold_ids[s: s + query_batch, None]
        hits += int((retrieved == gold).any(axis=1).sum())
    return hits / max(nq, 1)


def hits_from_store(query_vecs: np.ndarray, store: VectorStore,
                    gold_ids: np.ndarray, mesh, k: int = 10,
                    query_batch: int = 1024, chunk: int = 8192,
                    index=None, nprobe: Optional[int] = None) -> int:
    """Number of queries whose gold id lands in the store-streamed top-k.
    With `index` (index.ivf.IVFIndex), retrieval goes through the
    sublinear ANN path instead of the full-store sweep (docs/ANN.md) —
    the reported recall then measures model AND index quality together."""
    if query_vecs.shape[0] == 0:
        return 0
    if index is not None:
        _, retrieved, _ = index.search(
            np.asarray(query_vecs, np.float32), k=k, nprobe=nprobe)
    else:
        _, retrieved = topk_over_store(
            np.asarray(query_vecs, np.float32), store, mesh, k=k,
            chunk=chunk, query_batch=query_batch)
    return int((retrieved == gold_ids[:, None]).any(axis=1).sum())


def recall_from_store(query_vecs: np.ndarray, store: VectorStore,
                      gold_ids: np.ndarray, mesh, k: int = 10,
                      query_batch: int = 1024, chunk: int = 8192,
                      index=None, nprobe: Optional[int] = None) -> float:
    """Recall@k streaming the store through the sharded cross-shard merge —
    never materializes more than one store shard. `index`/`nprobe` route
    retrieval through the IVF ANN path instead (hits_from_store)."""
    hits = hits_from_store(query_vecs, store, gold_ids, mesh, k=k,
                           query_batch=query_batch, chunk=chunk,
                           index=index, nprobe=nprobe)
    return float(hits) / max(query_vecs.shape[0], 1)


def recall_vs_exact(index, store: VectorStore, query_vecs: np.ndarray,
                    mesh, k: int = 10, nprobe: Optional[int] = None,
                    query_batch: int = 1024, chunk: int = 8192) -> float:
    """ANN recall@k against the EXACT ground truth: the mean fraction of
    each query's exact top-k (topk_over_store) that the IVF index also
    returns at this `nprobe`. This is the index-quality contract
    (docs/ANN.md) — independent of model quality, unlike gold-id recall —
    and is what tests/test_ivf_index.py holds an index to."""
    qv = np.asarray(query_vecs, np.float32)
    if qv.shape[0] == 0:
        return 0.0
    _, exact_ids = topk_over_store(qv, store, mesh, k=k, chunk=chunk,
                                   query_batch=query_batch)
    _, ann_ids, _ = index.search(qv, k=k, nprobe=nprobe)
    total = 0.0
    for row_exact, row_ann in zip(exact_ids, ann_ids):
        truth = set(int(i) for i in row_exact if i >= 0)
        if not truth:
            total += 1.0
            continue
        got = set(int(i) for i in row_ann if i >= 0)
        total += len(truth & got) / len(truth)
    return total / qv.shape[0]


def evaluate_recall(embedder: BulkEmbedder, corpus: ToyCorpus,
                    store: VectorStore, num_queries: Optional[int] = None,
                    k: int = 10, index=None,
                    nprobe: Optional[int] = None) -> Tuple[float, int]:
    """Embed eval queries, search the store, return (recall@k, num_queries).
    Gold label for query i is page i (ToyCorpus invariant).

    Multi-host: each process embeds + searches a contiguous slice of the
    query range on its (local) mesh — every host still streams the full
    store, since any page can be a nearest neighbour of any query — and
    only the integer hit counts cross processes (call stack §4.3)."""
    from dnn_page_vectors_tpu.parallel.multihost import (
        allgather_hosts, process_info)
    nq = min(num_queries or embedder.cfg.eval.eval_queries, corpus.num_pages)
    pi, pc = process_info()
    lo, hi = pi * nq // pc, (pi + 1) * nq // pc
    query_vecs = embedder.embed_texts(
        [corpus.query_text(i) for i in range(lo, hi)], tower="query")
    gold = np.arange(lo, hi, dtype=np.int64)
    hits = hits_from_store(query_vecs, store, gold, embedder.mesh, k=k,
                           index=index, nprobe=nprobe)
    if pc > 1:
        counts = allgather_hosts(np.array([hits, hi - lo], np.int64)).sum(0)
        return float(counts[0]) / max(int(counts[1]), 1), nq
    return float(hits) / max(nq, 1), nq
