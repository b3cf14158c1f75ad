"""Parallel host ingestion + overlapped shard writeback (ISSUE 1 tentpole).

Four contracts:

1. The multi-worker producer (data.tokenize_workers) yields batches in
   deterministic order and the embedded store is BYTE-identical to the
   serial path — parallelism must be invisible in the output.
2. A tokenizer-worker exception mid-sweep re-raises consumer-side and
   leaves no shard falsely recorded as complete (resume correctness).
3. A background-writer failure propagates out of embed_corpus instead of
   being swallowed on the writer thread.
4. The pipeline profiler's stage keys land in the metrics log, for both
   the embed sweep and the train loop.
"""
import json
import os

import numpy as np
import pytest

from dnn_page_vectors_tpu.config import get_config
from dnn_page_vectors_tpu.data.loader import (
    TrainBatcher, iter_corpus_batches, ordered_parallel_map)
from dnn_page_vectors_tpu.data.toy import ToyCorpus
from dnn_page_vectors_tpu.data.trigram import TrigramTokenizer
from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
from dnn_page_vectors_tpu.infer.vector_store import VectorStore
from dnn_page_vectors_tpu.train.loop import Trainer
from dnn_page_vectors_tpu.utils.logging import MetricsLogger
from dnn_page_vectors_tpu.utils.profiling import PipelineProfiler

CFG_OVERRIDES = {
    "data.num_pages": 640,
    "data.trigram_buckets": 1024,
    "model.embed_dim": 16,
    "model.conv_channels": 16,
    "model.out_dim": 16,
    "train.batch_size": 32,
    "train.log_every": 1000,
    "eval.embed_batch_size": 64,
    "eval.store_shard_size": 256,
    "mesh.data": 1,
}


def _embedder(trainer, state, cfg):
    return BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                        trainer.mesh, query_tok=trainer.query_tok)


def _embed_store(emb, cfg, corpus, directory, workers, **kw):
    store = VectorStore(directory, dim=cfg.model.out_dim,
                        shard_size=cfg.eval.store_shard_size)
    emb.embed_corpus(corpus, store, workers=workers, **kw)
    return store


def _shard_bytes(store):
    out = {}
    for s in store.shards():
        for key in ("vec", "ids", "scl"):
            if key in s:
                with open(os.path.join(store.directory, s[key]), "rb") as f:
                    out[s[key]] = f.read()
    return out


def test_ordered_parallel_map_order_and_bound():
    seen = []

    def f(x):
        seen.append(x)
        return x * x

    got = list(ordered_parallel_map(f, range(50), workers=4))
    assert got == [x * x for x in range(50)]     # strict output order
    assert sorted(seen) == list(range(50))       # every item ran exactly once


def test_ordered_parallel_map_reraises_at_position():
    def f(x):
        if x == 7:
            raise ValueError("boom at 7")
        return x

    it = ordered_parallel_map(f, range(20), workers=3)
    got = [next(it) for _ in range(7)]
    assert got == list(range(7))                 # everything before the crash
    with pytest.raises(ValueError, match="boom at 7"):
        next(it)


def test_parallel_corpus_batches_match_serial():
    corpus = ToyCorpus(num_pages=200, seed=5)
    tok = TrigramTokenizer(buckets=512, max_words=16, k=4)
    serial = list(iter_corpus_batches(corpus, tok, 32, workers=1))
    para = list(iter_corpus_batches(corpus, tok, 32, workers=4))
    assert len(serial) == len(para) == 7          # 200/32 -> 6 full + padded
    for a, b in zip(serial, para):
        np.testing.assert_array_equal(a["page"], b["page"])
        np.testing.assert_array_equal(a["page_id"], b["page_id"])


def test_parallel_train_batcher_matches_serial():
    corpus = ToyCorpus(num_pages=96, seed=2)
    tok = TrigramTokenizer(buckets=512, max_words=8, k=4)
    serial = iter(TrainBatcher(corpus, tok, tok, batch_size=32, seed=7,
                               workers=1))
    para = iter(TrainBatcher(corpus, tok, tok, batch_size=32, seed=7,
                             workers=3))
    for _ in range(7):   # 3 steps/epoch -> crosses epoch boundaries
        want, got = next(serial), next(para)
        for key in want:
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)


def test_parallel_embed_store_byte_identical(tmp_path):
    cfg = get_config("cdssm_toy", CFG_OVERRIDES)
    trainer = Trainer(cfg, workdir=str(tmp_path))
    state = trainer.init_state()   # random params: equality is what matters
    emb = _embedder(trainer, state, cfg)
    s1 = _embed_store(emb, cfg, trainer.corpus, str(tmp_path / "serial"),
                      workers=1)
    s2 = _embed_store(emb, cfg, trainer.corpus, str(tmp_path / "parallel"),
                      workers=4)
    assert s1.num_vectors == s2.num_vectors == 640
    b1, b2 = _shard_bytes(s1), _shard_bytes(s2)
    assert b1.keys() == b2.keys()
    for name in b1:
        assert b1[name] == b2[name], f"{name} differs serial vs parallel"


class _FailingCorpus:
    """Delegates to a ToyCorpus but raises on reads past `fail_at` — a
    tokenizer worker dying mid-sweep (disk error, bad record...)."""

    def __init__(self, inner, fail_at):
        self._inner = inner
        self.fail_at = fail_at
        self.num_pages = inner.num_pages

    def fingerprint(self):
        return self._inner.fingerprint()

    def page_texts(self, ids):
        if max(int(i) for i in ids) >= self.fail_at:
            raise RuntimeError("injected read failure")
        return [self._inner.page_text(int(i)) for i in ids]

    def page_text(self, i):
        return self.page_texts([i])[0]

    def query_text(self, i):
        return self._inner.query_text(i)


@pytest.mark.parametrize("dtype", ["float16", "int8"])
def test_embed_from_jsonl_text_stores_every_page(tmp_path, dtype):
    """The from-text sweep over a jsonl corpus on disk (corpus read ->
    tokenizer workers -> device -> store writer) leaves every page in the
    store, at fp16 and at the device-quantized int8 width."""
    from dnn_page_vectors_tpu.data.synth import write_synth_jsonl
    n = CFG_OVERRIDES["data.num_pages"]
    path = write_synth_jsonl(str(tmp_path / "synth.jsonl"), n, seed=7,
                             page_len=24, query_len=8)
    cfg = get_config("cdssm_toy", {**CFG_OVERRIDES,
                                   "data.corpus": f"jsonl:{path}"})
    trainer = Trainer(cfg, workdir=str(tmp_path))
    emb = _embedder(trainer, trainer.init_state(), cfg)
    store = VectorStore(str(tmp_path / "store"), dim=cfg.model.out_dim,
                        shard_size=cfg.eval.store_shard_size, dtype=dtype)
    emb.embed_corpus(trainer.corpus, store, workers=3)
    assert store.num_vectors == n
    assert sorted(store.completed_shards()) == [0, 1, 2]


def test_worker_exception_reraises_and_no_false_complete_shard(tmp_path):
    """Contract 2: the failure lands in shard 1 (pages 256..), so shard 0
    may complete but the failing shard — and anything after — must not be
    recorded. A resumed job re-embeds exactly the missing shards."""
    cfg = get_config("cdssm_toy", CFG_OVERRIDES)
    trainer = Trainer(cfg, workdir=str(tmp_path))
    state = trainer.init_state()
    corpus = _FailingCorpus(trainer.corpus, fail_at=400)
    store = VectorStore(str(tmp_path / "store"), dim=cfg.model.out_dim,
                        shard_size=cfg.eval.store_shard_size)
    emb = BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                       trainer.mesh, query_tok=trainer.query_tok)
    with pytest.raises(RuntimeError):
        emb.embed_corpus(corpus, store, workers=3)
    done = store.completed_shards()
    assert 1 not in done and 2 not in done, done   # failing shard unrecorded
    assert done <= {0}, done
    # resume completes the remaining shards once the corpus heals
    corpus.fail_at = 10**9
    emb.embed_corpus(corpus, store, workers=3)
    assert store.num_vectors == 640


def test_writer_failure_propagates(tmp_path):
    """Contract 3: write_shard raising on the background writer thread must
    fail embed_corpus (join + re-raise), and nothing may be recorded."""
    cfg = get_config("cdssm_toy", CFG_OVERRIDES)
    trainer = Trainer(cfg, workdir=str(tmp_path))
    state = trainer.init_state()
    store = VectorStore(str(tmp_path / "store"), dim=cfg.model.out_dim,
                        shard_size=cfg.eval.store_shard_size)

    def _broken_write(*a, **kw):
        raise OSError("disk full (injected)")

    store.write_shard = _broken_write
    emb = BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                       trainer.mesh, query_tok=trainer.query_tok)
    # the writer-thread exception surfaces AS ITSELF from embed_corpus —
    # moving writeback off-thread must not change the exception surface
    with pytest.raises(OSError, match="disk full"):
        emb.embed_corpus(trainer.corpus, store, workers=2)
    fresh = VectorStore(str(tmp_path / "store"), dim=cfg.model.out_dim)
    assert fresh.completed_shards() == set()


def test_embed_stage_keys_in_metrics_log(tmp_path):
    """Contract 4a: embed_corpus writes the per-stage breakdown to the
    metrics log (the observability half of the tentpole)."""
    cfg = get_config("cdssm_toy", CFG_OVERRIDES)
    trainer = Trainer(cfg, workdir=str(tmp_path))
    state = trainer.init_state()
    log = MetricsLogger(str(tmp_path), echo=False)
    prof = PipelineProfiler()
    _embed_store(_embedder(trainer, state, cfg), cfg, trainer.corpus,
                 str(tmp_path / "store"), workers=2, log=log, profiler=prof)
    log.close()
    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    final = [r for r in recs if "bulk_embed_pages" in r]
    assert final, recs
    for key in ("stage_produce_wait_s", "stage_read_s", "stage_tokenize_s",
                "stage_h2d_s", "stage_compute_s", "stage_d2h_s",
                "stage_write_s"):
        assert key in final[-1], (key, sorted(final[-1]))
    # per-shard rate lines still come through (now from the writer thread)
    assert [r for r in recs if "bulk_embed_shard" in r]
    # the caller-supplied profiler saw the same stages
    assert prof.stages().get("write", 0) > 0


def test_train_stage_keys_in_metrics_log(tmp_path):
    """Contract 4b: the train loop logs stage_*_s next to pages/sec."""
    cfg = get_config("cdssm_toy", {**CFG_OVERRIDES, "train.log_every": 2})
    trainer = Trainer(cfg, workdir=str(tmp_path))
    log = MetricsLogger(str(tmp_path), name="train_metrics", echo=False)
    trainer.train(steps=2, log=log)
    log.close()
    with open(os.path.join(str(tmp_path), "train_metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    assert recs
    for key in ("stage_produce_wait_s", "stage_compute_s", "stage_h2d_s"):
        assert key in recs[-1], (key, sorted(recs[-1]))
