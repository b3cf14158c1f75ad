"""Integration oracle (SURVEY.md §5): config 1 — 'CDSSM char-trigram CNN,
toy corpus, single-process CPU' (BASELINE.json:7) — trained end-to-end until
Recall@10 beats random by a wide margin, exercising train -> bulk-embed ->
vector store -> retrieval eval as one pipeline.

Shrunk from 10k pages to 600 so the CPU run stays fast; full-size runs are
made on the chip (`chip_smoke.py`, `benchmarks/`).
"""
import numpy as np

from dnn_page_vectors_tpu.config import get_config
from dnn_page_vectors_tpu.evals.recall import evaluate_recall
from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
from dnn_page_vectors_tpu.infer.vector_store import VectorStore
from dnn_page_vectors_tpu.train.loop import Trainer


def test_cdssm_toy_end_to_end(tmp_path):
    cfg = get_config("cdssm_toy", {
        "data.num_pages": 600,
        "data.trigram_buckets": 4096,
        "model.embed_dim": 64,
        "model.conv_channels": 128,
        "model.out_dim": 64,
        "train.batch_size": 64,
        "train.steps": 80,
        "train.warmup_steps": 10,
        "train.learning_rate": 2e-3,
        "train.log_every": 40,
        "eval.eval_queries": 200,
        "eval.embed_batch_size": 128,
    })
    trainer = Trainer(cfg, workdir=str(tmp_path))
    state, metrics = trainer.train()
    assert np.isfinite(metrics["loss"])
    assert metrics["in_batch_acc"] > 0.5, metrics

    store = VectorStore(str(tmp_path / "store"), dim=cfg.model.out_dim,
                        shard_size=256)
    embedder = BulkEmbedder(cfg, trainer.model, state.params,
                            trainer.page_tok, trainer.mesh,
                            query_tok=trainer.query_tok)
    embedder.embed_corpus(trainer.corpus, store, batch_size=128)
    assert store.num_vectors == 600

    recall, nq = evaluate_recall(embedder, trainer.corpus, store,
                                 num_queries=200, k=10)
    # random recall@10 over 600 pages ~ 1.7%; a trained CDSSM must crush it
    assert recall > 0.5, f"recall@10={recall} over {nq} queries"
