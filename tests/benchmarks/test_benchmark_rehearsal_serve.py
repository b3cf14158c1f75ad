"""The serve job rehearsed on the CPU at toy widths (shrunk here, in the
test only): the result line's keys, an answer or a token altered where it
is produced (`correct` has to come out false), and the lower-precision
control, which has to fail."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, tiny  # noqa: E402

SEED, SERVE_LIMITS = tiny.SEED, tiny.SERVE_LIMITS
SERVE_CELL = "bert_mini.serve_exact"
SERVE_TINY = {"store_rows": 5000, "store_seed": 11, "rate_qps": 40.0,
              "checked_answers": 32, "clients": 8}


@pytest.fixture(autouse=True)
def _caches_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "JAX_CACHE", str(tmp_path / "jax"))


def _serve(tmp_path, trace=False):
    from benchmarks.jobs import serve
    root = tiny.make_root(str(tmp_path / "root"), SERVE_CELL,
                          limits=SERVE_LIMITS, **SERVE_TINY)
    cell = harness.Cell(SERVE_CELL, root)
    cell.config["program"]["overrides"]["eval.store_shard_size"] = 1024
    return cell, serve.run(cell, SEED, 1.5, trace, time.perf_counter(),
                           require_chip=False)


def test_serve_rehearsal_is_correct_and_prints_its_line(tmp_path):
    cell, out = _serve(tmp_path)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 60 and out["failed"] == 0
    line = tiny.check_line(cell, out, "serve_p95_ms")
    assert {"rank_gap", "score_gap", "short_answers", "recompiles"} <= \
        set(line["compared"])
    ctx = out["ctx"]
    assert ctx["cache_hits"] == 0          # every query text distinct
    for metric in ("queue_wait_p95_ms.serve", "batch_occupancy.serve",
                   "encode_ms.serve", "topk_ms.serve",
                   "gen_late_p95_ms.serve"):
        assert harness.read_metric(metric, ctx) is not None
    assert harness.read_metric("sharded_topk_roofline", ctx) is None
    assert harness.read_metric("device_idle_share.serve", ctx) is None


def _wrong_answer(search):
    def wrong(query, k):
        hits = search(query, k)
        hits[0] = dict(hits[0], page_id=(hits[-1]["page_id"] + 1) % 5000)
        return hits
    return wrong


def test_serve_altered_answer_reads_not_correct(tmp_path, monkeypatch):
    from benchmarks.jobs import serve
    monkeypatch.setattr(serve, "_wrap_search", _wrong_answer)
    _, out = _serve(tmp_path)
    assert out["correct"] is False
    assert not out["compared"]["rank_gap"]["ok"]


def test_serve_altered_token_reads_not_correct(tmp_path, monkeypatch):
    from dnn_page_vectors_tpu.data.subword import SubwordTokenizer
    real = SubwordTokenizer.encode_batch

    def altered(self, texts):
        ids = real(self, texts).copy()
        ids[:, 0] = 2 + (ids[:, 0] + 7) % 400
        return ids
    monkeypatch.setattr(SubwordTokenizer, "encode_batch", altered)
    _, out = _serve(tmp_path)
    assert out["correct"] is False
    assert not out["compared"]["rank_gap"]["ok"]


def test_serve_control_in_fp8_fails_a_limit(tmp_path):
    from benchmarks import compare, vocab
    from benchmarks.jobs import serve, train
    from benchmarks.reference import towers
    from benchmarks.traffic import generator
    root = tiny.make_root(str(tmp_path / "root"), SERVE_CELL,
                          limits=SERVE_LIMITS, **SERVE_TINY)
    cell = harness.Cell(SERVE_CELL, root)
    with harness.scratch_dir("ctl_") as scratch:
        tok = type("T", (), {"vocab_size": 512})()
        tree = train.tree_without_a_run(cell, SEED, serve._Pages(5000),
                                        (None, tok), scratch)
        voc = vocab.load_or_build(harness.CACHE_DIR, cell.config)
        plan = generator.schedule(cell.traffic, SEED, 1.5)
        texts = serve.query_pool(scratch, SEED, 200, 8)
        answers = [[]] * len(plan["due_s"])      # only their places are used
        numbers = serve.check_answers(cell, SEED, tree, voc, texts, plan,
                                      answers, 1024, quant=towers.to_fp8,
                                      control=True)
    numbers.pop("short_answers")
    judged = compare.judge(numbers, SERVE_LIMITS)
    assert not all(c["ok"] for c in judged.values()), judged


