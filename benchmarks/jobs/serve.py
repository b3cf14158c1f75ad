"""The serve job: `SearchService.search` through the micro-batcher, in this
process, over a store the benchmark writes (once per checkout, from the
traffic file's `store_seed`) and the service stages into HBM; the model's
weights and the queries come from `--seed`; open-loop arrivals at the cell's fixed rate, each request
timed from the instant it was due.

`correct` compares a sample of the window's answers, drawn from the seed,
with the plain reference (tokenize -> query tower -> scores against every
row -> exact top-k) once the window has closed and the service is freed.
"""
from __future__ import annotations

import concurrent.futures
import gc
import os
import time

import numpy as np

from .. import compare, corpus, flops, harness, vocab, weights
from ..reference import serve_ref, towers
from ..traffic import generator
from . import train as train_job

WAIT_AFTER_CLOSE_S = 60.0


class _Pages:
    """What the service asks of a corpus: a snippet for a page id."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages

    def page_text(self, i: int) -> str:
        return f"page {int(i)}"

    def query_text(self, i: int) -> str:
        return f"query {int(i)}"


def query_pool(scratch: str, seed: int, n: int, words: int) -> list:
    """n distinct query texts: the queries of a synthetic corpus written
    from the seed."""
    path = os.path.join(scratch, "queries.jsonl")
    corpus.write_synth_jsonl(path, n, seed=seed & 0x7FFFFFFF,
                             query_len=words, page_len=8)
    recs = corpus.read_records(path, range(n))
    os.remove(path)
    return [recs[i]["query"] for i in range(n)]


def open_store(directory: str, seed: int, rows: int, dim: int,
               shard_rows: int, dtype: str):
    """The store, through the program's own `VectorStore`: unit rows made on
    the device from the traffic file's `store_seed`, one shard at a time,
    page id = row number. It is written once per checkout (5 GB at the
    cell's size) and found again by every later run: a marker file, written
    last, says with what it was made."""
    import json
    import shutil
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    made = {"seed": int(seed), "rows": rows, "dim": dim,
            "shard_rows": shard_rows, "dtype": dtype}
    marker = os.path.join(directory, "made_from.json")
    if os.path.exists(marker):
        with open(marker) as f:
            if json.load(f) == made:
                return VectorStore(directory)
    shutil.rmtree(directory, ignore_errors=True)
    store = VectorStore(directory, dim=dim, shard_size=shard_rows,
                        dtype=dtype)
    store.ensure_model_step(0)

    def write(shard, lo, n, arr):
        store.write_shard(shard, np.arange(lo, lo + n, dtype=np.int64),
                          np.asarray(arr))

    pending = None
    for shard, lo in enumerate(range(0, rows, shard_rows)):
        n = min(shard_rows, rows - lo)
        nxt = (shard, lo, n, serve_ref.make_shard(seed, shard, n, dim))
        if pending is not None:     # write shard i while i+1 is generated
            write(*pending)
        pending = nxt
    write(*pending)
    with open(marker, "w") as f:
        json.dump(made, f)
    return store


def _wrap_search(search):
    """A seam for the tests, which alter an answer where it is produced."""
    return search


class Served:
    """The service under test with everything a window needs: built, its
    store staged, every shape warm. `drive` offers one schedule to it."""

    def __init__(self, cell, seed: int, scratch: str, pool_size: int):
        from dnn_page_vectors_tpu.data.subword import SubwordTokenizer
        from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
        from dnn_page_vectors_tpu.infer.serve import SearchService
        from dnn_page_vectors_tpu.train.loop import Trainer
        t, a = cell.traffic, cell.config["assumed"]
        self.cell, self.seed, self.scratch = cell, seed, scratch
        self.k, self.rows = int(t["k"]), int(t["store_rows"])
        self.cfg = cfg = train_job.program_config(cell, seed)
        self.shard_rows = cfg.eval.store_shard_size
        self.voc = vocab.load_or_build(harness.CACHE_DIR, cell.config)
        q_tok, p_tok = (SubwordTokenizer(self.voc, style="wordpiece",
                                         max_tokens=n)
                        for n in (a["query_len"], a["page_len"]))
        pages = _Pages(self.rows)
        trainer = Trainer(cfg, corpus=pages, tokenizers=(q_tok, p_tok),
                          workdir=os.path.join(scratch, "work"))
        self.tree = train_job.shape_tree(trainer)
        params = weights.make_params(self.tree, seed, a["temperature_init"])
        embedder = BulkEmbedder(cfg, trainer.model, params, p_tok,
                                trainer.mesh, query_tok=q_tok)
        harness.note_time("imports, vocabulary, model and weights")
        self.store_seed = int(t["store_seed"])
        store = open_store(
            os.path.join(harness.CACHE_DIR, "store_" + cell.entry["traffic"]),
            self.store_seed, self.rows, a["out_dim"], self.shard_rows,
            t["store_dtype"])
        harness.note_time("store opened (written in a checkout's first run)")
        self.texts = query_pool(scratch, seed, pool_size + 96,
                                int(t["query_words"]))
        self.svc = svc = SearchService(
            cfg, embedder, pages, store,
            preload_hbm_gb=cell.workload["preload_hbm_gb"])
        harness.note_time("service built, store staged")
        if svc.degraded or not svc.preloaded:
            raise SystemExit("the store is not HBM-resident or the service "
                             "came up degraded")
        svc.start_batcher()
        self.search = _wrap_search(svc.search)
        self.pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=int(t["clients"]), thread_name_prefix="client")
        # warm every shape: the compiled encode, scan and merge programs,
        # then full buckets through the batcher, on texts no window sends
        svc.warmup(k=self.k)
        for part in (self.texts[-64:], self.texts[-96:-64]):
            list(self.pool.map(lambda q: self.search(q, self.k), part))
        harness.note_time("warm-up")
        harness.note_compiles("before the window")

    def drive(self, plan: dict, seconds: float, trace: bool) -> dict:
        """Offer `plan` in an open loop; every answer is waited for, a
        minute past the close if need be."""
        svc, n = self.svc, len(plan["due_s"])
        svc.profiler.reset()
        recompiles0, hits0 = svc.recompiles, svc.cache_hits
        done_at = np.full(n, np.nan)
        sent_late = np.zeros(n)
        answers: list = [None] * n
        errors: list = [None] * n

        def one(i: int, due: float) -> None:
            try:
                answers[i] = self.search(self.texts[plan["query"][i]], self.k)
            except BaseException as e:  # noqa: BLE001 — counted as failed
                errors[i] = e
            done_at[i] = time.perf_counter() - due

        futs = []
        with harness.Window(seconds, trace, self.scratch) as win:
            for i in range(n):
                due = win.t0 + plan["due_s"][i]
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent_late[i] = time.perf_counter() - due
                futs.append(self.pool.submit(one, i, due))
            rest = win.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
            window_s = win.close()
            # the service's own rolling window (obs.window_s, shorter than
            # any window here), read at the close: warm-up has aged out
            queue_wait_p95 = svc.registry.histogram(
                "serve.queue_wait_ms").window_percentile(95)
        concurrent.futures.wait(futs, timeout=WAIT_AFTER_CLOSE_S)
        unanswered = np.isnan(done_at)
        done_at[unanswered] = WAIT_AFTER_CLOSE_S + win.seconds
        finished = plan["due_s"] + done_at         # on the window's clock
        backlog = lambda at: int((plan["due_s"] <= at).sum()
                                 - (finished <= at).sum())
        m = svc.metrics()
        return {
            "n": n, "window_s": window_s, "reduced": win.reduced,
            "programs_built": win.programs_built,
            "latency_ms": done_at * 1000.0, "answers": answers,
            "failed": int(unanswered.sum()
                          + sum(e is not None for e in errors)),
            "stage_seconds": svc.profiler.stages(),
            "stage_counts": svc.profiler.counts(),
            "ctx": {
                "queue_wait_p95_ms": queue_wait_p95,
                "mean_batch": m.get("serve_mean_batch"),
                "max_batch": self.cfg.serve.max_batch,
                "query_batch": svc.query_batch,
                "recompiles": svc.recompiles - recompiles0,
                "cache_hits": svc.cache_hits - hits0,
                "backlog_at_middle": backlog(win.seconds / 2),
                "backlog_at_close": backlog(win.seconds),
                "gen_late_p95_ms": harness.percentile(sent_late * 1e3, 95),
            },
        }

    def close(self) -> None:
        self.pool.shutdown(wait=False, cancel_futures=True)
        self.svc.close()
        self.svc = self.search = None


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True) -> dict:
    harness.setup_jax()
    if require_chip:
        harness.require_chips(cell.chips)
    t = cell.traffic
    with harness.scratch_dir("bench_serve_") as scratch:
        plan = generator.schedule(
            t, seed, harness.window_seconds(seconds, trace))
        served = Served(cell, seed, scratch, int(plan["query"].max()) + 1)
        try:
            setup_s = time.perf_counter() - t_start
            stats = served.drive(plan, seconds, trace)
            device = harness.device_info(cell.chips)
            harness.note_memory("after the window")
        finally:
            served.close()
        gc.collect()
        harness.note_time("window, waiting for answers, freeing the service")
        # the plain reference over a sample of the answers, drawn from the seed
        numbers = check_answers(cell, seed, served.tree, served.voc,
                                served.texts, plan, stats["answers"],
                                served.shard_rows)
        harness.note_time("reference")
    ctx, n, failed = stats["ctx"], stats["n"], stats["failed"]
    numbers["recompiles"] = float(ctx["recompiles"])
    numbers["built_in_window"] = float(stats["programs_built"])
    harness.note_compiles("at the end")
    limits = dict(cell.workload["limits"], short_answers=0.0, recompiles=0.0,
                  built_in_window=0.0)
    compared = compare.judge(numbers, limits)
    lat_ms = stats["latency_ms"]
    a = cell.config["assumed"]
    shape = flops.shape_of(cell.config)
    return {
        "correct": bool(all(c["ok"] for c in compared.values())
                        and failed == 0),
        "attempted": n, "failed": failed,
        "end_to_end": {"serve_p95_ms": harness.percentile(lat_ms, 95),
                       "setup_s": setup_s},
        "compared": compared, "device": device, "reduced": stats["reduced"],
        "ctx": dict(ctx, job="serve", window_s=stats["window_s"],
                    requests=n, answered=n - failed, chips=cell.chips,
                    latency_p50_ms=harness.percentile(lat_ms, 50),
                    stage_seconds=stats["stage_seconds"],
                    stage_counts=stats["stage_counts"],
                    reduced=stats["reduced"], device_kind=device["kind"],
                    flops_per_query=flops.serve_flops_per_query(
                        shape, served.rows),
                    scan_bytes_per_launch=flops.scan_bytes_per_dispatch(
                        served.shard_rows, a["out_dim"]),
                    trace_modules=cell.workload.get("trace_modules", {})),
    }


def check_answers(cell, seed: int, tree, voc: dict, texts: list, plan: dict,
                  answers: list, shard_rows: int,
                  quant=towers.identity, control: bool = False) -> dict:
    """The compared numbers of a serve cell. For each sampled request the
    reference's own score of every row is worked out; `rank_gap` is the
    widest gap by which a served row's reference score lies below the
    reference's row of the same rank, `score_gap` the widest gap between a
    served score and the reference's score of that row.

    With `control`, the answers judged are not the program's: they are the
    top-k of the same reference computed through `quant` (the lower
    precision), which has to come out as not correct."""
    t, a = cell.traffic, cell.config["assumed"]
    k, rows, dim = int(t["k"]), int(t["store_rows"]), a["out_dim"]
    have = [i for i, ans in enumerate(answers) if ans is not None]
    if control:
        answers = [[None] * int(t["k"])] * len(answers)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xC4EC])
    take = min(int(t["checked_answers"]), len(have))
    sample = sorted(rng.choice(have, size=take, replace=False).tolist()) \
        if take else []
    short = 0 if control else sum(
        len(answers[i]) != k
        or len({h["page_id"] for h in answers[i]}) != k for i in sample)
    out = {"short_answers": float(short)}
    good = [i for i in sample if len(answers[i]) == k]
    if not good:
        return dict(out, rank_gap=float("inf"), score_gap=float("inf"))
    params = weights.make_params(tree, seed, a["temperature_init"])
    arch = train_job.arch_of(cell)
    ids = vocab.encode(voc, [texts[plan["query"][i]] for i in good],
                       a["query_len"])
    q_ref = serve_ref.query_vectors(params, ids, arch)
    if control:
        q_low = serve_ref.query_vectors(params, ids, arch, quant=quant)
        none = np.full((len(good), k), -1, np.int64)
        low_s, low_i, _ = serve_ref.exact_topk(
            q_low, int(t["store_seed"]), rows, shard_rows, dim, k, none)
        served_ids, served_scores = low_i, low_s
    else:
        served_ids = np.asarray([[h["page_id"] for h in answers[i]]
                                 for i in good], np.int64)
        served_scores = np.asarray([[h["score"] for h in answers[i]]
                                    for i in good], np.float32)
    best_s, _, ref_of_served = serve_ref.exact_topk(
        q_ref, int(t["store_seed"]), rows, shard_rows, dim, k, served_ids)
    out["rank_gap"] = float(np.max(best_s - ref_of_served))
    out["score_gap"] = float(np.max(np.abs(served_scores - ref_of_served)))
    return out
