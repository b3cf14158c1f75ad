#!/usr/bin/env python3
"""The readings the limits of `correct` are set from (not run by the
benchmark; kept so that a later `benchmark` PR can repeat them):

    python3 benchmarks/study.py <cell> --seeds 12 --control-seeds 3 --seconds 4

For each seed one short run of the cell's job at the cell's own size gives
the program's numbers against the plain reference (the lower reading is the
largest over the seeds). For the first `--control-seeds` seeds the reference
is also put in the program's place, computed in float8 (the control), and,
for a train cell, with half of the batch left out (the planted fault); the
upper reading is the smallest of those. One JSON line per reading, and a
summary, on standard output and under chiprun_out/.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _train_controls(cell, seed):
    import numpy as np
    from benchmarks import compare, harness
    from benchmarks.jobs import train
    from benchmarks.reference import towers
    with harness.scratch_dir("study_") as scratch:
        feed = train.Feed(cell, seed, scratch)
        tree = train.tree_without_a_run(cell, seed, feed.corpus,
                                        feed.tokenizers, scratch)
        b = train.program_config(cell, seed).train.batch_size
        rng = np.random.default_rng(seed & 0xFFFFFFFF)
        rows = [rng.choice(feed.corpus.num_pages, size=b, replace=False)
                for _ in range(3)]
        ref = train.reference_readings(cell, feed, tree, seed, rows)
        out = {}
        for kind, kw in (("control_fp8", {"quant": towers.to_fp8}),
                         ("fault_half_batch", {"half_batch": True})):
            other = train.reference_readings(cell, feed, tree, seed, rows,
                                             **kw)
            out[kind] = compare.train_numbers(other, ref)
    return out


def _serve_controls(cell, seed, seconds):
    from benchmarks import harness, vocab
    from benchmarks.jobs import serve, train
    from benchmarks.reference import towers
    from benchmarks.traffic import generator
    t = cell.traffic
    with harness.scratch_dir("study_") as scratch:
        cfg = train.program_config(cell, seed)
        tok = type("T", (), {"vocab_size":
                             cell.config["published"]["vocab_size"]})()
        tree = train.tree_without_a_run(
            cell, seed, serve._Pages(t["store_rows"]), (tok, tok), scratch)
        voc = vocab.load_or_build(harness.CACHE_DIR, cell.config)
        plan = generator.schedule(t, seed, seconds)
        texts = serve.query_pool(scratch, seed,
                                 int(plan["query"].max()) + 1,
                                 int(t["query_words"]))
        numbers = serve.check_answers(
            cell, seed, tree, voc, texts, plan, [[]] * len(plan["due_s"]),
            cfg.eval.store_shard_size, quant=towers.to_fp8, control=True)
    numbers.pop("short_answers", None)
    return {"control_fp8": numbers}


def sweep(cell, seed: int, rates: list, seconds: float, emit) -> None:
    """The knee, once: one service, the window offered at each fixed rate in
    turn (queries distinct across the rates, so the embedding LRU never
    hits). A rate is sustained when the backlog at the window's close is no
    larger than at its middle and the generator ran on time."""
    import numpy as np
    from benchmarks import harness
    from benchmarks.jobs import serve
    from benchmarks.traffic import generator
    plans, offset = [], 0
    for i, rate in enumerate(rates):
        plan = generator.schedule(dict(cell.traffic, rate_qps=rate),
                                  seed + i, seconds)
        plan["query"] = plan["query"] + offset
        offset += len(plan["due_s"])
        plans.append(plan)
    with harness.scratch_dir("sweep_") as scratch:
        served = serve.Served(cell, seed, scratch, offset)
        try:
            for rate, plan in zip(rates, plans):
                st = served.drive(plan, seconds, False)
                lat = st["latency_ms"]
                emit({"cell": cell.name, "kind": "sweep", "rate_qps": rate,
                      "requests": st["n"], "failed": st["failed"],
                      "p50_ms": harness.percentile(lat, 50),
                      "p95_ms": harness.percentile(lat, 95),
                      "max_ms": float(np.max(lat)),
                      "topk_ms_per_bucket": 1e3 * (
                          st["stage_seconds"].get("topk", 0)
                          + st["stage_seconds"].get("merge", 0))
                      / max(st["stage_counts"].get("topk", 1), 1),
                      **st["ctx"]})
        finally:
            served.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--sweep", default="",
                    help="serve cells: comma-separated rates to offer in "
                         "turn to one service, in place of the readings")
    args = ap.parse_args(argv)
    import importlib
    from benchmarks import harness
    harness.setup_jax()
    cell = harness.Cell(args.cell)
    harness.require_chips(cell.chips)
    job = importlib.import_module(f"benchmarks.jobs.{cell.job}")
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(_ROOT, "chiprun_out", f"study_{args.cell}.jsonl")
    lower: dict = {}
    upper: dict = {}
    with open(path, "a") as log:
        def emit(rec):
            line = json.dumps(rec)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
        if args.sweep:
            sweep(cell, args.first_seed,
                  [float(r) for r in args.sweep.split(",")], args.seconds,
                  emit)
            return 0
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            out = job.run(cell, seed, args.seconds, False, t0)
            nums = {k: c["value"] for k, c in out["compared"].items()}
            emit({"cell": args.cell, "seed": seed, "kind": "program",
                  "numbers": nums, "correct": out["correct"],
                  "end_to_end": out["end_to_end"], "device": out["device"],
                  "wall_s": time.perf_counter() - t0})
            for k, v in nums.items():
                lower[k] = max(lower.get(k, 0.0), v)
            if i < args.control_seeds:
                ctl = (_train_controls(cell, seed) if cell.job == "train"
                       else _serve_controls(cell, seed, args.seconds))
                for kind, numbers in ctl.items():
                    emit({"cell": args.cell, "seed": seed, "kind": kind,
                          "numbers": numbers})
                    for k, v in numbers.items():
                        u = upper.setdefault(kind, {})
                        u[k] = min(u.get(k, float("inf")), v)
        emit({"cell": args.cell, "kind": "summary", "lower": lower,
              "upper": upper, "seeds": args.seeds})
    return 0


if __name__ == "__main__":
    sys.exit(main())
