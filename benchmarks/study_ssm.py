#!/usr/bin/env python3
"""The readings the limits of the `granitemoehybrid` serve cell are set from,
and its rate sweep (`study.py` for a job whose controls live in
`jobs/serve_ssm.py`; not run by the benchmark):

    python3 benchmarks/study_ssm.py <cell> --seeds 3 --control-seeds 3 --seconds 4
    python3 benchmarks/study_ssm.py <cell> --sweep 4,8,12,16 --seconds 15

For each seed one short run of the cell's job gives the program's numbers
against the plain reference. For the first `--control-seeds` seeds the
reference is also put in the program's place: in float8 (the control), with
the state dropped at every chunk boundary, with a softmax over all the
router's logits, and without the residual multiplier (the planted faults);
each is judged by the cell's limits as a run is (`compare.judge`), its line
says `correct` and which limits it failed, and the exit code is 1 if one of
them reads correct. `--sweep` offers the window at each fixed rate in turn to one service. One
JSON line per reading and a summary, on standard output and under
chiprun_out/.
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


def sweep(cell, seed: int, rates: list, seconds: float, emit) -> None:
    """The knee, once: a rate is sustained when the backlog at the window's
    close is no larger than at its middle and the generator ran on time."""
    import numpy as np
    from benchmarks import harness
    from benchmarks.jobs import serve_ssm
    from benchmarks.traffic import generator
    plans, offset = [], 0
    for i, rate in enumerate(rates):
        plan = generator.schedule(dict(cell.traffic, rate_qps=rate),
                                  seed + i, seconds)
        plan["query"] = plan["query"] + offset
        offset += len(plan["due_s"])
        plans.append(plan)
    with harness.scratch_dir("sweep_") as scratch:
        served = serve_ssm.Served(cell, seed, scratch, offset)
        try:
            for rate, plan in zip(rates, plans):
                st = served.drive(plan, seconds, False)
                lat, sec, cnt = (st["latency_ms"], st["stage_seconds"],
                                 st["stage_counts"])
                per = lambda *keys: 1e3 * sum(sec.get(k, 0) for k in keys) \
                    / max(cnt.get(keys[0], 1), 1)
                emit({"cell": cell.name, "kind": "sweep", "rate_qps": rate,
                      "requests": st["n"], "failed": st["failed"],
                      "p50_ms": harness.percentile(lat, 50),
                      "p95_ms": harness.percentile(lat, 95),
                      "max_ms": float(np.max(lat)),
                      # the tail, largest first: where the 95th percentile
                      # lies among its neighbours (on a cliff between two
                      # clusters it swings with the seed)
                      "top_ms": np.sort(lat)[::-1][:24].round(1).tolist(),
                      "encode_ms_per_call": per("encode", "tokenize"),
                      "topk_ms_per_bucket": per("topk", "merge"),
                      "encode_calls": cnt.get("encode", 0), **st["ctx"]})
        finally:
            served.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_400_000_101)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates to offer in turn to one "
                         "service, in place of the readings")
    args = ap.parse_args(argv)
    from benchmarks import compare, harness
    from benchmarks.jobs import serve_ssm
    harness.setup_jax()
    cell = harness.Cell(args.cell)
    harness.require_chips(cell.chips)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(_ROOT, "chiprun_out", f"study_{args.cell}.jsonl")
    lower: dict = {}
    upper: dict = {}
    passed = []                    # controls that the limits let through
    with open(path, "a") as log:
        def emit(rec):
            line = json.dumps(rec, default=float)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
        if args.sweep:
            sweep(cell, args.first_seed,
                  [float(r) for r in args.sweep.split(",")], args.seconds,
                  emit)
            return 0
        for i in range(max(args.seeds, args.control_seeds)):
            seed = args.first_seed + 7919 * i
            if i < args.seeds:
                t0 = time.perf_counter()
                out = serve_ssm.run(cell, seed, args.seconds, False, t0)
                nums = {k: c["value"] for k, c in out["compared"].items()}
                emit({"cell": args.cell, "seed": seed, "kind": "program",
                      "numbers": nums, "correct": out["correct"],
                      "end_to_end": out["end_to_end"],
                      "device": out["device"],
                      "wall_s": time.perf_counter() - t0})
                for k, v in nums.items():
                    lower[k] = max(lower.get(k, 0.0), v)
            if i < args.control_seeds:
                t0 = time.perf_counter()
                for kind, numbers in serve_ssm.controls(cell, seed).items():
                    judged = compare.judge(numbers, serve_ssm.limits_of(cell))
                    failed = sorted(k for k, c in judged.items()
                                    if not c["ok"])
                    emit({"cell": args.cell, "seed": seed, "kind": kind,
                          "numbers": numbers, "correct": not failed,
                          "failed_limits": failed})
                    if not failed:
                        passed.append((kind, seed))
                    u = upper.setdefault(kind, {})
                    for k, v in numbers.items():
                        u[k] = min(u.get(k, float("inf")), v)
                emit({"cell": args.cell, "seed": seed, "kind": "controls_s",
                      "wall_s": time.perf_counter() - t0})
        emit({"cell": args.cell, "kind": "summary", "lower": lower,
              "upper": upper, "seeds": args.seeds,
              "controls_read_correct": passed})
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
