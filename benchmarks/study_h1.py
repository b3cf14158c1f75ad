#!/usr/bin/env python3
"""The readings the limits of the `falcon_h1` serve cell are set from, and its
rate sweep with the rule that fixes the cell's rate (`study_ssm.py` for a job
whose controls live in `jobs/serve_h1.py`; not run by the benchmark):

    python3 benchmarks/study_h1.py <cell> --seeds 3 --control-seeds 3
    python3 benchmarks/study_h1.py <cell> --sweep 4,6,8,10,11,12,14,16 \
        --seconds 15

For each seed one short run of the cell's job gives the program's numbers
against the plain reference. For the first `--control-seeds` seeds the
reference is also put in the program's place: in float8 (the control), and
with each planted fault of `jobs/serve_h1.py:controls`; each is judged by the
cell's limits as a run is (`compare.judge`), its line says `correct` and which
limits it failed, and the exit code is 1 if one of them reads correct.
`--sweep` offers the window at each fixed rate in turn to one service and
applies the rule: a rate is sustained when the backlog at the window's close
is no larger than at its middle, nothing failed and the generator ran on time
(p95 lateness under 5 ms); the sustained rate is the highest of the grid that
passes, and the cell offers 0.6 of it. One JSON line per reading and a
summary, on standard output and under chiprun_out/.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

LATE_MS = 5.0      # the generator is on time under this (p95)


def sweep(cell, job, seed: int, rates: list, seconds: float, emit) -> None:
    import numpy as np
    from benchmarks import harness
    from benchmarks.traffic import generator
    plans, offset = [], 0
    for i, rate in enumerate(rates):
        plan = generator.schedule(dict(cell.traffic, rate_qps=rate),
                                  seed + i, seconds)
        plan["query"] = plan["query"] + offset
        offset += len(plan["due_s"])
        plans.append(plan)
    passed = []
    with harness.scratch_dir("sweep_") as scratch:
        served = job.Served(cell, seed, scratch, offset)
        try:
            for rate, plan in zip(rates, plans):
                st = served.drive(plan, seconds, False)
                lat, sec, cnt, ctx = (st["latency_ms"], st["stage_seconds"],
                                      st["stage_counts"], st["ctx"])
                per = lambda *keys: 1e3 * sum(sec.get(k, 0) for k in keys) \
                    / max(cnt.get(keys[0], 1), 1)
                ok = (ctx["backlog_at_close"] <= ctx["backlog_at_middle"]
                      and st["failed"] == 0
                      and ctx["gen_late_p95_ms"] < LATE_MS)
                if ok:
                    passed.append(rate)
                emit({"cell": cell.name, "kind": "sweep", "rate_qps": rate,
                      "sustained": ok, "requests": st["n"],
                      "failed": st["failed"],
                      "p50_ms": harness.percentile(lat, 50),
                      "p95_ms": harness.percentile(lat, 95),
                      "max_ms": float(np.max(lat)),
                      "top_ms": np.sort(lat)[::-1][:24].round(1).tolist(),
                      "encode_ms_per_call": per("encode", "tokenize"),
                      "topk_ms_per_bucket": per("topk", "merge"),
                      "encode_calls": cnt.get("encode", 0), **ctx})
        finally:
            served.close()
    knee = max(passed) if passed else None
    emit({"cell": cell.name, "kind": "sweep_rule", "grid": rates,
          "passed": passed, "sustained_qps": knee,
          "cell_rate_qps": None if knee is None else round(0.6 * knee, 2),
          "failed_below_a_pass": [r for r in rates if knee is not None
                                  and r < knee and r not in passed]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_700_000_101)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates to offer in turn to one "
                         "service, in place of the readings")
    args = ap.parse_args(argv)
    from benchmarks import compare, harness
    harness.setup_jax()
    cell = harness.Cell(args.cell)
    job = importlib.import_module(f"benchmarks.jobs.{cell.job}")
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
            sweep(cell, job, args.first_seed,
                  [float(r) for r in args.sweep.split(",")], args.seconds,
                  emit)
            return 0
        for i in range(max(args.seeds, args.control_seeds)):
            seed = args.first_seed + 7919 * i
            if i < args.seeds:
                t0 = time.perf_counter()
                out = job.run(cell, seed, args.seconds, False, t0)
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
                for kind, numbers in job.controls(cell, seed).items():
                    judged = compare.judge(numbers, job.limits_of(cell))
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
