#!/usr/bin/env python3
"""The readings the limits of the `qwen3_next` train cell are set from
(`study_moe.py` for the job `jobs/train_qwen3_next.py`; not run by the
benchmark):

    python3 benchmarks/study_qwen3_next.py qwen3_next_80b_ep16.train \
        --seeds 3 --control-seeds 1 --seconds 4

For each seed one short run of the cell's job gives the program's numbers
against the plain reference. For the first `--control-seeds` seeds the
reference is also put in the program's place: in float8 (the control) and
with each planted fault of `reference/qwen3_next.py:FAULTS`. One JSON line
per reading and a summary (the largest sound reading of each number, the
least of each control's), on standard output and under chiprun_out/.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_101)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    from benchmarks import harness
    from benchmarks.jobs import train_qwen3_next as job
    harness.setup_jax()
    cell = harness.Cell(args.cell)
    harness.require_chips(cell.chips)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(_ROOT, "chiprun_out", f"study_{args.cell}.jsonl")
    sound: dict = {}
    faulty: dict = {}
    with open(path, "a") as log:
        def emit(rec):
            line = json.dumps(rec)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
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
                      "gdn_state_norm_max": out["ctx"]["gdn_state_norm_max"],
                      "wall_s": time.perf_counter() - t0})
                for k, v in nums.items():
                    sound[k] = max(sound.get(k, 0.0), v)
            if i < args.control_seeds:
                t0 = time.perf_counter()
                for kind, numbers in job.controls(cell, seed).items():
                    emit({"cell": args.cell, "seed": seed, "kind": kind,
                          "numbers": numbers})
                    least = faulty.setdefault(kind, {})
                    for k, v in numbers.items():
                        least[k] = min(least.get(k, float("inf")), v)
                emit({"cell": args.cell, "seed": seed, "kind": "controls_s",
                      "wall_s": time.perf_counter() - t0})
        emit({"cell": args.cell, "kind": "summary", "sound": sound,
              "controls": faulty, "seeds": args.seeds})
    return 0


if __name__ == "__main__":
    sys.exit(main())
