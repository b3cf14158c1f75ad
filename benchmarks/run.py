#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by the names in BENCHMARK.json, runs its job in this
one process on the machine it is started on, and prints one JSON object as
the last line of standard output. With no accelerator, or fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()      # set-up is counted from here

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import harness
    cell = harness.Cell(args.workload)
    job = importlib.import_module(f"benchmarks.jobs.{cell.job}")
    out = job.run(cell, args.seed, args.seconds, bool(args.trace), _T_START)
    line = harness.result_line(cell, bool(args.trace), out)
    harness.print_compared(out["compared"])
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
