"""Production SLO harness (docs/SERVING.md "SLO methodology"):

  * `workload` — seeded open-loop (Poisson, burst) and closed-loop
    traffic models over a Zipfian query mix with mixed (k, nprobe)
    profiles, plus the optional concurrent append/refresh `Mutator`;
  * `driver` — timed trials against a live `SearchService`, every number
    read from the PR-7 telemetry registry, and the binary search for
    "qps @ p99 < X ms".

Entry points: `cli loadtest` (one-shot report: `run_trial` and the
`find_qps_at_p99` search) and `tests/test_loadgen.py` (the `slo`
marker).
"""
from dnn_page_vectors_tpu.loadgen.driver import (
    BalancedClient, find_qps_at_p99, run_trial, snapshot_line)
from dnn_page_vectors_tpu.loadgen.workload import (
    DEFAULT_PROFILE, SHAPES, BurstWorkload, ClosedLoopWorkload, Mutator,
    PoissonWorkload, QueryMix, Request, Workload, make_workload)

__all__ = [
    "BalancedClient", "BurstWorkload", "ClosedLoopWorkload",
    "DEFAULT_PROFILE", "Mutator",
    "PoissonWorkload", "QueryMix", "Request", "SHAPES", "Workload",
    "find_qps_at_p99", "make_workload", "run_trial", "snapshot_line",
]
