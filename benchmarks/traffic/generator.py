"""The one general generator of request traffic: it reads a traffic file's
parameters and the seed, and returns the schedule of an open loop.

Arrivals follow a Poisson process at the file's fixed `rate_qps`, drawn so
that every seed offers the same work: the `n = round(rate * seconds)`
inter-arrival gaps are the n quantile mid-points of the exponential
distribution, put in one order that the file's `arrival_seed` draws (one
sample path of the process, with its clusters and lulls), and the run's
seed only chooses where in that cycle the window starts. So every run
offers exactly n requests with the same gaps in the same cyclic order, and
two seeds differ in the starting point and in the query texts. With `burst`
in the file the same gaps are sorted into on/off phases (short gaps
together), which keeps the mean rate.

A copy, cut to what the cells use, of the seeded open-loop model in
`dnn_page_vectors_tpu/loadgen/workload.py`.
"""
from __future__ import annotations

import numpy as np


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    """{"due_s": [n] offsets from the window's start, "query": [n] indices
    into the query pool}."""
    rate = float(traffic["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xA551])
    path = np.random.default_rng([int(traffic.get("arrival_seed", 0)), n])
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps *= (seconds * (n - 0.5) / n) / gaps.sum()   # the last is due inside
    gaps = np.roll(path.permutation(gaps), int(rng.integers(n)))
    burst = traffic.get("burst")
    if burst:
        # on/off phases: within each period the shortest gaps come first
        period = max(2, int(burst["period_requests"]))
        for s in range(0, n, period):
            gaps[s:s + period] = np.sort(gaps[s:s + period])
    due = np.cumsum(gaps) - gaps[0] * 0.5
    distinct = int(traffic.get("distinct_queries", 0)) or n
    if distinct >= n:
        query = rng.permutation(distinct)[:n]          # every text distinct
    else:
        alpha = float(traffic.get("zipf_alpha", 0.0))
        p = np.arange(1, distinct + 1, dtype=np.float64) ** -alpha
        query = rng.choice(distinct, size=n, p=p / p.sum())
    return {"due_s": due, "query": query.astype(np.int64)}
