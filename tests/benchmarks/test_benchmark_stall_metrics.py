"""The two readers of what lies inside the serve encode (PR 39): its launch
and its wait. Each on a hand-made `ctx` gives the exact value, and nothing
where its inputs are missing, as in the parent's program, which has no
such sums."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, tiny  # noqa: E402

WAIT = "encode_wait_ms.serve"
LAUNCH = "encode_launch_ms.serve"


def ctx(**over):
    """A serve window of 6 s: 40 encode calls of 61 ms, 58 of them a wait
    on the tower; the collector ran 90 passes, two of them
    second-generation."""
    base = {
        "job": "serve", "window_s": 6.0,
        "stage_seconds": {
            "dispatch": 2.9, "tokenize": 0.02, "encode": 2.44,
            "encode_launch": 0.08, "encode_wait": 2.32, "topk": 0.11,
            "merge": 0.14, "format": 0.29, "gc": 0.16, "gc_gen2": 0.12,
            "queue_wait": 3.0},
        "stage_counts": {
            "dispatch": 36, "tokenize": 40, "encode": 40,
            "encode_launch": 40, "encode_wait": 40, "topk": 36, "merge": 36,
            "format": 36, "gc": 90, "gc_gen2": 2, "queue_wait": 40},
    }
    base.update(over)
    return base


def without(c, *keys):
    return dict(c, **{group: {k: v for k, v in c[group].items()
                              if k not in keys}
                      for group in ("stage_seconds", "stage_counts")})


@pytest.mark.parametrize("name,want", [
    (WAIT, 1000.0 * 2.32 / 40),
    (LAUNCH, 1000.0 * 0.08 / 40),
])
def test_reader_gives_the_exact_value(name, want):
    assert harness.read_metric(name, ctx()) == pytest.approx(want, rel=1e-12)


def test_launch_and_wait_lie_inside_encode():
    c = ctx()
    assert (harness.read_metric(LAUNCH, c) + harness.read_metric(WAIT, c)
            <= harness.read_metric("encode_ms.serve", c))


# the parent commit's program: the same window with none of the new sums
PARENT = without(ctx(), "encode_launch", "encode_wait", "gc", "gc_gen2")


@pytest.mark.parametrize("name,missing", [
    (WAIT, {}), (LAUNCH, {}),
    (WAIT, ctx(job="train")), (LAUNCH, ctx(job="train")),
    (WAIT, PARENT), (LAUNCH, PARENT),
    (WAIT, without(ctx(), "encode")), (LAUNCH, without(ctx(), "encode")),
])
def test_reader_gives_nothing_when_its_inputs_are_missing(name, missing):
    assert harness.read_metric(name, missing) is None


def test_the_serve_job_gives_both_a_number(tmp_path, monkeypatch):
    """The serve job rehearsed at toy widths (as in the serve rehearsal's
    own test): its `ctx` carries the new sums, so each reader gives a
    number, and the encode's two parts lie within `encode_ms.serve`."""
    from benchmarks.jobs import serve
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "JAX_CACHE", str(tmp_path / "jax"))
    name = "bert_mini.serve_exact"
    root = tiny.make_root(str(tmp_path / "root"), name,
                          limits=tiny.SERVE_LIMITS, store_rows=5000,
                          store_seed=11, rate_qps=40.0, checked_answers=8,
                          clients=8)
    cell = harness.Cell(name, root)
    cell.config["program"]["overrides"]["eval.store_shard_size"] = 1024
    out = serve.run(cell, tiny.SEED, 1.5, False, time.perf_counter(),
                    require_chip=False)
    assert out["correct"], out["compared"]
    got = {m: harness.read_metric(m, out["ctx"])
           for m in (WAIT, LAUNCH, "encode_ms.serve")}
    assert all(v is not None for v in got.values()), got
    assert got[LAUNCH] + got[WAIT] <= got["encode_ms.serve"]
