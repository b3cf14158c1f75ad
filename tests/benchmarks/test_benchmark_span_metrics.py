"""The three readers of the program's own spans: each on a hand-made `ctx`
gives the exact value, and nothing where its inputs are missing (as in a
program that has no such span)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

BUSY = "dispatcher_busy_share.serve"
SELF = "dispatch_self_ms.serve"
WITH_WORK = "idle_with_work_share.serve"


def ctx(**over):
    """A traced serve window of 6 s: 30 batches took 4.5 s of the
    dispatcher thread, 4.2 s of that under a named stage; the device was
    idle for 85%, 0.6 s + 0.3 s of it while the dispatcher waited."""
    base = {
        "job": "serve", "window_s": 6.0,
        "stage_seconds": {"dispatch": 4.5, "tokenize": 0.03, "encode": 0.06,
                          "topk": 3.9, "merge": 0.15, "format": 0.06,
                          "queue_wait": 19.0, "batcher_idle": 1.1,
                          "batch_window": 0.4},
        "stage_counts": {"dispatch": 30, "tokenize": 30, "encode": 30,
                         "topk": 30, "merge": 30, "format": 30,
                         "queue_wait": 120, "batcher_idle": 31,
                         "batch_window": 30},
        "reduced": {"window_s": 6.0, "busy_s": 0.9, "idle_share": 0.85,
                    "idle_gaps": [["serve.topk", 3.3],
                                  ["serve.batcher_idle", 0.6],
                                  ["PjitFunction(convert_element_type)", 0.5],
                                  ["serve.batch_window", 0.3],
                                  ["bench_window", 0.2],
                                  ["serve.dispatch", 0.2]]},
    }
    base.update(over)
    return base


def without(c, group, *keys):
    return dict(c, **{group: {k: v for k, v in c[group].items()
                              if k not in keys}})


@pytest.mark.parametrize("name,want", [
    (BUSY, 100.0 * 4.5 / 6.0),
    (SELF, 1000.0 * (4.5 - (0.03 + 0.06 + 3.9 + 0.15 + 0.06)) / 30),
    (WITH_WORK, 100.0 * (0.85 - (0.6 + 0.3) / 6.0)),
])
def test_reader_gives_the_exact_value(name, want):
    assert harness.read_metric(name, ctx()) == pytest.approx(want, rel=1e-12)


def test_self_time_sums_only_the_children_present():
    c = without(ctx(), "stage_seconds", "format", "tokenize")
    assert harness.read_metric(SELF, c) == pytest.approx(
        1000.0 * (4.5 - (0.06 + 3.9 + 0.15)) / 30)


def test_idle_with_work_equals_idle_share_when_no_waiting_gap_is_listed():
    red = dict(ctx()["reduced"], idle_gaps=[["serve.topk", 4.2],
                                            ["bench_window", 0.9]])
    assert harness.read_metric(WITH_WORK, ctx(reduced=red)) \
        == pytest.approx(85.0)
    red["idle_gaps"] = []
    assert harness.read_metric(WITH_WORK, ctx(reduced=red)) \
        == pytest.approx(85.0)


PARENT = without(without(ctx(), "stage_seconds", "dispatch", "batcher_idle",
                         "batch_window"),
                 "stage_counts", "dispatch", "batcher_idle", "batch_window")


@pytest.mark.parametrize("name,missing", [
    (BUSY, {}), (SELF, {}), (WITH_WORK, {}),
    (BUSY, ctx(job="train")), (SELF, ctx(job="train")),
    (WITH_WORK, ctx(job="train")),
    # the parent commit's program: the same window with no dispatcher span
    (BUSY, PARENT), (SELF, PARENT), (WITH_WORK, PARENT),
    (BUSY, ctx(window_s=0.0)),
    (SELF, without(ctx(), "stage_counts", "dispatch")),
    (WITH_WORK, ctx(reduced=None)),                # an untraced run
    (WITH_WORK, ctx(reduced=dict(ctx()["reduced"], idle_share=None))),
])
def test_reader_gives_nothing_when_its_inputs_are_missing(name, missing):
    assert harness.read_metric(name, missing) is None
