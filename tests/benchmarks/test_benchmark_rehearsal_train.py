"""The train job rehearsed on the CPU at toy widths (shrunk here, in the
test only): the result line's keys, a run without a chip that fails rather
than reports, the timed path broken underneath (`correct` has to come out
false), and the lower-precision control, which has to fail."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, tiny  # noqa: E402

SEED, TRAIN_LIMITS = tiny.SEED, tiny.TRAIN_LIMITS
TRAIN_CELLS = {
    "mt5_base.train": {"overrides": {"train.batch_size": 16},
                       "corpus_pages": 4096},
    # its files are in the tree, but BENCHMARK.json does not list it: its
    # memory reading is under the driver's floor (PERF.md, Open questions)
    "bert_mini.train": {"overrides": {"train.batch_size": 16},
                        "corpus_pages": 512,
                        "entry": {"config": "bert_mini", "chips": 1,
                                  "like": "mt5_base.train",
                                  "traffic": "train_text_b2048",
                                  "why": "text in, steps out"}},
}


@pytest.fixture(autouse=True)
def _caches_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "JAX_CACHE", str(tmp_path / "jax"))


def _train(tmp_path, name):
    from benchmarks.jobs import train
    root = tiny.make_root(str(tmp_path / "root"), name, limits=TRAIN_LIMITS,
                          **TRAIN_CELLS[name])
    cell = harness.Cell(name, root)
    return cell, train.run(cell, SEED, 1.5, False, time.perf_counter(),
                           require_chip=False)


@pytest.mark.parametrize("name", sorted(TRAIN_CELLS))
def test_train_rehearsal_is_correct_and_prints_its_line(tmp_path, name):
    cell, out = _train(tmp_path, name)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    line = tiny.check_line(cell, out, "train_pages_per_s")
    assert {"loss1", "loss2", "loss3", "grad_norm", "change_norm"} <= \
        set(line["compared"])
    ctx = out["ctx"]
    for metric in ("input_wait_share.train", "step_ms.train"):
        assert harness.read_metric(metric, ctx) is not None
    # no trace, no chip: device metrics are left out, never reported as 0
    assert harness.read_metric("step_device_ms.train", ctx) is None
    assert harness.read_metric("device_idle_share.train", ctx) is None
    with pytest.raises(KeyError):
        harness.read_metric("step_mfu.train", ctx)     # no peak for "cpu"


def _frozen(step):
    import jax
    import jax.numpy as jnp

    def frozen(state, batch, rng):
        copy = jax.tree_util.tree_map(jnp.array, state)
        return state, step(copy, batch, rng)[1]
    return frozen


def _half(step):
    def half(state, batch, rng):
        n = batch["query"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()}, rng)
    return half


@pytest.mark.parametrize("fault", [_frozen, _half])
def test_train_fault_reads_not_correct(tmp_path, monkeypatch, fault):
    from benchmarks.jobs import train
    monkeypatch.setattr(train, "_wrap_step", fault)
    _, out = _train(tmp_path, "bert_mini.train")
    assert out["correct"] is False
    bad = {k for k, c in out["compared"].items() if not c["ok"]}
    assert bad, out["compared"]


def test_train_control_in_fp8_fails_a_limit(tmp_path):
    """The reference computed in float8 in the program's place."""
    from benchmarks import compare
    from benchmarks.jobs import train
    from benchmarks.reference import towers
    name = "mt5_base.train"
    root = tiny.make_root(str(tmp_path / "root"), name, limits=TRAIN_LIMITS,
                          **TRAIN_CELLS[name])
    cell = harness.Cell(name, root)
    with harness.scratch_dir("ctl_") as scratch:
        feed = train.Feed(cell, SEED, scratch)
        tree = train.tree_without_a_run(cell, SEED, feed.corpus,
                                        feed.tokenizers, scratch)
        rows = [np.arange(i * 16, i * 16 + 16) for i in range(3)]
        ref = train.reference_readings(cell, feed, tree, SEED, rows)
        low = train.reference_readings(cell, feed, tree, SEED, rows,
                                       quant=towers.to_fp8)
        half = train.reference_readings(cell, feed, tree, SEED, rows,
                                        half_batch=True)
    for other in (low, half):
        judged = compare.judge(compare.train_numbers(other, ref),
                               TRAIN_LIMITS)
        assert not all(c["ok"] for c in judged.values()), judged


def test_run_without_a_chip_fails_and_prints_no_result(capsys):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import importlib
    run = importlib.import_module("benchmarks.run")
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "mt5_base.train", "--seed", "1",
                  "--seconds", "1", "--trace", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


def test_traced_run_without_a_device_fails_rather_than_reports(tmp_path):
    """Asked for device metrics where no device plane is in the trace, the
    run raises; it does not print a line with zeros in it."""
    from benchmarks.jobs import train
    name = "mt5_base.train"
    root = tiny.make_root(str(tmp_path / "root"), name, limits=TRAIN_LIMITS,
                          **TRAIN_CELLS[name])
    with pytest.raises(ValueError, match="no device plane"):
        train.run(harness.Cell(name, root), SEED, 1.0, True,
                  time.perf_counter(), require_chip=False)
