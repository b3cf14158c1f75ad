"""`trace_reduce`: busy union, idle share, per-module time, gaps laid to the
host — on hand-made planes, and on a small trace recorded on the chip and
kept under benchmarks/testdata. No accelerator library is loaded to read it."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmarks", "testdata", "small.xplane.pb")


def planes():
    ms = 1e6
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(123)", 0.0, 4 * ms),
                            ("jit_step(123)", 10 * ms, 4 * ms),
                            ("jit_other(9)", 16 * ms, 2 * ms)],
            "XLA Ops": [("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8] %p)", 0.0,
                         2 * ms), ("fusion.2", 1 * ms, 3 * ms),
                        ("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8] %p)",
                         10 * ms, 4 * ms),
                        ("copy.3", 16 * ms, 2 * ms)],
        },
        "/host:CPU": {
            "main": [("bench_window", 0.0, 20 * ms),
                     ("tokenize", 4 * ms, 6 * ms),
                     ("sleep", 14 * ms, 1.5 * ms)],
        },
    }


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    assert trace_reduce.module_name("jit_train_step(8812)") == \
        "jit_train_step"


def test_reduce_busy_idle_modules_and_gaps():
    red = trace_reduce.reduce(planes(), window_ns=(0.0, 20e6))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.020)
    assert red["busy_s"] == pytest.approx(0.010)        # 4 + 4 + 2 ms
    assert red["idle_share"] == pytest.approx(0.5)
    assert trace_reduce.find_module(red, "jit_step") == {
        "seconds": pytest.approx(0.008), "launches": 2}
    assert red["modules"]["jit_other(9)"]["launches"] == 1
    assert trace_reduce.find_module(red, "jit_absent") is None
    assert red["device_ops"][0] == ["fusion.1 f32[8,8]",
                                    pytest.approx(0.006)]
    gaps = dict(red["idle_gaps"])
    assert gaps["tokenize"] == pytest.approx(0.006)      # 4..10 ms
    assert gaps["sleep"] == pytest.approx(0.002)         # 14..16 ms
    assert gaps["bench_window"] == pytest.approx(0.002)  # 18..20 ms
    # cut to a narrower window
    cut = trace_reduce.reduce(planes(), window_ns=(10e6, 18e6))
    assert cut["busy_s"] == pytest.approx(0.006)
    assert cut["modules"]["jit_step(123)"]["launches"] == 1


def test_reduce_refuses_a_trace_without_device_events():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"/host:CPU": {"main": [("x", 0.0, 1.0)]}})


def test_importing_the_reducer_loads_no_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmarks.trace_reduce; "
            "assert 'jax' not in sys.modules and 'libtpu' not in sys.modules"
            % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_recorded_chip_trace():
    """Four launches each of two small programs on one v5e, 2 ms of host
    sleep between them, under the window's span."""
    from benchmarks import harness
    loaded = trace_reduce.load(RECORDED)
    window = harness._span_window(loaded)
    assert window is not None
    red = trace_reduce.reduce(loaded, window_ns=window)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0.5 < red["idle_share"] < 1.0       # the host slept between them
    # two programs, both `jit__lambda`, told apart by their fingerprints;
    # the clocks of host and device differ by some microseconds, so a launch
    # at the window's edge may fall outside it
    assert len(red["modules"]) == 2
    assert all(3 <= m["launches"] <= 4 for m in red["modules"].values())
    assert trace_reduce.find_module(red, "jit__lambda")["launches"] >= 3
    for m in red["modules"].values():
        assert 0 < m["seconds"] < red["busy_s"] * 1.001
    assert red["device_ops"] and red["idle_gaps"]
    assert all(len(name) <= 80 for name, _ in red["device_ops"])
    assert sum(s for _, s in red["idle_gaps"]) <= \
        red["window_s"] - red["busy_s"] + 1e-9
