"""BENCHMARK.json and the files it names: the contract's limits on names
and units, every cell's files found by name, the copied FLOP arithmetic
against the program's, the table of peaks, and the traffic generator."""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops, harness  # noqa: E402
from benchmarks.traffic import generator  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in manifest()["workloads"]]
PER_LAYER = [m["name"] for m in manifest()["per_layer"]]


def test_manifest_keys_names_and_units():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names)), group
        for e in m[group]:
            assert NAME.match(e["name"]), e["name"]
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert any(e["name"] == "setup_s" for e in m["end_to_end"])
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)


def test_every_file_lies_under_paths_and_is_named_plainly():
    m = manifest()
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in m["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel
    for c in m["configs"]:
        assert any(c["file"].startswith(p + "/") for p in m["paths"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = harness.Cell(name)
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "jobs", cell.job + ".py"))
    assert cell.config["name"] == cell.entry["config"]
    assert "limits" in cell.workload
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.metrics("per_layer")


@pytest.mark.parametrize("name", PER_LAYER)
def test_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(name):
    m = manifest()
    metric = [x for x in m["per_layer"] if x["name"] == name][0]
    path = os.path.join(ROOT, "benchmarks", "metrics", name + ".py")
    assert os.path.exists(path)
    assert harness.read_metric(name, {}) is None     # nothing to read
    moved = [e for e in m["end_to_end"] if e["name"] == metric["moves"]]
    assert len(moved) == 1
    cells = metric.get("workloads", CELLS)
    reporting = moved[0].get("workloads", CELLS)
    assert cells and set(cells) <= set(reporting)
    layers = {x["layer"] for x in m["per_layer"]}
    assert metric["layer"] in layers and "\n" not in metric["layer"]


@pytest.mark.parametrize("config,batch,gflop", [
    ("mt5_base", 256, 75.23), ("bert_mini", 2048, 1.567)])
def test_copied_flops_equal_the_programs(config, batch, gflop):
    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.utils import flops as prog
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    shape = flops.shape_of(cfg)
    pcfg = get_config(cfg["program"]["preset"],
                      dict(cfg["program"]["overrides"]))
    mine = flops.train_flops_per_pair(shape, batch)
    assert mine == prog.train_flops_per_pair(pcfg, batch)
    assert mine / 1e9 == pytest.approx(gflop, rel=1e-3)
    assert flops.embed_flops_per_page(shape) == \
        prog.embed_flops_per_page(pcfg)


@pytest.mark.parametrize("config", ["mt5_base", "bert_mini"])
def test_preset_resolves_to_the_published_widths(config):
    from benchmarks.jobs import train
    cell = harness.Cell([c for c in CELLS if c.startswith(config)][0])
    cfg = train.program_config(cell, seed=5)
    pub = cell.config["published"]
    assert cfg.model.num_heads == pub.get("num_heads",
                                          pub.get("num_attention_heads"))
    assert cfg.mesh.num_devices == 1 and cfg.model.attention == "dense"


def test_peaks_known_kind_and_unknown_kind():
    row = flops.peaks_for("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v5", "cpu", "source", ""):
        with pytest.raises(KeyError):
            flops.peaks_for(kind)


def test_schedule_same_seed_same_traffic_other_seed_same_gaps():
    t = {"rate_qps": 50.0, "distinct_queries": 0, "arrival_seed": 9}
    a = generator.schedule(t, 2**31 + 5, 10.0)
    b = generator.schedule(t, 2**31 + 5, 10.0)
    c = generator.schedule(t, 7, 10.0)
    assert len(a["due_s"]) == 500
    assert (a["due_s"] == b["due_s"]).all() and (a["query"] == b["query"]).all()
    assert not (a["due_s"] == c["due_s"]).all()
    assert len(set(a["query"].tolist())) == 500       # every text distinct
    assert 0 <= a["due_s"].min() and a["due_s"].max() < 10.0

    def gaps(s):       # due = cumsum(gaps) - gaps[0] / 2
        return np.concatenate([[2 * s["due_s"][0]], np.diff(s["due_s"])])
    ga, gc = gaps(a), gaps(c)
    assert np.allclose(np.sort(ga), np.sort(gc))      # the same set of gaps
    # ... in the same cyclic order, started elsewhere
    assert any(np.allclose(np.roll(ga, r), gc) for r in range(500))
    other = generator.schedule(dict(t, arrival_seed=10), 7, 10.0)
    assert not any(np.allclose(np.roll(gaps(other), r), gc)
                   for r in range(500))
    zipf = generator.schedule({"rate_qps": 50.0, "distinct_queries": 16,
                               "zipf_alpha": 1.0}, 3, 10.0)
    assert zipf["query"].max() < 16


@pytest.mark.parametrize("reading", [float("nan"), float("inf")])
def test_reading_that_is_no_number_fails_and_stays_plain_json(reading):
    from benchmarks import compare
    gap, leaf = compare.worst_leaf_gap({"a": reading, "b": 1.0},
                                       {"a": 1.0, "b": 1.0})
    judged = compare.judge({"gap": gap, "free": 2.0}, {"gap": 0.5})
    assert leaf == "a" and judged["gap"]["ok"] is False
    assert judged["free"]["ok"] is True
    json.dumps(judged, allow_nan=False)          # raises on inf or nan
