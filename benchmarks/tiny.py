"""A shrunken copy of a cell's files in a directory of the caller's, for the
CPU rehearsals in the tests: same code paths, toy widths, seconds not
minutes. Nothing here is used by a benchmark run."""
from __future__ import annotations

import json
import os

from . import harness

_TINY = {"d": 32, "ff": 64, "layers": 2, "heads": 2, "vocab": 512,
         "out_dim": 32}


def _load(*parts) -> dict:
    with open(os.path.join(harness.ROOT, *parts)) as f:
        return json.load(f)


def make_root(dest: str, cell_name: str, limits: dict | None = None,
              entry: dict | None = None, **traffic_changes) -> str:
    """Write BENCHMARK.json and the cell's three files under `dest`, with
    toy sizes; returns `dest`, to be given to `harness.Cell(name, root)`.
    `entry` is the `workloads` entry of a cell whose files are in the tree
    but which BENCHMARK.json does not list; it is added to the copy, and
    reports the metrics of the listed cell it names under `like`."""
    manifest = _load("BENCHMARK.json")
    if entry is not None:
        entry = dict(entry, name=cell_name)
        like = entry.pop("like")      # it reports what that listed cell does
        manifest["workloads"].append(entry)
        for group in ("end_to_end", "per_layer"):
            for metric in manifest[group]:
                if like in metric.get("workloads", ()):
                    metric["workloads"].append(cell_name)
    entry = [w for w in manifest["workloads"] if w["name"] == cell_name][0]
    cfg_entry = [c for c in manifest["configs"]
                 if c["name"] == entry["config"]][0]
    config = _load(cfg_entry["file"])
    pub, t = config["published"], _TINY
    if config["variant"] == "t5":
        pub.update(d_model=t["d"], d_ff=t["ff"], num_layers=t["layers"],
                   num_heads=t["heads"], d_kv=t["d"] // t["heads"])
    else:
        pub.update(hidden_size=t["d"], intermediate_size=t["ff"],
                   num_hidden_layers=t["layers"],
                   num_attention_heads=t["heads"])
    pub["vocab_size"] = t["vocab"]
    config["assumed"]["out_dim"] = t["out_dim"]
    if "vocab_sample" in config["assumed"]:
        config["assumed"]["vocab_sample"]["pages"] = 256
    config["program"]["overrides"].update({
        "model.model_dim": t["d"], "model.mlp_dim": t["ff"],
        "model.num_layers": t["layers"], "model.num_heads": t["heads"],
        "model.out_dim": t["out_dim"], "data.vocab_size": t["vocab"]})
    traffic = _load("benchmarks", "traffic", entry["traffic"] + ".json")
    traffic.update(traffic_changes)
    workload = _load("benchmarks", "workloads", cell_name + ".json")
    workload["reference_block_rows"] = 8
    if limits is not None:
        workload["limits"] = limits
    bench = os.path.join(dest, "benchmarks")
    for sub in ("configs", "workloads", "traffic"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for path, obj in (
            (os.path.join(dest, "BENCHMARK.json"), manifest),
            (os.path.join(dest, cfg_entry["file"]), config),
            (os.path.join(bench, "workloads", cell_name + ".json"), workload),
            (os.path.join(bench, "traffic",
                          entry["traffic"] + ".json"), traffic)):
        with open(path, "w") as f:
            json.dump(obj, f)
    return dest


# -- shared by the rehearsal tests ------------------------------------------

SEED = 2**31 + 1234            # more than 32 signed bits hold
TRAIN_LIMITS = {"loss1": 0.02, "loss2": 0.02, "loss3": 0.02,
                "grad_norm": 0.1, "change_norm": 0.1}
SERVE_LIMITS = {"rank_gap": 0.02, "score_gap": 0.02}


def check_line(cell, out: dict, metric: str) -> dict:
    """The `--trace 0` result line of a rehearsed run, checked for the keys
    the driver reads; returns it as parsed back from its JSON."""
    line = json.loads(json.dumps(harness.result_line(cell, False, out)))
    assert list(line)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert set(line["metrics"]) == {metric, "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["compared"].values():
        assert set(c) == {"value", "limit", "ok"}
    return line
