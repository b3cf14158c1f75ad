"""A shrunken copy of the `granitemoehybrid` cell's files in a directory of
the caller's, for the CPU rehearsals in the tests (`tiny.py` and
`tiny_moe.py` do the same for the other cells): same code paths, toy widths.
Nothing here is used by a benchmark run."""
from __future__ import annotations

import json
import os

from .tiny import _load

# hidden 64, 8 Mamba heads of 16 (expand 2), state 16, chunk 8, 4 + 2 attention
# heads of 16, 8 experts of width 16 (4 held, from the third), 3 a token
TYPES = ["mamba", "mamba", "attention", "mamba"]
PUBLISHED = {"hidden_size": 64, "intermediate_size": 16,
             "shared_intermediate_size": 32, "num_attention_heads": 4,
             "num_key_value_heads": 2, "attention_multiplier": 0.0625,
             "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
             "mamba_chunk_size": 8, "num_local_experts": 8,
             "num_experts_per_tok": 3, "num_hidden_layers": 4,
             "layer_types": TYPES, "vocab_size": 512}
HELD = {"num_hidden_layers": 4, "layer_types": TYPES, "num_local_experts": 4,
        "experts_held_start": 2, "vocab_size": 512}
ASSUMED = {"query_len": 24, "page_len": 24, "out_dim": 32,
           "encode_batch": 1}
LIMITS = {"rank_gap": 1e-3, "score_gap": 1e-3, "vector_gap": 1e-3,
          "routing_gap": 0.0}


def make_root(dest: str, cell_name: str, limits: dict | None = None,
              weights_dtype: str = "float32", **traffic_changes) -> str:
    """Write BENCHMARK.json and the cell's three files under `dest`, with
    toy sizes; returns `dest`, to be given to `harness.Cell(name, root)`."""
    manifest = _load("BENCHMARK.json")
    entry = [w for w in manifest["workloads"] if w["name"] == cell_name][0]
    cfg_entry = [c for c in manifest["configs"]
                 if c["name"] == entry["config"]][0]
    config = _load(cfg_entry["file"])
    config["published"].update(PUBLISHED)
    config["held"].update(HELD)
    config["assumed"].update(ASSUMED)
    config["compute_dtype"] = config["weights_dtype"] = weights_dtype
    pub, held, a = config["published"], config["held"], config["assumed"]
    config["program"]["overrides"].update({
        "model.model_dim": pub["hidden_size"],
        "model.mlp_dim": pub["intermediate_size"],
        "model.shared_intermediate_size": pub["shared_intermediate_size"],
        "model.num_heads": pub["num_attention_heads"],
        "model.num_key_value_heads": pub["num_key_value_heads"],
        "model.attention_multiplier": pub["attention_multiplier"],
        "model.mamba_n_heads": pub["mamba_n_heads"],
        "model.mamba_d_head": pub["mamba_d_head"],
        "model.mamba_d_state": pub["mamba_d_state"],
        "model.mamba_chunk_size": pub["mamba_chunk_size"],
        "model.n_routed_experts": pub["num_local_experts"],
        "model.num_experts_per_tok": pub["num_experts_per_tok"],
        "model.num_layers": held["num_hidden_layers"],
        "model.layer_types": held["layer_types"],
        "model.experts_held": held["num_local_experts"],
        "model.experts_held_start": held["experts_held_start"],
        "model.out_dim": a["out_dim"], "model.dtype": weights_dtype,
        "model.weights_dtype": weights_dtype,
        "data.vocab_size": held["vocab_size"],
        "data.page_len": a["page_len"], "data.query_len": a["query_len"],
        "serve.encode_batch": a["encode_batch"], "serve.max_batch": 2,
        "eval.store_shard_size": 512})
    traffic = _load("benchmarks", "traffic", entry["traffic"] + ".json")
    traffic.update(query_tokens=a["query_len"], store_rows=2048,
                   checked_answers=8, clients=8, rate_qps=20.0)
    traffic.update(traffic_changes)
    workload = _load("benchmarks", "workloads", cell_name + ".json")
    workload["reference_block_rows"] = 4
    workload["limits"] = dict(LIMITS if limits is None else limits)
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
