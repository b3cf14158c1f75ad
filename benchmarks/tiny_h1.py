"""A shrunken copy of the `falcon_h1` cell's files in a directory of the
caller's, for the CPU rehearsals in the tests (`tiny.py`, `tiny_moe.py` and
`tiny_ssm.py` do the same for the other cells): same code paths, toy widths,
all twelve multipliers away from 1. Nothing here is used by a benchmark run."""
from __future__ import annotations

import json
import os

from .tiny import _load

# hidden 64, 4 mixer heads of 8 in 2 groups (inner 32), state 16, chunk 8,
# 4 + 2 attention heads of 16, FFN 96, 3 layers
PUBLISHED = {"hidden_size": 64, "intermediate_size": 96,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "mamba_n_heads": 4, "mamba_d_head": 8,
             "mamba_d_ssm": 32, "mamba_d_state": 16, "mamba_n_groups": 2,
             "mamba_chunk_size": 8, "mamba_expand": 2,
             "num_hidden_layers": 3, "vocab_size": 512,
             "embedding_multiplier": 3.0, "ssm_in_multiplier": 0.5,
             "ssm_multipliers": [0.7, 0.5, 0.6, 1.4, 0.8],
             "ssm_out_multiplier": 0.3, "attention_in_multiplier": 0.9,
             "key_multiplier": 0.25, "attention_out_multiplier": 0.4,
             "mlp_multipliers": [0.6, 0.35], "rope_theta": 1e4}
HELD = {"num_hidden_layers": 3, "vocab_size": 512}
ASSUMED = {"query_len": 24, "page_len": 24, "out_dim": 32, "encode_batch": 1,
           "gains": {"embedding": 2.0, "wq": 3.0, "wk": 3.0, "wv": 1.5,
                     "wo": 1.5, "in_proj": [2.0, 2.0, 4.0, 3.0, 2.0],
                     "out_proj": 2.0, "wi_0": 2.0, "wi_1": 1.5,
                     "wo_mlp": 2.0}}
LIMITS = {"rank_gap": 1e-3, "score_gap": 1e-3, "vector_gap": 1e-3}
# published key -> the override that carries it to the preset
_OVERRIDES = {
    "hidden_size": "model_dim", "intermediate_size": "mlp_dim",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_key_value_heads", "head_dim": "head_dim",
    "mamba_n_heads": "mamba_n_heads", "mamba_d_head": "mamba_d_head",
    "mamba_d_ssm": "mamba_d_ssm", "mamba_d_state": "mamba_d_state",
    "mamba_n_groups": "mamba_n_groups",
    "mamba_chunk_size": "mamba_chunk_size",
    "embedding_multiplier": "embedding_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_multipliers": "ssm_multipliers",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "attention_in_multiplier": "attention_in_multiplier",
    "key_multiplier": "key_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "mlp_multipliers": "mlp_multipliers", "rope_theta": "rope_theta"}


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
    config["program"]["overrides"].update(
        {"model." + field: pub[key] for key, field in _OVERRIDES.items()})
    config["program"]["overrides"].update({
        "model.num_layers": held["num_hidden_layers"],
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
