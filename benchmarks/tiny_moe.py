"""A shrunken copy of a routed-expert cell's files in a directory of the
caller's, for the CPU rehearsals in the tests (`tiny.py` does the same for
the dense cells): same code paths, toy widths. Nothing here is used by a
benchmark run."""
from __future__ import annotations

import json
import os

from .tiny import _load

# hidden 64, 8 experts of width 32 (4 held, from the third), 2 a token,
# ranks 16 / 24, 2 heads of (12 + 4 | 16), 1 dense + 2 expert layers
PUBLISHED = {"hidden_size": 64, "intermediate_size": 96,
             "moe_intermediate_size": 32, "num_attention_heads": 2,
             "num_key_value_heads": 2, "q_lora_rank": 16, "kv_lora_rank": 24,
             "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
             "n_routed_experts": 8, "num_experts_per_tok": 2,
             "num_hidden_layers": 3, "vocab_size": 512}
HELD = {"num_hidden_layers": 3, "n_routed_experts": 4,
        "experts_held_start": 2, "vocab_size": 512}
SCALING = 1.8        # the published routed_scaling_factor, kept
ASSUMED = {"query_len": 8, "page_len": 24, "out_dim": 32}
LIMITS = {"loss1": 1e-3, "loss2": 1e-3, "loss3": 1e-3, "grad_norm": 1e-3,
          "change_norm": 1e-3}


def make_root(dest: str, cell_name: str, batch: int = 8,
              limits: dict | None = None, dtype: str = "float32") -> str:
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
    config["compute_dtype"] = dtype
    pub, held, a = config["published"], config["held"], config["assumed"]
    config["program"]["overrides"].update({
        "model.model_dim": pub["hidden_size"],
        "model.mlp_dim": pub["intermediate_size"],
        "model.moe_intermediate_size": pub["moe_intermediate_size"],
        "model.num_heads": pub["num_attention_heads"],
        "model.q_lora_rank": pub["q_lora_rank"],
        "model.kv_lora_rank": pub["kv_lora_rank"],
        "model.qk_nope_head_dim": pub["qk_nope_head_dim"],
        "model.qk_rope_head_dim": pub["qk_rope_head_dim"],
        "model.v_head_dim": pub["v_head_dim"],
        "model.n_routed_experts": pub["n_routed_experts"],
        "model.num_experts_per_tok": pub["num_experts_per_tok"],
        "model.num_layers": held["num_hidden_layers"],
        "model.experts_held": held["n_routed_experts"],
        "model.experts_held_start": held["experts_held_start"],
        "model.out_dim": a["out_dim"], "model.dtype": dtype,
        "data.vocab_size": held["vocab_size"],
        "data.page_len": a["page_len"], "data.query_len": a["query_len"]})
    traffic = _load("benchmarks", "traffic", entry["traffic"] + ".json")
    traffic["corpus_pages"] = 4096
    traffic["overrides"] = {"train.batch_size": batch}
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
