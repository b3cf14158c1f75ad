"""A shrunken copy of the `qwen3_next` cell's files in a directory of the
caller's, for the CPU rehearsals in the tests (`tiny_moe.py` does the same
for the GLM cell): same code paths, toy widths. Nothing here is used by a
benchmark run."""
from __future__ import annotations

import json
import os

from .tiny import _load

# hidden 64; 4 + 2 attention heads of 16 (rotary on 4); linear attention 2
# key heads of 16 and 4 value heads of 8, conv 4; 8 experts of width 16 (4
# held, from the third), 3 a token, shared 16; one period of 4 layers
PUBLISHED = {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 16,
             "linear_num_key_heads": 2, "linear_num_value_heads": 4,
             "linear_key_head_dim": 16, "linear_value_head_dim": 8,
             "moe_intermediate_size": 16,
             "shared_expert_intermediate_size": 16, "intermediate_size": 48,
             "num_experts": 8, "num_experts_per_tok": 3,
             "num_hidden_layers": 4, "vocab_size": 512}
HELD = {"num_hidden_layers": 4, "num_experts": 4, "experts_held_start": 2,
        "vocab_size": 512}
ASSUMED = {"query_len": 8, "page_len": 136, "out_dim": 32, "chunk": 64}
LIMITS = {"loss1": 1e-3, "loss2": 1e-3, "loss3": 1e-3, "grad_norm": 1e-3,
          "change_norm": 1e-3}
_FIELDS = {"hidden_size": "model_dim", "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_key_value_heads",
           "head_dim": "head_dim",
           "linear_num_key_heads": "linear_num_key_heads",
           "linear_num_value_heads": "linear_num_value_heads",
           "linear_key_head_dim": "linear_key_head_dim",
           "linear_value_head_dim": "linear_value_head_dim",
           "moe_intermediate_size": "moe_intermediate_size",
           "shared_expert_intermediate_size": "shared_intermediate_size",
           "intermediate_size": "mlp_dim", "num_experts": "n_routed_experts",
           "num_experts_per_tok": "num_experts_per_tok"}


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
    ov = {f"model.{f}": pub[k] for k, f in _FIELDS.items()}
    ov.update({"model.num_layers": held["num_hidden_layers"],
               "model.experts_held": held["num_experts"],
               "model.experts_held_start": held["experts_held_start"],
               "model.out_dim": a["out_dim"], "model.dtype": dtype,
               "data.vocab_size": held["vocab_size"],
               "data.page_len": a["page_len"],
               "data.query_len": a["query_len"]})
    config["program"]["overrides"].update(ov)
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
