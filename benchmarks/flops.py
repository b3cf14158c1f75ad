"""The benchmark's own yardstick: matmul FLOP counts of the two-tower
transformer jobs, store bytes of the exact scan, and the table of device
peaks. The FLOP arithmetic is a copy of `dnn_page_vectors_tpu/utils/flops.py`
(one multiply-accumulate = 2 FLOPs; gathers, softmax and norms left out;
recomputed work not counted), kept here so that no later PR can move it.
Everything takes plain numbers from the benchmark's config files, nothing
from the program.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def shape_of(config: dict) -> dict:
    """The sizes the FLOP model needs, from a `configs/<name>.json` dict."""
    pub, assumed = config["published"], config["assumed"]
    if config["variant"] == "t5":
        d, ff = pub["d_model"], pub["d_ff"]
        layers = pub["num_layers"]
    else:
        d, ff = pub["hidden_size"], pub["intermediate_size"]
        layers = pub["num_hidden_layers"]
    return {"variant": config["variant"], "d": d, "ff": ff, "layers": layers,
            "out_dim": assumed["out_dim"], "page_len": assumed["page_len"],
            "query_len": assumed["query_len"]}


def encoder_flops_per_example(shape: dict, seq_len: int) -> float:
    """Forward matmul FLOPs of ONE sequence through one tower: per token per
    layer the q, k, v and output projections (8 d^2), attention scores and
    apply (4 L d), the MLP (bert: 4 d ff; t5 gated GELU: 6 d ff); then the
    pooled projection."""
    d, ff, L = shape["d"], shape["ff"], seq_len
    mlp = 6 * d * ff if shape["variant"] == "t5" else 4 * d * ff
    per_tok_layer = 8 * d * d + 4 * L * d + mlp
    return float(L * shape["layers"] * per_tok_layer
                 + 2 * d * shape["out_dim"])


def train_flops_per_pair(shape: dict, batch_size: int) -> float:
    """Matmul FLOPs per (query, page) pair of one optimizer step: forward of
    both towers plus the in-batch logits row, times 3 for forward+backward."""
    fwd = (encoder_flops_per_example(shape, shape["query_len"])
           + encoder_flops_per_example(shape, shape["page_len"])
           + 2.0 * batch_size * shape["out_dim"])
    return 3.0 * fwd


def embed_flops_per_page(shape: dict) -> float:
    return encoder_flops_per_example(shape, shape["page_len"])


def serve_flops_per_query(shape: dict, store_rows: int) -> float:
    """Query-tower forward plus the exact scan's 2 * rows * dim."""
    return (encoder_flops_per_example(shape, shape["query_len"])
            + 2.0 * store_rows * shape["out_dim"])


def scan_bytes_per_dispatch(rows: int, dim: int, itemsize: int = 2) -> float:
    """Bytes the exact scan must read from HBM for one dispatch over `rows`
    stored rows (the query block and the k winners are noise beside it)."""
    return float(rows) * dim * itemsize


def load_peaks(path: str | None = None) -> dict:
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        return json.load(f)


def peaks_for(device_kind: str, table: dict | None = None) -> dict:
    """The row of `peaks.json` for exactly this `device_kind`. A kind that
    is not in the table is an error, never a neighbouring chip's peak."""
    table = load_peaks() if table is None else table
    row = table.get(device_kind)
    if not isinstance(row, dict):
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add its "
            "published figures to benchmarks/peaks.json")
    return row
