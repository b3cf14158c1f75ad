"""The yardstick of the `granitemoehybrid` cells: forward matmul FLOP counts
of the tower as the configuration file cuts it, and the operations and bytes
of its two distinctive pieces of work (the state-space scan, the grouped
expert products): the same work whatever implements it. One
multiply-accumulate = 2 FLOPs; gathers, softmax, norms, the convolution and
the gates are left out. Everything takes plain numbers from the benchmark's
config file, nothing from the program.
"""
from __future__ import annotations


def shape_of(config: dict) -> dict:
    """The sizes the counts need, from a `configs/<name>.json` dict."""
    pub, held, a = config["published"], config["held"], config["assumed"]
    return {"d": pub["hidden_size"], "layer_types": list(held["layer_types"]),
            "heads": pub["num_attention_heads"],
            "kv_heads": pub["num_key_value_heads"],
            "m_heads": pub["mamba_n_heads"], "m_head": pub["mamba_d_head"],
            "state": pub["mamba_d_state"], "chunk": pub["mamba_chunk_size"],
            "expert": pub["intermediate_size"],
            "shared": pub["shared_intermediate_size"],
            "experts": pub["num_local_experts"],
            "held": held["num_local_experts"],
            "top_k": pub["num_experts_per_tok"], "out_dim": a["out_dim"],
            "query_len": a["query_len"], "page_len": a["page_len"]}


def scan_flops_per_query(s: dict, seq_len: int) -> float:
    """One Mamba-2 layer's recurrence over one sequence, in chunks: the
    within-chunk products over the visible pairs once (C_i . B_j, then the
    decayed sum into every head's Y_i), each chunk's state but the last's,
    and the carried state's part of every chunk's output but the first's."""
    Q = min(s["chunk"], seq_len)
    n = -(-seq_len // Q)
    H, P, N = s["m_heads"], s["m_head"], s["state"]
    pairs = n * Q * (Q + 1) / 2
    return float(pairs * (2 * N + 2 * H * P)
                 + 2 * (n - 1) * 2 * Q * H * P * N)


def scan_bytes_per_query(s: dict, seq_len: int) -> float:
    """Reads of X, B, C (2 bytes an element) and delta (float32), write of
    Y (float32), one Mamba-2 layer, one sequence."""
    H, P, N = s["m_heads"], s["m_head"], s["state"]
    return float(seq_len * (2 * H * P + 2 * 2 * N + 4 * H + 4 * H * P))


def _mamba_layer(s: dict, L: int) -> float:
    d, inner = s["d"], s["m_heads"] * s["m_head"]
    proj = 2 * d * (2 * inner + 2 * s["state"] + s["m_heads"]) \
        + 2 * inner * d
    return L * proj + scan_flops_per_query(s, L)


def _attention_layer(s: dict, L: int) -> float:
    d, dh = s["d"], s["d"] // s["heads"]
    proj = 2 * 2 * d * d + 2 * 2 * d * s["kv_heads"] * dh
    return L * proj + 2 * 2 * dh * s["heads"] * L * (L + 1) / 2


def expert_flops_per_assignment(s: dict) -> float:
    """The three grouped products of one token-assignment."""
    return 6.0 * s["d"] * s["expert"]


def expert_kernel_bytes_per_call(s: dict) -> float:
    """The held experts' three stacked kernels of one layer (2 bytes an
    element): what one call of the layer has to read whatever it routes."""
    return 2.0 * s["held"] * 3 * s["d"] * s["expert"]


def _moe_layer(s: dict, L: int) -> float:
    d = s["d"]
    routed = s["top_k"] * s["held"] / s["experts"]      # expected, a token
    return L * (2 * d * s["experts"] + 6 * d * s["shared"]
                + routed * expert_flops_per_assignment(s))


def encoder_flops_per_example(s: dict, seq_len: int) -> float:
    """Forward FLOPs of one sequence through the tower as held."""
    total = 2.0 * s["d"] * s["out_dim"]
    for kind in s["layer_types"]:
        mix = _mamba_layer if kind == "mamba" else _attention_layer
        total += mix(s, seq_len) + _moe_layer(s, seq_len)
    return float(total)


def serve_flops_per_query(s: dict, store_rows: int) -> float:
    """Query-tower forward plus the exact scan's 2 * rows * dim."""
    return encoder_flops_per_example(s, s["query_len"]) \
        + 2.0 * store_rows * s["out_dim"]


def mamba_layers(s: dict) -> int:
    return sum(k == "mamba" for k in s["layer_types"])
