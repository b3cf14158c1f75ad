"""The yardstick of the `qwen3_next` cells: matmul FLOP counts of the
two-tower step with ONE shared Gated DeltaNet / gated-attention /
routed-expert tower, and the chunked gated delta rule's FLOPs and bytes, from
the configuration file's numbers alone (one multiply-accumulate = 2 FLOPs;
gathers, softmax, norms, the convolution, RoPE and the router's top-k left
out; recomputed work not counted; x3 for forward + backward).

Routed experts are counted at the EXPECTED load of the experts held (every
token sends `num_experts_per_tok` assignments over `num_experts`, `held` of
them here). Causal scores are counted once: L (L + 1) / 2 pairs a sequence.
The gated delta rule is counted as its chunk form computes it
(ops/gated_delta.py): per chunk of Q tokens and value head, K K^T, Q K^T, W,
U and the scores' product with V' at their full Q x Q tiles, the
2 (f - 1) products of Q x Q tiles that make T by diagonal blocks that
double (f = ceil(log2 Q) levels, the first without a product), and the
three products with the carried state (W S, Q S, K^T V').
"""
from __future__ import annotations


def shape_of(config: dict) -> dict:
    """The sizes the FLOP model needs, from a `configs/<name>.json` dict."""
    pub, held, a = config["published"], config["held"], config["assumed"]
    return {
        "d": pub["hidden_size"], "heads": pub["num_attention_heads"],
        "kv_heads": pub["num_key_value_heads"], "head_dim": pub["head_dim"],
        "k_heads": pub["linear_num_key_heads"],
        "v_heads": pub["linear_num_value_heads"],
        "k_dim": pub["linear_key_head_dim"],
        "v_dim": pub["linear_value_head_dim"],
        "interval": pub["full_attention_interval"],
        "ff_expert": pub["moe_intermediate_size"],
        "ff_shared": pub["shared_expert_intermediate_size"],
        "experts": pub["num_experts"], "experts_held": held["num_experts"],
        "per_tok": pub["num_experts_per_tok"],
        "layers": held["num_hidden_layers"], "chunk": a["chunk"],
        "out_dim": a["out_dim"], "page_len": a["page_len"],
        "query_len": a["query_len"]}


def attention_layers(s: dict) -> int:
    return s["layers"] // s["interval"]


def gdn_layers(s: dict) -> int:
    return s["layers"] - attention_layers(s)


def held_assignments_per_token(s: dict) -> float:
    return s["per_tok"] * s["experts_held"] / s["experts"]


def _chunk(s: dict, seq_len: int) -> int:
    return min(s["chunk"], seq_len)


def gated_delta_flops_per_token(s: dict, seq_len: int) -> float:
    """Forward FLOPs of the chunked rule a token brings to one layer (all
    value heads; a sequence whose length is a whole number of chunks)."""
    Q, K, V = _chunk(s, seq_len), s["k_dim"], s["v_dim"]
    factors = max(Q - 1, 1).bit_length()
    return s["v_heads"] * 2.0 * (3 * Q * K + 2 * Q * V
                                 + 2 * (factors - 1) * Q * Q + 3 * K * V)


def gated_delta_bytes_per_token(s: dict) -> float:
    """Forward + backward HBM bytes of the rule a token brings to one layer:
    the forward reads q and k (K) and v (V) in bfloat16 and g and beta in
    float32 a value head, and writes o (V) in float32; the backward reads
    all of that again with o's cotangent and writes the inputs' cotangents,
    about twice the forward's traffic."""
    K, V = s["k_dim"], s["v_dim"]
    return 3.0 * s["v_heads"] * (2 * (2 * K + V) + 2 * 4 + 4 * V)


def flash_flops_per_sequence(s: dict, seq_len: int) -> float:
    """Forward FLOPs of causal attention over one sequence in one layer:
    q k^T and p v over L (L + 1) / 2 visible pairs a head."""
    pairs = seq_len * (seq_len + 1) / 2
    return 4.0 * pairs * s["heads"] * s["head_dim"]


def expert_flops_per_token(s: dict) -> float:
    """Forward FLOPs of the grouped products a token brings to one expert
    layer, at the expected load of the experts held."""
    return held_assignments_per_token(s) * 6.0 * s["d"] * s["ff_expert"]


def encoder_flops_per_example(s: dict, seq_len: int) -> float:
    """Forward matmul FLOPs of ONE sequence through the tower."""
    d, L = s["d"], seq_len
    Q = _chunk(s, L)
    padded = -(-L // Q) * Q
    Hk, Hv, K, V = s["k_heads"], s["v_heads"], s["k_dim"], s["v_dim"]
    gdn = L * 2.0 * d * (2 * Hk * K + 2 * Hv * V + 2 * Hv + Hv * V) \
        + padded * gated_delta_flops_per_token(s, L)
    H, G, dh = s["heads"], s["kv_heads"], s["head_dim"]
    attn = L * 2.0 * d * (2 * H * dh + 2 * G * dh + H * dh) \
        + flash_flops_per_sequence(s, L)
    moe = L * (2.0 * d * s["experts"] + 2.0 * d + 6.0 * d * s["ff_shared"]
               + expert_flops_per_token(s))
    return (gdn_layers(s) * gdn + attention_layers(s) * attn
            + s["layers"] * moe + 2.0 * d * s["out_dim"])


def train_flops_per_pair(s: dict, batch_size: int) -> float:
    """Matmul FLOPs per (query, page) pair of one optimizer step."""
    fwd = (encoder_flops_per_example(s, s["query_len"])
           + encoder_flops_per_example(s, s["page_len"])
           + 2.0 * batch_size * s["out_dim"])
    return 3.0 * fwd


def expert_matmul_flops_per_step(s: dict, batch_size: int) -> float:
    """Forward + backward FLOPs of the grouped products of one step (every
    layer, both sides of every pair), expected assignments."""
    tokens = batch_size * (s["query_len"] + s["page_len"])
    return 3.0 * tokens * s["layers"] * expert_flops_per_token(s)


def flash_flops_per_step(s: dict, batch_size: int) -> float:
    """Forward + backward FLOPs of causal flash attention of one step: 2
    forward + 5 backward products of the visible pairs, counted once each
    (flops_glm4_moe_lite.flash_flops_per_step)."""
    fwd = batch_size * attention_layers(s) * (
        flash_flops_per_sequence(s, s["query_len"])
        + flash_flops_per_sequence(s, s["page_len"]))
    return fwd * 3.5


def gated_delta_flops_per_token_fb(s: dict) -> float:
    """Forward + backward FLOPs of the rule a token of a page brings to one
    layer: what `gated_delta_roofline.train` multiplies by the tokens the
    `gdn_stats` counter counts (the query side's chunk is its whole length
    where that is shorter; the cell's queries of 64 are one chunk of 64)."""
    return 3.0 * gated_delta_flops_per_token(s, s["page_len"])
