"""The yardstick of the `glm4_moe_lite` cells: matmul FLOP counts of the
two-tower step with ONE shared latent-attention / routed-expert tower, from
the configuration file's numbers alone (one multiply-accumulate = 2 FLOPs;
gathers, softmax, norms, RoPE and the router's top-k left out; recomputed
work not counted; x3 for forward + backward).

Routed experts are counted at the EXPECTED load of the experts held: every
token sends `num_experts_per_tok` assignments over `n_routed_experts`, of
which `held` are here, so a token brings `k * held / published` expert
SwiGLUs on average. Causal scores are counted once: a query at position i
sees i + 1 keys, L (L + 1) / 2 pairs a sequence.
"""
from __future__ import annotations


def shape_of(config: dict) -> dict:
    """The sizes the FLOP model needs, from a `configs/<name>.json` dict."""
    pub, held, a = config["published"], config["held"], config["assumed"]
    return {
        "d": pub["hidden_size"], "heads": pub["num_attention_heads"],
        "q_rank": pub["q_lora_rank"], "kv_rank": pub["kv_lora_rank"],
        "nope": pub["qk_nope_head_dim"], "rope": pub["qk_rope_head_dim"],
        "v": pub["v_head_dim"], "ff_dense": pub["intermediate_size"],
        "ff_expert": pub["moe_intermediate_size"],
        "experts": pub["n_routed_experts"],
        "experts_held": held["n_routed_experts"],
        "per_tok": pub["num_experts_per_tok"],
        "shared": pub["n_shared_experts"],
        "layers": held["num_hidden_layers"],
        "dense_layers": min(pub["first_k_dense_replace"],
                            held["num_hidden_layers"]),
        "out_dim": a["out_dim"], "page_len": a["page_len"],
        "query_len": a["query_len"]}


def held_assignments_per_token(s: dict) -> float:
    return s["per_tok"] * s["experts_held"] / s["experts"]


def _mla_proj_per_token(s: dict) -> float:
    d, H = s["d"], s["heads"]
    return 2.0 * (d * s["q_rank"] + s["q_rank"] * H * (s["nope"] + s["rope"])
                  + d * (s["kv_rank"] + s["rope"])
                  + s["kv_rank"] * H * (s["nope"] + s["v"])
                  + H * s["v"] * d)


def flash_flops_per_sequence(s: dict, seq_len: int) -> float:
    """Forward FLOPs of causal attention over one sequence in one layer:
    q k^T and p v over L (L + 1) / 2 visible pairs a head."""
    pairs = seq_len * (seq_len + 1) / 2
    return 2.0 * pairs * s["heads"] * (s["nope"] + s["rope"] + s["v"])


def expert_flops_per_token(s: dict) -> float:
    """Forward FLOPs of the grouped products a token brings to one expert
    layer, at the expected load of the experts held."""
    return held_assignments_per_token(s) * 6.0 * s["d"] * s["ff_expert"]


def encoder_flops_per_example(s: dict, seq_len: int) -> float:
    """Forward matmul FLOPs of ONE sequence through the tower."""
    d, L = s["d"], seq_len
    expert_layers = s["layers"] - s["dense_layers"]
    per_tok = (s["layers"] * _mla_proj_per_token(s)
               + s["dense_layers"] * 6.0 * d * s["ff_dense"]
               + expert_layers * (2.0 * d * s["experts"]
                                  + s["shared"] * 6.0 * d * s["ff_expert"]
                                  + expert_flops_per_token(s)))
    return (L * per_tok + s["layers"] * flash_flops_per_sequence(s, L)
            + 2.0 * d * s["out_dim"])


def train_flops_per_pair(s: dict, batch_size: int) -> float:
    """Matmul FLOPs per (query, page) pair of one optimizer step."""
    fwd = (encoder_flops_per_example(s, s["query_len"])
           + encoder_flops_per_example(s, s["page_len"])
           + 2.0 * batch_size * s["out_dim"])
    return 3.0 * fwd


def expert_matmul_flops_per_step(s: dict, batch_size: int) -> float:
    """Forward + backward FLOPs of the grouped products of one step (all
    expert layers, both sides of every pair), expected assignments."""
    tokens = batch_size * (s["query_len"] + s["page_len"])
    return 3.0 * tokens * (s["layers"] - s["dense_layers"]) \
        * expert_flops_per_token(s)


def flash_flops_per_step(s: dict, batch_size: int) -> float:
    """Forward + backward FLOPs of causal flash attention of one step: the
    forward's two products, the backward's five (scores and p v recomputed
    in part: dq needs s, dp, ds k; dk / dv need s, dp, p^T g, ds^T q; the
    recomputed s and dp are counted once each, so 2 forward + 5 backward
    products of L (L + 1) / 2 pairs), counted once each."""
    fwd = batch_size * s["layers"] * (
        flash_flops_per_sequence(s, s["query_len"])
        + flash_flops_per_sequence(s, s["page_len"]))
    return fwd * 3.5
