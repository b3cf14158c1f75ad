"""The yardstick of the `falcon_h1` cells: forward matmul FLOP counts of the
tower as the configuration file cuts it, and the operations and bytes of its
distinctive pieces of work (the state-space scan with groups, the causal flash
forward under grouped-query heads, the dense SwiGLU): the same work whatever
implements it. One multiply-accumulate = 2 FLOPs; gathers, softmax, norms,
rotary, the convolution, the gates and the multipliers are left out.
Everything takes plain numbers from the benchmark's config file, nothing from
the program.
"""
from __future__ import annotations


def shape_of(config: dict) -> dict:
    """The sizes the counts need, from a `configs/<name>.json` dict."""
    pub, held, a = config["published"], config["held"], config["assumed"]
    return {"d": pub["hidden_size"], "layers": held["num_hidden_layers"],
            "heads": pub["num_attention_heads"],
            "kv_heads": pub["num_key_value_heads"],
            "head": pub["head_dim"], "m_heads": pub["mamba_n_heads"],
            "m_head": pub["mamba_d_head"], "inner": pub["mamba_d_ssm"],
            "state": pub["mamba_d_state"], "groups": pub["mamba_n_groups"],
            "chunk": pub["mamba_chunk_size"], "conv": pub["mamba_d_conv"],
            "mlp": pub["intermediate_size"], "vocab": held["vocab_size"],
            "out_dim": a["out_dim"], "query_len": a["query_len"],
            "page_len": a["page_len"]}


def scan_flops_per_query(s: dict, seq_len: int) -> float:
    """One mixer's recurrence over one sequence, in chunks: the within-chunk
    products over the visible pairs once (C_i . B_j for each GROUP, then the
    decayed sum into every head's Y_i), each chunk's state but the last's,
    and the carried state's part of every chunk's output but the first's."""
    Q = min(s["chunk"], seq_len)
    n = -(-seq_len // Q)
    H, P, N, G = s["m_heads"], s["m_head"], s["state"], s["groups"]
    pairs = n * Q * (Q + 1) / 2
    return float(pairs * (2 * N * G + 2 * H * P)
                 + 2 * (n - 1) * 2 * Q * H * P * N)


def scan_bytes_per_query(s: dict, seq_len: int) -> float:
    """Reads of X, B, C (2 bytes an element; B and C by group) and delta
    (float32), write of Y (float32), one mixer, one sequence."""
    H, P, N, G = s["m_heads"], s["m_head"], s["state"], s["groups"]
    return float(seq_len * (2 * H * P + 2 * 2 * G * N + 4 * H + 4 * H * P))


def flash_flops_per_layer(s: dict, seq_len: int) -> float:
    """The causal forward of one layer, one sequence: q k^T and p v over the
    visible pairs once, every query head."""
    return float(2 * 2 * s["head"] * s["heads"] * seq_len * (seq_len + 1) / 2)


def flash_bytes_per_layer(s: dict, seq_len: int) -> float:
    """q read and o written for every query head, k and v read for every
    key/value head ONCE (2 bytes an element), one layer, one sequence."""
    return float(2 * seq_len * s["head"] * (2 * s["heads"]
                                            + 2 * s["kv_heads"]))


def mlp_flops_per_token(s: dict) -> float:
    """The SwiGLU's three products of one token, one layer."""
    return 6.0 * s["d"] * s["mlp"]


def projection_flops_per_token(s: dict) -> float:
    """One layer's dense products of one token: the mixer's two, the
    attention's four, the SwiGLU's three."""
    d = s["d"]
    mixer = 2 * d * (2 * s["inner"] + 2 * s["groups"] * s["state"]
                     + s["m_heads"]) + 2 * s["inner"] * d
    attn = 2 * 2 * d * s["heads"] * s["head"] \
        + 2 * 2 * d * s["kv_heads"] * s["head"]
    return float(mixer + attn + mlp_flops_per_token(s))


def encoder_flops_per_example(s: dict, seq_len: int) -> float:
    """Forward FLOPs of one sequence through the tower as held."""
    layer = seq_len * projection_flops_per_token(s) \
        + scan_flops_per_query(s, seq_len) + flash_flops_per_layer(s, seq_len)
    return float(s["layers"] * layer + 2.0 * s["d"] * s["out_dim"])


def serve_flops_per_query(s: dict, store_rows: int) -> float:
    """Query-tower forward plus the exact scan's 2 * rows * dim."""
    return encoder_flops_per_example(s, s["query_len"]) \
        + 2.0 * store_rows * s["out_dim"]


def parameters_per_layer(s: dict) -> int:
    """What one layer holds: the mixer (projections, convolution with bias,
    A_log, D, dt_bias, the gated norm's scale), the attention's four
    matrices, the SwiGLU's three, two norms."""
    d, inner, H = s["d"], s["inner"], s["m_heads"]
    conv_dim = inner + 2 * s["groups"] * s["state"]
    mixer = d * (inner + conv_dim + H) + inner * d \
        + conv_dim * (s["conv"] + 1) + 3 * H + inner
    attn = 2 * d * s["heads"] * s["head"] + 2 * d * s["kv_heads"] * s["head"]
    return int(mixer + attn + 3 * d * s["mlp"] + 2 * d)


def parameters_held(s: dict) -> int:
    """The held layers, the embedding's held rows, the final norm and the
    repo's `proj` (with its bias)."""
    return int(s["layers"] * parameters_per_layer(s) + s["vocab"] * s["d"]
               + s["d"] + s["d"] * s["out_dim"] + s["out_dim"])
