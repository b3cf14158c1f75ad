"""Analytic FLOP counts + per-chip peak tables, for MFU reporting.

`Trainer.train` logs model FLOPs utilisation next to pages/sec/chip so a
throughput number is interpretable — without an analytic FLOPs/step
nobody can tell whether a measured rate is 5% or 50% of the hardware peak.
Counts follow the standard convention: one multiply-accumulate = 2 FLOPs;
embedding gathers, softmax, layernorm and other vector ops are excluded
(they are bandwidth-, not FLOP-, bound and conventionally left out of MFU
math).
"""
from __future__ import annotations

from typing import Optional

from dnn_page_vectors_tpu.config import Config, ModelConfig
from dnn_page_vectors_tpu.ops.gated_delta import CHUNK


def _mixer_flops(m: ModelConfig, L: int) -> float:
    """One Mamba-2 mixer over one sequence: its two projections and the
    chunked recurrence (within-chunk products over the visible pairs once,
    the score tile one a group; the chunks' states; the carried state's part
    of the output)."""
    d = m.model_dim
    H, P, N, G = (m.mamba_n_heads, m.mamba_d_head, m.mamba_d_state,
                  m.mamba_n_groups)
    Q = min(m.mamba_chunk_size, L)
    n = -(-L // Q)
    scan = n * Q * (Q + 1) / 2 * (2 * N * G + 2 * H * P) \
        + 2 * (n - 1) * 2 * Q * H * P * N
    return L * (2 * d * (2 * H * P + 2 * G * N + H) + 2 * H * P * d) + scan


def _gated_delta_flops(m: ModelConfig, L: int) -> float:
    """The chunked gated delta rule (ops/gated_delta.py) over one sequence
    in one layer, every product at its full tile as computed: per chunk of
    Q and value head, K K^T, Q K^T, W, U, the scores' product with V' (Q x Q
    tiles), the 2 (f - 1) products that make T over f levels of doubling
    blocks, and the three products with the carried state (W S, Q S,
    K^T V')."""
    H, K, V = (m.linear_num_value_heads, m.linear_key_head_dim,
               m.linear_value_head_dim)
    Q = min(CHUNK, L)
    factors = max(Q - 1, 1).bit_length()
    per_token = 2 * (3 * Q * K + 2 * Q * V + 2 * (factors - 1) * Q * Q
                     + 3 * K * V)
    return -(-L // Q) * Q * H * per_token


def encoder_flops_per_example(m: ModelConfig, seq_len: int) -> float:
    """Forward-pass matmul FLOPs for ONE sequence through one tower."""
    if m.encoder in ("bert", "t5"):
        d, ff, L = m.model_dim, m.mlp_dim, seq_len
        # per token per layer: QKV+output projections (8 d^2), attention
        # score+apply (4 L d), MLP (bert: two matmuls = 4 d ff; t5 gated
        # GELU: three matmuls = 6 d ff)
        mlp = 6 * d * ff if m.encoder == "t5" else 4 * d * ff
        per_tok_layer = 8 * d * d + 4 * L * d + mlp
        proj = 2 * d * m.out_dim          # pooled vector -> out_dim
        return float(L * m.num_layers * per_tok_layer + proj)
    if m.encoder == "glm4_moe_lite":
        # latent attention's five projections, causal scores counted once,
        # the dense layers' SwiGLU; in expert layers the router, the shared
        # expert and the EXPECTED share of assignments on the experts held
        d, L, H = m.model_dim, seq_len, m.num_heads
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        mla = 2 * (d * m.q_lora_rank + m.q_lora_rank * H * qk
                   + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                   + m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
                   + H * m.v_head_dim * d) \
            + 2 * (L + 1) / 2 * H * (qk + m.v_head_dim)
        held = (m.experts_held or m.n_routed_experts) / m.n_routed_experts
        expert = 6 * d * m.moe_intermediate_size
        moe = 2 * d * m.n_routed_experts \
            + expert * (1 + m.num_experts_per_tok * held)
        dense = min(m.first_k_dense_replace, m.num_layers)
        return float(L * (m.num_layers * mla + dense * 6 * d * m.mlp_dim
                          + (m.num_layers - dense) * moe)
                     + 2 * d * m.out_dim)
    if m.encoder == "granitemoehybrid":
        # per layer the mixer (`_mixer_flops`) or grouped-query attention
        # (causal scores counted once), then the router, the shared expert
        # and the EXPECTED share of assignments held
        d, L = m.model_dim, seq_len
        mamba = _mixer_flops(m, L)
        dh = d // m.num_heads
        attn = L * (4 * d * d + 4 * d * m.num_key_value_heads * dh) \
            + 4 * dh * m.num_heads * L * (L + 1) / 2
        held = (m.experts_held or m.n_routed_experts) / m.n_routed_experts
        moe = L * (2 * d * m.n_routed_experts
                   + 6 * d * m.shared_intermediate_size
                   + m.num_experts_per_tok * held * 6 * d * m.mlp_dim)
        n_mamba = sum(t == "mamba" for t in m.layer_types)
        return float(n_mamba * mamba + (m.num_layers - n_mamba) * attn
                     + m.num_layers * moe + 2 * d * m.out_dim)
    if m.encoder == "falcon_h1":
        # per layer the mixer (`_mixer_flops`), grouped-query attention
        # beside it (causal scores counted once) and the dense SwiGLU
        d, L = m.model_dim, seq_len
        mamba = _mixer_flops(m, L)
        dh = m.head_dim
        attn = L * (4 * d * m.num_heads * dh
                    + 4 * d * m.num_key_value_heads * dh) \
            + 4 * dh * m.num_heads * L * (L + 1) / 2
        return float(m.num_layers * (mamba + attn + L * 6 * d * m.mlp_dim)
                     + 2 * d * m.out_dim)
    if m.encoder == "qwen3_next":
        # per layer Gated DeltaNet (its three projections and the chunked
        # rule, `_gated_delta_flops`) or gated grouped-query attention
        # (causal scores counted once); in every layer the router, the gated
        # shared expert and the EXPECTED share of assignments held
        d, L = m.model_dim, seq_len
        Hk, Hv = m.linear_num_key_heads, m.linear_num_value_heads
        Dk, Dv = m.linear_key_head_dim, m.linear_value_head_dim
        gdn = L * 2 * d * (2 * Hk * Dk + 2 * Hv * Dv + 2 * Hv + Hv * Dv) \
            + _gated_delta_flops(m, L)
        H, G, dh = m.num_heads, m.num_key_value_heads, m.head_dim
        attn = L * 2 * d * (2 * H * dh + 2 * G * dh + H * dh) \
            + 4 * dh * H * L * (L + 1) / 2
        held = (m.experts_held or m.n_routed_experts) / m.n_routed_experts
        moe = L * (2 * d * m.n_routed_experts + 2 * d
                   + 6 * d * m.shared_intermediate_size
                   + m.num_experts_per_tok * held * 6 * d
                   * m.moe_intermediate_size)
        n_attn = m.num_layers // m.full_attention_interval
        return float((m.num_layers - n_attn) * gdn + n_attn * attn
                     + m.num_layers * moe + 2 * d * m.out_dim)
    if m.encoder == "cdssm":
        E, C = m.embed_dim, m.conv_channels
        conv = sum(2 * w * E * C for w in m.conv_widths) * seq_len
        return float(conv + 2 * C * m.out_dim)
    if m.encoder == "kim_cnn":
        E, C = m.embed_dim, m.conv_channels
        conv = sum(2 * w * E * C for w in m.conv_widths) * seq_len
        return float(conv + 2 * len(m.conv_widths) * C * m.out_dim)
    if m.encoder == "lstm":
        # per direction per token: input proj 2*E_in*4H + recurrent 2*H*4H;
        # layer 1 reads the embedding (E), deeper layers read [B, L, 2H]
        H = m.model_dim
        per_dir = 0.0
        e_in = m.embed_dim
        for _ in range(m.num_layers):
            per_dir += 2 * e_in * 4 * H + 2 * H * 4 * H
            e_in = 2 * H
        return float(seq_len * 2 * per_dir + 2 * (2 * H) * m.out_dim)
    raise ValueError(f"no FLOP model for encoder {m.encoder!r}")


def train_flops_per_pair(cfg: Config, batch_size: int,
                         pack: Optional[int] = None) -> float:
    """Matmul FLOPs per (query, page) pair for one optimizer step.

    fwd for both towers (+ hard-negative pages), in-batch logits matmul,
    then the usual 3x multiplier for fwd+bwd (bwd of a matmul costs 2 fwds).

    `pack` (default cfg.train.pack_pages) — sequence packing: the page
    tower runs one [data.page_len] ROW carrying `pack` pages, so the
    per-page page-tower cost is the row cost / pack. This is the row the
    device actually computes (segment masking zeroes scores, it does not
    skip tiles), so MFU stays an honest achieved-FLOPs ratio; the
    packing WIN shows up as pages/sec, and in useful-FLOPs terms by the
    accounting of docs/MFU.md "Packing accounting"."""
    m, d = cfg.model, cfg.data
    H = cfg.train.hard_negatives
    pack = max(1, cfg.train.pack_pages if pack is None else pack)
    # mined negatives ride UNPACKED [B*H, page_len] rows either way
    fwd = (encoder_flops_per_example(m, d.query_len)
           + encoder_flops_per_example(m, d.page_len) / pack
           + H * encoder_flops_per_example(m, d.page_len))
    # logits: q [B, D] @ pages [(1+H) B, D]^T, per pair:
    fwd += 2.0 * batch_size * (1 + H) * m.out_dim
    return 3.0 * fwd


def embed_flops_per_page(cfg: Config) -> float:
    """Matmul FLOPs to embed one page (forward only)."""
    return encoder_flops_per_example(cfg.model, cfg.data.page_len)


# Per-chip peak HBM bandwidth (bytes/s) by device_kind substring.
# (Public figures: v4 1228, v5e 819, v5p 2765, v6e/Trillium 1640 GB/s;
# v2/v3 per-core devices: 350 / 450 GB/s.)
_PEAK_HBM = [
    ("v6", 1640e9),
    ("v5 lite", 819e9),
    ("v5e", 819e9),
    ("v5litepod", 819e9),
    ("v5p", 2765e9),
    ("v4", 1228e9),
    ("v3", 450e9),
    ("v2", 350e9),
]


def _peak_for(device, table, what: str) -> Optional[float]:
    """`table` row for this device's kind; None off-TPU (CPU has no peak
    to compare against). A TPU kind the table does not list is an error,
    never a neighbouring generation's peak."""
    kind = getattr(device, "device_kind", "").lower()
    if "tpu" not in kind and getattr(device, "platform", "") != "tpu":
        return None
    for sub, peak in table:
        if sub in kind:
            return peak
    raise ValueError(
        f"no {what} on record for TPU device_kind "
        f"{getattr(device, 'device_kind', '')!r}; add its published "
        "figure to utils/flops.py")


def device_peak_hbm_bps(device) -> Optional[float]:
    """Per-device peak HBM bandwidth in bytes/s, or None off-TPU."""
    return _peak_for(device, _PEAK_HBM, "peak HBM bandwidth")


# Per-chip peak dense bf16 FLOP/s by `jax.Device.device_kind` substring.
# (Public figures: v4 275, v5e 197, v5p 459, v6e/Trillium 918 TFLOP/s.
# v2/v3 report per-core devices: 23 / 61.5 TFLOP/s per device.)
_PEAK_BF16 = [
    ("v6", 918e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v3", 61.5e12),
    ("v2", 23e12),
]


def device_peak_flops(device) -> Optional[float]:
    """Per-device peak bf16 FLOP/s, or None off-TPU (e.g. CPU)."""
    return _peak_for(device, _PEAK_BF16, "peak bf16 FLOP/s")
