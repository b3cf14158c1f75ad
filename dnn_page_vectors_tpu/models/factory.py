"""Encoder-zoo factory: ModelConfig -> TwoTower module (SURVEY.md §3 #5-9)."""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from dnn_page_vectors_tpu.config import Config
from dnn_page_vectors_tpu.models.cdssm import CdssmEncoder
from dnn_page_vectors_tpu.models.falcon_h1 import (FalconH1Encoder,
                                                   FalconH1Sizes)
from dnn_page_vectors_tpu.models.glm_moe import GlmMoeEncoder, GlmSizes
from dnn_page_vectors_tpu.models.granite_hybrid import (GraniteHybridEncoder,
                                                        GraniteSizes)
from dnn_page_vectors_tpu.models.kim_cnn import KimCnnEncoder
from dnn_page_vectors_tpu.models.lstm import LstmEncoder
from dnn_page_vectors_tpu.models.qwen3_next import (Qwen3NextEncoder,
                                                    Qwen3NextSizes)
from dnn_page_vectors_tpu.models.transformer import TransformerEncoder
from dnn_page_vectors_tpu.models.two_tower import TwoTower

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _build_encoder(cfg: Config, vocab_size: int, name: str,
                   mesh: Optional[Any] = None) -> nn.Module:
    m = cfg.model
    dtype = _DTYPES[m.dtype]
    if m.encoder == "cdssm":
        return CdssmEncoder(vocab_size=vocab_size, embed_dim=m.embed_dim,
                            conv_width=m.conv_widths[0],
                            conv_channels=m.conv_channels, out_dim=m.out_dim,
                            dtype=dtype, name=name)
    if m.encoder == "kim_cnn":
        return KimCnnEncoder(vocab_size=vocab_size, embed_dim=m.embed_dim,
                             conv_widths=m.conv_widths,
                             conv_channels=m.conv_channels, out_dim=m.out_dim,
                             dropout=m.dropout, dtype=dtype, name=name)
    if m.encoder == "lstm":
        return LstmEncoder(vocab_size=vocab_size, embed_dim=m.embed_dim,
                           hidden_dim=m.model_dim, num_layers=m.num_layers,
                           out_dim=m.out_dim, dropout=m.dropout,
                           dtype=dtype, name=name)
    if m.encoder in ("bert", "t5"):
        if m.attention not in ("dense", "flash", "ring"):
            raise ValueError(f"unknown attention kind {m.attention!r} "
                             "(want dense | flash | ring)")
        max_len = max(cfg.data.query_len, cfg.data.page_len)
        return TransformerEncoder(vocab_size=vocab_size,
                                  num_layers=m.num_layers,
                                  num_heads=m.num_heads,
                                  model_dim=m.model_dim, mlp_dim=m.mlp_dim,
                                  out_dim=m.out_dim, max_len=max_len,
                                  dropout=m.dropout, variant=m.encoder,
                                  attention_kind=m.attention,
                                  mesh=mesh if m.attention == "ring" else None,
                                  dtype=dtype, name=name)
    if m.encoder == "glm4_moe_lite":
        sizes = GlmSizes(
            num_heads=m.num_heads, model_dim=m.model_dim, mlp_dim=m.mlp_dim,
            moe_mlp_dim=m.moe_intermediate_size, q_lora_rank=m.q_lora_rank,
            kv_lora_rank=m.kv_lora_rank,
            qk_nope_head_dim=m.qk_nope_head_dim,
            qk_rope_head_dim=m.qk_rope_head_dim, v_head_dim=m.v_head_dim,
            n_routed_experts=m.n_routed_experts,
            num_experts_per_tok=m.num_experts_per_tok,
            routed_scaling_factor=m.routed_scaling_factor,
            first_k_dense_replace=m.first_k_dense_replace,
            experts_held=m.experts_held or m.n_routed_experts,
            experts_held_start=m.experts_held_start,
            rope_theta=m.rope_theta, norm_eps=m.rms_norm_eps)
        return GlmMoeEncoder(vocab_size=vocab_size, sizes=sizes,
                             num_layers=m.num_layers, out_dim=m.out_dim,
                             dropout=m.dropout, remat=m.remat_blocks,
                             attention_kind=m.attention, dtype=dtype,
                             name=name)
    if m.encoder == "granitemoehybrid":
        if len(m.layer_types) != m.num_layers:
            raise ValueError(f"model.layer_types names {len(m.layer_types)} "
                             f"layers, model.num_layers is {m.num_layers}")
        sizes = GraniteSizes(
            model_dim=m.model_dim, layer_types=tuple(m.layer_types),
            num_heads=m.num_heads, num_kv_heads=m.num_key_value_heads,
            attention_multiplier=m.attention_multiplier,
            embedding_multiplier=m.embedding_multiplier,
            residual_multiplier=m.residual_multiplier,
            mamba_n_heads=m.mamba_n_heads, mamba_d_head=m.mamba_d_head,
            mamba_d_state=m.mamba_d_state, mamba_expand=m.mamba_expand,
            mamba_d_conv=m.mamba_d_conv, mamba_n_groups=m.mamba_n_groups,
            mamba_chunk_size=m.mamba_chunk_size, moe_mlp_dim=m.mlp_dim,
            shared_mlp_dim=m.shared_intermediate_size,
            n_routed_experts=m.n_routed_experts,
            num_experts_per_tok=m.num_experts_per_tok,
            experts_held=m.experts_held or m.n_routed_experts,
            experts_held_start=m.experts_held_start,
            norm_eps=m.rms_norm_eps)
        return GraniteHybridEncoder(vocab_size=vocab_size, sizes=sizes,
                                    out_dim=m.out_dim,
                                    attention_kind=m.attention, dtype=dtype,
                                    name=name)
    if m.encoder == "falcon_h1":
        sizes = FalconH1Sizes(
            model_dim=m.model_dim, mlp_dim=m.mlp_dim, num_heads=m.num_heads,
            num_kv_heads=m.num_key_value_heads, head_dim=m.head_dim,
            rope_theta=m.rope_theta, mamba_n_heads=m.mamba_n_heads,
            mamba_d_head=m.mamba_d_head, mamba_d_ssm=m.mamba_d_ssm,
            mamba_d_state=m.mamba_d_state, mamba_n_groups=m.mamba_n_groups,
            mamba_d_conv=m.mamba_d_conv,
            mamba_chunk_size=m.mamba_chunk_size,
            embedding_multiplier=m.embedding_multiplier,
            ssm_in_multiplier=m.ssm_in_multiplier,
            ssm_multipliers=tuple(m.ssm_multipliers),
            ssm_out_multiplier=m.ssm_out_multiplier,
            attention_in_multiplier=m.attention_in_multiplier,
            key_multiplier=m.key_multiplier,
            attention_out_multiplier=m.attention_out_multiplier,
            mlp_multipliers=tuple(m.mlp_multipliers),
            norm_eps=m.rms_norm_eps)
        return FalconH1Encoder(vocab_size=vocab_size, sizes=sizes,
                               num_layers=m.num_layers, out_dim=m.out_dim,
                               attention_kind=m.attention, dtype=dtype,
                               name=name)
    if m.encoder == "qwen3_next":
        sizes = Qwen3NextSizes(
            model_dim=m.model_dim, num_heads=m.num_heads,
            num_kv_heads=m.num_key_value_heads, head_dim=m.head_dim,
            partial_rotary_factor=m.partial_rotary_factor,
            rope_theta=m.rope_theta,
            full_attention_interval=m.full_attention_interval,
            linear_num_key_heads=m.linear_num_key_heads,
            linear_num_value_heads=m.linear_num_value_heads,
            linear_key_head_dim=m.linear_key_head_dim,
            linear_value_head_dim=m.linear_value_head_dim,
            linear_conv_kernel_dim=m.linear_conv_kernel_dim,
            moe_mlp_dim=m.moe_intermediate_size,
            shared_mlp_dim=m.shared_intermediate_size,
            n_routed_experts=m.n_routed_experts,
            num_experts_per_tok=m.num_experts_per_tok,
            experts_held=m.experts_held or m.n_routed_experts,
            experts_held_start=m.experts_held_start,
            norm_eps=m.rms_norm_eps)
        return Qwen3NextEncoder(vocab_size=vocab_size, sizes=sizes,
                                num_layers=m.num_layers, out_dim=m.out_dim,
                                remat=m.remat_blocks,
                                attention_kind=m.attention, dtype=dtype,
                                name=name)
    raise ValueError(f"unknown encoder {cfg.model.encoder!r}")


def build_two_tower(cfg: Config, vocab_size: int,
                    mesh: Optional[Any] = None) -> TwoTower:
    """Both towers share one tokenizer vocab (query/page differ only in
    length), so one vocab_size parameterises both. `mesh` is only needed for
    model.attention == 'ring' (sequence parallelism)."""
    query_tower = _build_encoder(cfg, vocab_size, "query_tower", mesh)
    page_tower = _build_encoder(cfg, vocab_size, "page_tower", mesh)
    return TwoTower(query_tower=query_tower, page_tower=page_tower,
                    shared=cfg.model.shared_towers,
                    temperature_init=cfg.train.temperature_init)
