"""Falcon-H1 (`falcon_h1`) as an embedding tower: in EVERY block a Mamba-2
mixer and grouped-query attention read the same normed input side by side,
their outputs are scaled and summed into the residual, and a dense SwiGLU
follows; causal, rotary positions, RMSNorm, no biases but the convolution's,
and twelve constant muP multipliers; the hidden state of the last non-pad
token is projected to the page/query vector.

RMSNorm everywhere (eps `rms_norm_eps`, a learned scale); h a block's input
[L, d]; names in backticks are the published config's keys:

Tower   h0 = `embedding_multiplier` * Embed(ids); the blocks; final RMSNorm;
        last non-pad token; `proj` Dense to out_dim (float32).
Block   u = norm(h);
        x = h + `ssm_out_multiplier` * Mamba(u)
              + `attention_out_multiplier`
                * Attn(`attention_in_multiplier` * u);
        y = x + Mlp(norm(x)).
Mamba-2 p = ((`ssm_in_multiplier` * u) W_in) * mup, mup the five
        `ssm_multipliers` over p's segments [z | x | B | C | dt] (`mamba_d_ssm`
        | `mamba_d_ssm` | groups x N | groups x N | heads); then the mixer of
        models/granite_hybrid.py with `mamba_n_groups` groups: head i reads
        group i // (heads / groups) of B and C, and the gated norm divides
        each group's run of columns by its own root mean square
        (`mamba_norm_before_gate` false: gate first). The inner width is
        `mamba_d_ssm` = heads x d_head; `mamba_expand` does not set it.
Attn    a = `attention_in_multiplier` * u; q = a W_q (heads x `head_dim`),
        k = `key_multiplier` * (a W_k), v = a W_v (kv heads; query head i
        reads key/value head i // (heads / kv heads)); rotary over the whole
        head of q and k (half-split pairing, `rope_theta`, positions 0..L-1);
        softmax(q k^T / sqrt(head_dim) + causal + pad) v; W_o.
Mlp     `mlp_multipliers`[1] * ((silu(`mlp_multipliers`[0] * (v W_gate))
        * (v W_up)) W_down)  (models/glm_moe.py:SwiGlu).

The mixer, the scan under it and the attention are the ones the Granite tower
builds (models/granite_hybrid.py, ops/ssd_scan.py): what differs arrives as
sizes. The multipliers are multiplied in the step, in the compute dtype, where
the published code multiplies them; none is folded into a weight. No output
head, so `lm_head_multiplier` is unused.

Device-side scopes (docs/OBSERVABILITY.md): `mamba` and its five children
(each multiplier inside the child it scales), `attn`, `attn.qkv`, `attn.rope`,
`attn.flash`, `attn.out`, `mlp`, `mlp.gate_up`, `mlp.down`. The tower sows no
counters; it asks the serving encode to count its tokens
(`counts_encode_tokens`; infer/bulk_embed.py).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dnn_page_vectors_tpu.models.glm_moe import SwiGlu, last_token, times
from dnn_page_vectors_tpu.models.granite_hybrid import (GqaAttention,
                                                        Mamba2Mixer)
from dnn_page_vectors_tpu.models.transformer import RmsNorm


@dataclasses.dataclass(frozen=True)
class FalconH1Sizes:
    """The published keys of a `falcon_h1` config that shape a block, under
    the names the shared mixer and attention read."""
    model_dim: int
    mlp_dim: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_ssm: int
    mamba_d_state: int
    mamba_n_groups: int
    mamba_d_conv: int
    mamba_chunk_size: int
    embedding_multiplier: float
    ssm_in_multiplier: float
    ssm_multipliers: Tuple[float, ...]        # z, x, B, C, dt
    ssm_out_multiplier: float
    attention_in_multiplier: float
    key_multiplier: float
    attention_out_multiplier: float
    mlp_multipliers: Tuple[float, float]      # gate, down
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head} is not "
                f"mamba_d_ssm = {self.mamba_d_ssm}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_heads} mixer heads do not split "
                             f"into {self.mamba_n_groups} groups")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not share "
                             f"{self.num_kv_heads} key/value heads evenly")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers wants five values (z, x, B, C, "
                             "dt) and mlp_multipliers two (gate, down)")

    @property
    def attention_multiplier(self) -> float:
        """The score scale: this family's is 1 / sqrt(head_dim)."""
        return self.head_dim ** -0.5


class FalconH1Block(nn.Module):
    """One layer: mixer and attention on the same normed input, scaled and
    summed into the residual, then the dense SwiGLU."""
    sizes: FalconH1Sizes
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"

    @nn.compact
    def __call__(self, h, pad_mask):
        c = self.sizes
        norm = lambda name: RmsNorm(dtype=self.dtype, eps=c.norm_eps,
                                    name=name)
        u = norm("ln_mix")(h)
        with jax.named_scope("mamba"):
            mix = Mamba2Mixer(c, dtype=self.dtype, name="mixer")(u)
            with jax.named_scope("mamba.out_proj"):
                mix = times(mix, c.ssm_out_multiplier)
        with jax.named_scope("attn"):
            with jax.named_scope("attn.qkv"):
                a = times(u, c.attention_in_multiplier)
            att = GqaAttention(c, dtype=self.dtype, kind=self.attention_kind,
                               name="attn")(a, pad_mask)
            with jax.named_scope("attn.out"):
                att = times(att, c.attention_out_multiplier)
        x = h + mix + att
        with jax.named_scope("mlp"):
            gate, down = c.mlp_multipliers
            y = SwiGlu(c.mlp_dim, c.model_dim, dtype=self.dtype,
                       gate_multiplier=gate, down_multiplier=down,
                       name="mlp")(norm("ln_ffn")(x))
        return x + y


class FalconH1Encoder(nn.Module):
    vocab_size: int
    sizes: FalconH1Sizes
    num_layers: int
    out_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"
    # no field: what BulkEmbedder asks a tower before it builds the counted
    # encode (`encode.tokens`); this one sows no `moe_stats`
    counts_encode_tokens = True

    @nn.compact
    def __call__(self, ids: jnp.ndarray,
                 deterministic: bool = True) -> jnp.ndarray:
        # ids: [B, L], 0 = pad, pads at the end of the row (the model is
        # causal, so they cannot reach the pooled token). No dropout in the
        # published config: `deterministic` changes nothing.
        c = self.sizes
        pad_mask = ids > 0
        x = nn.Embed(self.vocab_size, c.model_dim, dtype=self.dtype,
                     name="tok_embed")(ids)
        x = times(x, c.embedding_multiplier)
        for i in range(self.num_layers):
            x = FalconH1Block(c, dtype=self.dtype,
                              attention_kind=self.attention_kind,
                              name=f"block{i}")(x, pad_mask)
        x = RmsNorm(dtype=self.dtype, eps=c.norm_eps, name="ln_final")(x)
        pooled = last_token(x.astype(jnp.float32), pad_mask)
        return nn.Dense(self.out_dim, dtype=jnp.float32, name="proj")(pooled)
