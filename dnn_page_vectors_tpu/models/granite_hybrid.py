"""Granite-4.0-H (`granitemoehybrid`) as an embedding tower: Mamba-2 mixers
with an attention layer where `layer_types` says so, and in EVERY layer a
routed-expert FFN with one shared expert; causal, no positional embedding,
RMSNorm, no biases but the convolution's; the hidden state of the last
non-pad token is projected to the page/query vector.

RMSNorm everywhere (eps `rms_norm_eps`, a learned scale); h a block's input
[L, d]; m = `residual_multiplier`:

Tower   h0 = embedding_multiplier * Embed(ids); the blocks by `layer_types`;
        final RMSNorm; last non-pad token; `proj` Dense to out_dim (float32).
Block   x = h + m * Mix(norm(h)), Mix the mixer or attention by the layer's
        type;  y = x + m * (Routed(u) + Shared(u)), u = norm(x).
Mamba-2 [z | xBC | dt] = u W_in  (d_inner | d_inner + 2 N | heads);
        xBC = silu(conv1d_causal(xBC; w[d_conv, .], b)), depthwise, zeros on
        the left; xBC -> X (heads x d_head), B (N), C (N): one group, shared
        by all heads; delta = softplus(dt + dt_bias) per head, A = -exp(A_log);
        per head S_t = exp(delta_t A) S_{t-1} + delta_t X_t B_t^T, S_0 = 0,
        Y_t = S_t C_t + D X_t  (ops/ssd_scan.py computes it in chunks of
        `mamba_chunk_size`); g = norm_{d_inner}(Y * silu(z)); out = g W_out.
Attn    q = u W_q (heads x head_dim), k, v = u W_k, u W_v (kv heads; query
        head i reads key/value head i // (heads / kv heads)); no rotary, no
        bias; softmax(q k^T * attention_multiplier + causal + pad) v; W_o.
Routed  models/glm_moe.py:RoutedExperts with `router="softmax_topk"`: the k
        largest of the float32 logits, a softmax over those k alone (held
        here or not), no bias on selection, no scaling, nothing dropped; the
        held experts' part of the sum, what absent experts would add left
        out. Shared: the same SwiGLU at `shared_intermediate_size`.

The causal flash kernels scale scores by 1/sqrt(head_dim), so q is scaled by
attention_multiplier * sqrt(head_dim) before them (not at all where that is
1); the key/value heads are repeated to the query heads' count (an index map
in the kernel would save two small copies).

`Mamba2Mixer` and `GqaAttention` are the one mixer and the one attention of
BOTH hybrid towers (models/falcon_h1.py builds them too). What differs
arrives as sizes, and a size at its default adds no operation, so this tower
lowers to the program it had before the other came: the mixer takes
`mamba_n_groups` (B and C [groups x N]; head h reads group h // (heads /
groups); the gated norm is by group), an inner width of heads x d_head
whatever `mamba_expand` says, `ssm_in_multiplier` on its input and
`ssm_multipliers` over the projection's segments [z | x | B | C | dt]; the
attention takes `head_dim` (0: hidden / heads), `rope_theta` (0: no
positions; else rotary over the whole head, half-split pairing),
`key_multiplier` on k, and the score scale `attention_multiplier`.

Device-side scopes (docs/OBSERVABILITY.md): `mamba`, `mamba.in_proj`,
`mamba.conv`, `mamba.ssd`, `mamba.gate_norm`, `mamba.out_proj`, `attn`,
`attn.qkv`, `attn.rope` (where there is rotary), `attn.flash`, `attn.out`,
and the expert layer's `moe`, `moe.*`. Counters are sown into
`moe_stats` as the GLM tower sows them (one entry per layer, stacked).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dnn_page_vectors_tpu.models.glm_moe import (STATS, _FLASH_BLOCK,
                                                 RoutedExperts, last_token,
                                                 rope, times)
from dnn_page_vectors_tpu.models.transformer import RmsNorm
from dnn_page_vectors_tpu.ops.ssd_scan import ssd_scan

LAYER_TYPES = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class GraniteSizes:
    """The published keys of a `granitemoehybrid` config that shape a block,
    and the share of the routed experts held here."""
    model_dim: int
    layer_types: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_expand: int
    mamba_d_conv: int
    mamba_chunk_size: int
    moe_mlp_dim: int              # every routed expert's width
    shared_mlp_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    experts_held: int
    experts_held_start: int = 0
    norm_eps: float = 1e-5
    # what the shared mixer and attention read besides, at this family's
    # values (one group, no multipliers, hidden / heads, no positions)
    mamba_n_groups: int = 1
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = ()
    head_dim: int = 0
    rope_theta: float = 0.0
    key_multiplier: float = 1.0

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in LAYER_TYPES]
        if bad or not self.layer_types:
            raise ValueError(f"layer_types wants a non-empty sequence of "
                             f"{LAYER_TYPES}, got {self.layer_types!r}")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.model_dim:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head} is not "
                f"mamba_expand x hidden = {self.mamba_expand * self.model_dim}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not share "
                             f"{self.num_kv_heads} key/value heads evenly")


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a time step drawn log-uniform in
    [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv(x: jnp.ndarray, w: jnp.ndarray,
                b: Optional[jnp.ndarray] = None):
    """Depthwise causal convolution along axis 1 of [B, L, C] with taps
    w [K, C] (tap K-1 is the token itself), zeros on the left, bias b [C]
    (None: no bias)."""
    K, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + L] * w[i].astype(x.dtype) for i in range(K))
    return y if b is None else y + b.astype(x.dtype)


def segment_multipliers(multipliers, widths) -> np.ndarray:
    """The constant vector that scales the mixer's projection: one of the
    five `multipliers` over each of the segments [z | x | B | C | dt] of
    `widths`, in that order."""
    if len(multipliers) != len(widths):
        raise ValueError(f"{len(multipliers)} multipliers for the "
                         f"{len(widths)} segments of the mixer's projection")
    return np.repeat(np.asarray(multipliers, np.float32), widths)


class Mamba2Mixer(nn.Module):
    sizes: Any                    # GraniteSizes | models/falcon_h1.py's
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        c = self.sizes
        B, L, d = u.shape
        H, P, N, G = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                      c.mamba_n_groups)
        inner, conv_dim = H * P, H * P + 2 * G * N
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         name=name)
        with jax.named_scope("mamba.in_proj"):
            zxd = dense(inner + conv_dim + H, "in_proj")(
                times(u, c.ssm_in_multiplier))
            if c.ssm_multipliers:
                zxd = zxd * jnp.asarray(segment_multipliers(
                    c.ssm_multipliers, (inner, inner, G * N, G * N, H)),
                    zxd.dtype)
        z, xbc, dt = (zxd[..., :inner], zxd[..., inner:inner + conv_dim],
                      zxd[..., inner + conv_dim:])
        with jax.named_scope("mamba.conv"):
            w = self.param("conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (c.mamba_d_conv, conv_dim))
            b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
            xbc = nn.silu(causal_conv(xbc, w, b))
        x = xbc[..., :inner].reshape(B, L, H, P)
        a_log = self.param("A_log", _a_log_init, (H,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,))
        skip = self.param("D", nn.initializers.ones, (H,))
        # B and C by group; one group's stay [B, L, N]
        grouped = lambda t: t if G == 1 else t.reshape(B, L, G, N)
        with jax.named_scope("mamba.ssd"):
            delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y = ssd_scan(x, delta, -jnp.exp(a_log),
                         grouped(xbc[..., inner:inner + G * N]),
                         grouped(xbc[..., inner + G * N:]),
                         c.mamba_chunk_size)
            y = y + skip[:, None] * x.astype(jnp.float32)
        with jax.named_scope("mamba.gate_norm"):
            g = y.reshape(B, L, inner) * nn.silu(z.astype(jnp.float32))
            g = RmsNorm(dtype=self.dtype, eps=c.norm_eps, groups=G,
                        name="norm")(g)
        with jax.named_scope("mamba.out_proj"):
            return dense(d, "out_proj")(g)


class GqaAttention(nn.Module):
    """Causal grouped-query attention at a stated score scale, with rotary
    positions or none. Three options, each off by default (and then no
    operation of the program): `qk_norm`, a zero-centred RMSNorm over each
    head of q and of k (`q_norm`, `k_norm`, eps the sizes' `norm_eps`)
    before the rotary; `rotary_dim`, rotary over the first that many dims of
    a head only (0: the whole head); `output_gate`, `wq` twice as wide, [q |
    gate] a head, and the heads' output times sigmoid(gate) before `wo`
    (models/qwen3_next.py)."""
    sizes: Any                    # GraniteSizes | falcon_h1's | qwen3_next's
    dtype: jnp.dtype = jnp.bfloat16
    kind: str = "flash"           # flash | dense
    qk_norm: bool = False
    rotary_dim: int = 0
    output_gate: bool = False

    @nn.compact
    def __call__(self, u: jnp.ndarray, pad_mask: jnp.ndarray) -> jnp.ndarray:
        c = self.sizes
        B, L, d = u.shape
        H, G = c.num_heads, c.num_kv_heads
        dh = c.head_dim or d // H
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         name=name)
        with jax.named_scope("attn.qkv"):
            if self.output_gate:
                q = dense(H * 2 * dh, "wq")(u).reshape(B, L, H, 2 * dh)
                q, gate = q[..., :dh], q[..., dh:]
            else:
                q = dense(H * dh, "wq")(u).reshape(B, L, H, dh)
            k = times(dense(G * dh, "wk")(u), c.key_multiplier).reshape(
                B, L, G, dh)
            v = dense(G * dh, "wv")(u).reshape(B, L, G, dh)
            if self.qk_norm:
                norm = lambda name: RmsNorm(dtype=self.dtype, eps=c.norm_eps,
                                            zero_centred=True, name=name)
                q, k = norm("q_norm")(q), norm("k_norm")(k)
        if c.rope_theta:
            with jax.named_scope("attn.rope"):
                r = self.rotary_dim or dh
                turn = lambda t: rope(t, c.rope_theta) if r == dh else \
                    jnp.concatenate([rope(t[..., :r], c.rope_theta),
                                     t[..., r:]], axis=-1)
                q, k = turn(q), turn(k)
        k, v = (jnp.repeat(t, H // G, axis=2) for t in (k, v))
        bhld = lambda t: t.transpose(0, 2, 1, 3)
        if self.kind == "flash":
            from dnn_page_vectors_tpu.ops.flash_attention import (
                flash_attention)
            to_kernels = c.attention_multiplier * np.sqrt(dh)
            q = times(q, 1.0 if np.isclose(to_kernels, 1.0) else to_kernels)
            with jax.named_scope("attn.flash"):
                out = flash_attention(bhld(q), bhld(k), bhld(v), pad_mask,
                                      block_q=_FLASH_BLOCK,
                                      block_kv=_FLASH_BLOCK, causal=True)
            out = bhld(out.astype(self.dtype))
        elif self.kind == "dense":
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
                * c.attention_multiplier
            pos = jnp.arange(L)
            allowed = (pos[None, :] <= pos[:, None])[None, None] \
                & pad_mask[:, None, None, :]
            s = jnp.where(allowed, s, jnp.asarray(-1e9, jnp.float32))
            out = jnp.einsum("bhqk,bkhd->bqhd",
                             nn.softmax(s, axis=-1).astype(self.dtype), v)
        else:
            raise ValueError(f"unknown attention kind {self.kind!r} for the "
                             "hybrid tower (want dense | flash)")
        with jax.named_scope("attn.out"):
            out = out.reshape(B, L, H * dh)
            if self.output_gate:
                out = out * jax.nn.sigmoid(gate.reshape(B, L, H * dh))
            return dense(d, "wo")(out)


class HybridBlock(nn.Module):
    """One layer: the mixer or attention, then the routed + shared FFN, both
    residuals scaled; returns it with the expert layer's counters."""
    sizes: GraniteSizes
    kind: str                     # mamba | attention
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"

    @nn.compact
    def __call__(self, h, pad_mask):
        c = self.sizes
        norm = lambda name: RmsNorm(dtype=self.dtype, eps=c.norm_eps,
                                    name=name)
        m = jnp.asarray(c.residual_multiplier, self.dtype)
        u = norm("ln_mix")(h)
        if self.kind == "mamba":
            with jax.named_scope("mamba"):
                mix = Mamba2Mixer(c, dtype=self.dtype, name="mixer")(u)
        else:
            with jax.named_scope("attn"):
                mix = GqaAttention(c, dtype=self.dtype,
                                   kind=self.attention_kind,
                                   name="attn")(u, pad_mask)
        x = h + m * mix
        u = norm("ln_ffn")(x)
        with jax.named_scope("moe"):
            ffn, stats = RoutedExperts(
                c.model_dim, c.moe_mlp_dim, c.n_routed_experts,
                c.num_experts_per_tok, 1.0, c.experts_held,
                c.experts_held_start, dtype=self.dtype,
                router="softmax_topk", shared_dim=c.shared_mlp_dim,
                name="moe")(u)
        return x + m * ffn, stats


class GraniteHybridEncoder(nn.Module):
    vocab_size: int
    sizes: GraniteSizes
    out_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"
    sows_moe_stats = True         # as GlmMoeEncoder's

    @nn.compact
    def __call__(self, ids: jnp.ndarray,
                 deterministic: bool = True) -> jnp.ndarray:
        # ids: [B, L], 0 = pad, pads at the end of the row (the model is
        # causal, so they cannot reach the pooled token). Dropout is 0.0 in
        # the published config: `deterministic` changes nothing.
        c = self.sizes
        pad_mask = ids > 0
        x = nn.Embed(self.vocab_size, c.model_dim, dtype=self.dtype,
                     name="tok_embed")(ids)
        x = x * jnp.asarray(c.embedding_multiplier, self.dtype)
        stats = []
        for i, kind in enumerate(c.layer_types):
            x, st = HybridBlock(c, kind, dtype=self.dtype,
                                attention_kind=self.attention_kind,
                                name=f"block{i}")(x, pad_mask)
            stats.append(st)
        if not self.is_initializing():
            for key, v in jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a), *stats).items():
                self.sow(STATS, key, v)
        x = RmsNorm(dtype=self.dtype, eps=c.norm_eps, name="ln_final")(x)
        pooled = last_token(x.astype(jnp.float32), pad_mask)
        return nn.Dense(self.out_dim, dtype=jnp.float32, name="proj")(pooled)
