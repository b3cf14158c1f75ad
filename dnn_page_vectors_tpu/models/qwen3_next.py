"""Qwen3-Next (`qwen3_next`) as an embedding tower: Gated DeltaNet linear
attention in three layers of four and gated grouped-query attention in the
fourth (`full_attention_interval`), and in EVERY layer a routed-expert FFN
with a sigmoid-gated shared expert; causal, partial rotary positions,
zero-centred RMSNorm, no biases; the hidden state of the last non-pad token
is projected to the page/query vector.

Norms are zero-centred, x / rms(x) * (1 + w) with w starting at 0, but for
the gated norm of the linear attention (x / rms(x) * w, w starting at 1).
h a block's input; names in backticks are the published config's keys:

Tower   Embed(ids); the blocks; final norm; last non-pad token; `proj` Dense
        to out_dim (float32).
Block   x = h + Mix(norm(h)), Mix by the layer's type (layer i is attention
        where (i + 1) % `full_attention_interval` == 0);
        y = x + Routed(u) + sigmoid(u W_sg) Shared(u), u = norm(x).
GDN     [q | k | v | z] = u W_qkvz, laid out per key head as [q Dk | k Dk |
        v r Dv | z r Dv] (r = value heads / key heads; the r value heads of
        a key head adjacent); [b | a] = u W_ba, per key head [b r | a r];
        [q | k | v] = silu(conv1d_causal([q | k | v]; w[K, .])), depthwise,
        no bias, zeros on the left; beta = sigmoid(b);
        g = -exp(A_log) softplus(a + dt_bias) (float32); q and k repeated to
        the value heads (value head j reads key head j // r), L2-normalised
        (eps 1e-6), q scaled by Dk^-1/2; the gated delta rule of
        ops/gated_delta.py per value head; o = rms(o) * w * silu(z) over
        each head's Dv; out = o W_out.
Attn    models/granite_hybrid.py:GqaAttention with `output_gate` (W_q gives
        [q | gate] a head), `qk_norm` (zero-centred, over each head) and
        rotary over the first `partial_rotary_factor` x head_dim dims
        (half-split pairing inside them, `rope_theta`); softmax(q k^T /
        sqrt(head_dim) + causal + pad) v; times sigmoid(gate); W_o.
Routed  models/glm_moe.py:RoutedExperts with `router="softmax_topk"`: a
        softmax over all experts, the top k kept and renormalised
        (`norm_topk_prob`) is a softmax over the k selected logits; the held
        experts' part of the sum, what absent experts would add left out.
        Shared: a SwiGLU at `shared_expert_intermediate_size`, times the
        gate (`shared_gate`).

Device-side scopes (docs/OBSERVABILITY.md): `gdn`, `gdn.in_proj`,
`gdn.conv`, `gdn.delta`, `gdn.gate_norm`, `gdn.out_proj`; `attn`,
`attn.qkv`, `attn.rope`, `attn.flash`, `attn.out`; the expert layer's `moe`,
`moe.*`. Counters, sown once a call as stacked arrays: `moe_stats` as the
GLM tower sows them (one entry per layer), and `gdn_stats`: `tokens`
[Gated DeltaNet layers], the positions the recurrence ran over, and
`state_norm_max` [Gated DeltaNet layers], the largest Frobenius norm of a
head's final state (a health reading: a wrong decay or a lost beta shows as
a state that grows).
"""
from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from dnn_page_vectors_tpu.models.glm_moe import (_ROW_GROUP_TOKENS, STATS,
                                                 RoutedExperts, last_token)
from dnn_page_vectors_tpu.models.granite_hybrid import (GqaAttention,
                                                        causal_conv)
from dnn_page_vectors_tpu.models.transformer import RmsNorm
from dnn_page_vectors_tpu.ops.gated_delta import gated_delta

GDN_STATS = "gdn_stats"       # the linear attention's collection
# What a recomputed half block keeps from its first forward: the shared
# expert's two up-products (2 KB a token and layer); everything else is made
# again (models/glm_moe.py:_KEPT says why a list is short)
_KEPT = ("shared_gate", "shared_up")


@dataclasses.dataclass(frozen=True)
class Qwen3NextSizes:
    """The published keys of a `qwen3_next` config that shape a block, under
    the names the shared attention and expert layer read, and the share of
    the routed experts held here."""
    model_dim: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    full_attention_interval: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    moe_mlp_dim: int              # every routed expert's width
    shared_mlp_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    experts_held: int
    experts_held_start: int = 0
    norm_eps: float = 1e-6
    key_multiplier = 1.0          # what GqaAttention reads besides

    def __post_init__(self):
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(f"{self.linear_num_value_heads} value heads do "
                             f"not share {self.linear_num_key_heads} key "
                             "heads evenly")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not share "
                             f"{self.num_kv_heads} key/value heads evenly")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"partial_rotary_factor "
                             f"{self.partial_rotary_factor} of head_dim "
                             f"{self.head_dim} is no even rotary width")

    @property
    def attention_multiplier(self) -> float:
        """The score scale: 1 / sqrt(head_dim)."""
        return self.head_dim ** -0.5

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


def layer_types(num_layers: int, interval: int) -> tuple:
    """"attention" where (i + 1) % interval == 0, "gdn" elsewhere."""
    return tuple("gdn" if (i + 1) % interval else "attention"
                 for i in range(num_layers))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def l2_normalise(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """x / sqrt(sum(x^2) + eps) over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def split_projections(qkvz: jnp.ndarray, ba: jnp.ndarray,
                      c: Qwen3NextSizes) -> tuple:
    """The published layout of the two input projections, [B, L, .] each:
    per key head [q Dk | k Dk | v r Dv | z r Dv] and [b r | a r], the r
    value heads of a key head adjacent -> q, k [B, L, key heads, Dk], v, z
    [B, L, value heads, Dv], b, a [B, L, value heads]."""
    B, L = qkvz.shape[:2]
    Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
    Dk, Dv = c.linear_key_head_dim, c.linear_value_head_dim
    r = Hv // Hk
    qkvz = qkvz.reshape(B, L, Hk, 2 * Dk + 2 * r * Dv)
    ba = ba.reshape(B, L, Hk, 2 * r)
    by_value_head = lambda t, e: t.reshape(B, L, Hv, e)
    return (qkvz[..., :Dk], qkvz[..., Dk:2 * Dk],
            by_value_head(qkvz[..., 2 * Dk:2 * Dk + r * Dv], Dv),
            by_value_head(qkvz[..., 2 * Dk + r * Dv:], Dv),
            by_value_head(ba[..., :r], 1)[..., 0],
            by_value_head(ba[..., r:], 1)[..., 0])


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer; returns (out, {"tokens", "state_norm"})."""
    sizes: Qwen3NextSizes
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u: jnp.ndarray):
        c = self.sizes
        B, L, d = u.shape
        Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
        Dk, Dv = c.linear_key_head_dim, c.linear_value_head_dim
        r = Hv // Hk
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         name=name)
        with jax.named_scope("gdn.in_proj"):
            q, k, v, z, b, a = split_projections(
                dense(Hk * (2 * Dk + 2 * r * Dv), "in_proj_qkvz")(u),
                dense(Hk * 2 * r, "in_proj_ba")(u), c)
        with jax.named_scope("gdn.conv"):
            x = jnp.concatenate([q.reshape(B, L, Hk * Dk),
                                 k.reshape(B, L, Hk * Dk),
                                 v.reshape(B, L, Hv * Dv)], axis=-1)
            w = self.param("conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (c.linear_conv_kernel_dim, x.shape[-1]))
            x = nn.silu(causal_conv(x, w))
        a_log = self.param("A_log", _a_log_init, (Hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,))
        with jax.named_scope("gdn.delta"):
            heads = lambda t, h, e: t.reshape(B, L, h, e)
            q = heads(x[..., :Hk * Dk], Hk, Dk)
            k = heads(x[..., Hk * Dk:2 * Hk * Dk], Hk, Dk)
            v = heads(x[..., 2 * Hk * Dk:], Hv, Dv)
            beta = jax.nn.sigmoid(b.astype(jnp.float32))
            g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32)
                                                  + dt_bias)
            q = (l2_normalise(q) * Dk ** -0.5).astype(self.dtype)
            k = l2_normalise(k).astype(self.dtype)
            q, k = (jnp.repeat(t, r, axis=2) for t in (q, k))
            o, state = gated_delta(q, k, v, g, beta)
            norm = jnp.sqrt(jnp.sum(jnp.square(state), axis=(-2, -1)))
        with jax.named_scope("gdn.gate_norm"):
            o = RmsNorm(dtype=self.dtype, eps=c.norm_eps, name="norm")(o)
            o = (o.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
                 ).astype(self.dtype)
        with jax.named_scope("gdn.out_proj"):
            out = dense(d, "out_proj")(o.reshape(B, L, Hv * Dv))
        stats = {"tokens": jnp.asarray(B * L, jnp.int32),
                 "state_norm": jax.lax.stop_gradient(norm.max())}
        return out, stats


class MixHalf(nn.Module):
    """x + Mix(norm(x)): the first half of a block; returns it with the
    linear attention's counters (None for an attention layer)."""
    sizes: Qwen3NextSizes
    kind: str                     # gdn | attention
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"

    @nn.compact
    def __call__(self, x, pad_mask):
        c = self.sizes
        h = RmsNorm(dtype=self.dtype, eps=c.norm_eps, zero_centred=True,
                    name="ln_mix")(x)
        stats = None
        if self.kind == "gdn":
            with jax.named_scope("gdn"):
                h, stats = GatedDeltaNet(c, dtype=self.dtype,
                                         name="linear_attn")(h)
        else:
            with jax.named_scope("attn"):
                h = GqaAttention(c, dtype=self.dtype,
                                 kind=self.attention_kind, qk_norm=True,
                                 rotary_dim=c.rotary_dim, output_gate=True,
                                 name="attn")(h, pad_mask)
        return x + h, stats


class FfnHalf(nn.Module):
    """x + Routed(u) + Shared(u), u = norm(x); returns it with the expert
    layer's counters."""
    sizes: Qwen3NextSizes
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        u = RmsNorm(dtype=self.dtype, eps=c.norm_eps, zero_centred=True,
                    name="ln_ffn")(x)
        with jax.named_scope("moe"):
            h, stats = RoutedExperts(
                c.model_dim, c.moe_mlp_dim, c.n_routed_experts,
                c.num_experts_per_tok, 1.0, c.experts_held,
                c.experts_held_start, dtype=self.dtype,
                router="softmax_topk", shared_dim=c.shared_mlp_dim,
                shared_gate=True, name="moe")(u)
        return x + h, stats


class Blocks(nn.Module):
    """All the blocks, for one group of rows: a scan body, (carry, (x,
    pad_mask)) -> (carry, (y, the expert layers' counters, the linear
    attention's counters, each stacked by layer)). With `remat` each half
    block is recomputed in the backward pass from its input and what `_KEPT`
    names (models/glm_moe.py:Blocks)."""
    sizes: Qwen3NextSizes
    num_layers: int
    remat: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"

    @nn.compact
    def __call__(self, carry, xs):
        x, pad_mask = xs
        mix, ffn = MixHalf, FfnHalf
        if self.remat:
            keep = jax.checkpoint_policies.save_only_these_names(*_KEPT)
            mix, ffn = (nn.remat(half, policy=keep)
                        for half in (mix, ffn))
        moe, gdn = [], []
        kinds = layer_types(self.num_layers,
                            self.sizes.full_attention_interval)
        for i, kind in enumerate(kinds):
            x, st = mix(self.sizes, kind, dtype=self.dtype,
                        attention_kind=self.attention_kind,
                        name=f"block{i}_mix")(x, pad_mask)
            if st is not None:
                gdn.append(st)
            x, st = ffn(self.sizes, dtype=self.dtype,
                        name=f"block{i}_ffn")(x)
            moe.append(st)
        stack = lambda sts: jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *sts) if sts else {}
        return carry, (x, stack(moe), stack(gdn))


class Qwen3NextEncoder(nn.Module):
    vocab_size: int
    sizes: Qwen3NextSizes
    num_layers: int
    out_dim: int
    # recompute each half block in the backward, but for what `_KEPT` lists
    remat: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"
    # no fields: what Trainer and BulkEmbedder ask a tower before they apply
    # it with the `moe_stats` (and `gdn_stats`) collections mutable
    sows_moe_stats = True
    sows_gdn_stats = True

    @nn.compact
    def __call__(self, ids: jnp.ndarray,
                 deterministic: bool = True) -> jnp.ndarray:
        # ids: [B, L], 0 = pad, pads at the end of the row (the model is
        # causal, so they cannot reach the pooled token). No dropout in the
        # published config: `deterministic` changes nothing.
        B, L = ids.shape
        c = self.sizes
        pad_mask = ids > 0
        x = nn.Embed(self.vocab_size, c.model_dim, dtype=self.dtype,
                     name="tok_embed")(ids)
        # as models/glm_moe.py:GlmMoeEncoder: with recomputation on, a long
        # batch goes through the blocks in groups of rows, in sequence
        groups = B * L // _ROW_GROUP_TOKENS
        if groups < 2 or B % groups or not self.remat:
            groups = 1
        blocks = Blocks if groups == 1 else nn.scan(
            Blocks, variable_broadcast="params",
            split_rngs={"params": False, "dropout": True})
        split = lambda a: a if groups == 1 else a.reshape(
            (groups, B // groups) + a.shape[1:])
        _, (x, moe, gdn) = blocks(
            c, self.num_layers, remat=self.remat, dtype=self.dtype,
            attention_kind=self.attention_kind,
            name="layers")(None, (split(x), split(pad_mask)))
        x = x.reshape((B,) + x.shape[-2:])
        if not self.is_initializing():
            for key, v in moe.items():        # [groups,] layers, ...
                self.sow(STATS, key, v if groups == 1 else v.sum(0))
            if gdn:
                self.sow(GDN_STATS, "tokens", gdn["tokens"] if groups == 1
                         else gdn["tokens"].sum(0))
                self.sow(GDN_STATS, "state_norm_max",
                         gdn["state_norm"] if groups == 1
                         else gdn["state_norm"].max(0))
        x = RmsNorm(dtype=self.dtype, eps=c.norm_eps, zero_centred=True,
                    name="ln_final")(x)
        pooled = last_token(x.astype(jnp.float32), pad_mask)
        return nn.Dense(self.out_dim, dtype=jnp.float32, name="proj")(pooled)
