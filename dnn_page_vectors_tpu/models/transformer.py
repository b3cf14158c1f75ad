"""Transformer encoder shared by the BERT-mini and mT5 towers
(SURVEY.md §3 #7-8; BASELINE.json:9,11).

One implementation, two variants:
  * variant="bert" — learned absolute positions, LayerNorm, GELU MLP
    (BERT-mini geometry: L=4, d=256, A=4).
  * variant="t5"   — T5 relative-position buckets shared across layers,
    RMSNorm, gated-GELU MLP, no biases (mT5-base encoder geometry:
    L=12, d=768, A=12, ff=2048).

TPU-first choices: pre-norm blocks (stable in bfloat16), softmax in float32,
everything else bfloat16 on the MXU, static [B, L] shapes, no Python control
flow dependent on data. Attention/MLP matmul dims are the tensor-parallel
('model' mesh axis) sharding surface — see parallel/sharding.py rules keyed
on the param names used here (wq/wk/wv/wo, wi/wi_0/wi_1/wo_mlp).
"""
from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def _relative_position_bucket(rel_pos: jnp.ndarray, num_buckets: int = 32,
                              max_distance: int = 128) -> jnp.ndarray:
    """T5 bidirectional relative-position bucketing."""
    num_buckets //= 2
    ret = (rel_pos > 0).astype(jnp.int32) * num_buckets
    n = jnp.abs(rel_pos)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    val_if_large = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact + 1e-6)
        / np.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    val_if_large = jnp.minimum(val_if_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_if_large)


class RmsNorm(nn.Module):
    """x / rms(x) * scale over the last axis; with `groups` > 1 each of that
    many equal runs of the axis is divided by its own root mean square (the
    learned scale still spans the whole axis). `zero_centred`: the learned
    vector w starts at 0 and the scale is 1 + w (param `centred_scale`), as
    Qwen3-Next's norms have it."""
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-6
    groups: int = 1
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        xf = x.astype(jnp.float32)
        if self.groups > 1:
            xf = xf.reshape(x.shape[:-1] + (self.groups, -1))
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        if self.groups > 1:
            y = y.reshape(x.shape)
        if self.zero_centred:
            w = self.param("centred_scale", nn.initializers.zeros,
                           (x.shape[-1],))
            return (y * (1.0 + w)).astype(self.dtype)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return (y * scale).astype(self.dtype)


class Attention(nn.Module):
    """kind: 'dense' (materialised scores), 'flash' (Pallas kernel,
    ops/flash_attention.py), or 'ring' (sequence-parallel over the mesh
    'seq' axis, parallel/ring_attention.py). For the T5 variant, dense/flash
    take the materialised rel_bias while ring takes rel_bias_table — the
    ring rebuilds its bias block per step from global positions instead of
    ever holding the O(L²) bias.

    `seg` (sequence packing, train.pack_pages): [B, L] segment ids
    (0 = pad, s >= 1 = packed page s) restrict attention to
    within-segment pairs — dense builds the [B, L, L] block mask, flash
    compares segment ids per score tile inside the kernel (no [B, L, L]
    in HBM). The T5 rel_bias stays the GLOBAL-position bias: segments
    are contiguous in the row, so within-segment relative distance
    equals global distance, and cross-segment entries are masked."""
    num_heads: int
    model_dim: int
    use_bias: bool
    dtype: jnp.dtype = jnp.bfloat16
    kind: str = "dense"
    mesh: Any = None          # jax.sharding.Mesh, required for kind='ring'

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask: jnp.ndarray,
                 rel_bias: jnp.ndarray | None,
                 rel_bias_table: jnp.ndarray | None = None,
                 seg: jnp.ndarray | None = None) -> jnp.ndarray:
        head_dim = self.model_dim // self.num_heads
        B, L, _ = x.shape
        # Three separate projections, DELIBERATELY not fused into one [d,3d]
        # dot: a fused dot reads x once and has a wider N, so it wins in
        # isolation, but inside the full model its post-matmul q/k/v slices
        # materialize three [B,L,H,Dh] copies, and XLA already overlaps the
        # separate dots with neighboring work (docs/MFU.md "What does not
        # help"; not measured on this code).
        dense = lambda name: nn.Dense(self.model_dim, use_bias=self.use_bias,
                                      dtype=self.dtype, name=name)
        shape = (B, L, self.num_heads, head_dim)
        q = dense("wq")(x).reshape(shape)
        k = dense("wk")(x).reshape(shape)
        v = dense("wv")(x).reshape(shape)
        bhld = lambda t: t.transpose(0, 2, 1, 3)
        if self.kind == "flash":
            from dnn_page_vectors_tpu.ops.flash_attention import flash_attention
            bias = None if rel_bias is None else rel_bias[0]  # [H, L, L]
            out = flash_attention(bhld(q), bhld(k), bhld(v), pad_mask, bias,
                                  seg=seg)
            out = bhld(out.astype(self.dtype))                # [B, L, H, Dh]
        elif self.kind == "ring":
            from dnn_page_vectors_tpu.parallel.ring_attention import ring_attention
            assert self.mesh is not None, "ring attention needs a mesh"
            assert seg is None, \
                "sequence packing (train.pack_pages) supports dense/flash " \
                "attention only — the ring path shards L itself"
            # ring consumes the bias TABLE (rebuilt per step); a materialised
            # [1,H,L,L] bias here means a caller wired the wrong operand
            assert rel_bias is None, "ring attention takes rel_bias_table"
            out = ring_attention(self.mesh, bhld(q), bhld(k), bhld(v),
                                 pad_mask, bias_table=rel_bias_table,
                                 bucket_fn=(None if rel_bias_table is None
                                            else _relative_position_bucket))
            out = bhld(out.astype(self.dtype))
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
            scores = scores.astype(jnp.float32)
            if rel_bias is not None:
                scores = scores + rel_bias
            big_neg = jnp.asarray(-1e9, jnp.float32)
            if seg is None:
                allowed = pad_mask[:, None, None, :]
            else:
                # block-diagonal segment mask: token i may attend j only
                # inside its own packed page (and never to pad, seg 0)
                allowed = ((seg[:, None, :] == seg[:, :, None])
                           & (seg > 0)[:, None, :]
                           & pad_mask[:, None, :])[:, None]   # [B,1,L,L]
            scores = jnp.where(allowed, scores, big_neg)
            probs = nn.softmax(scores, axis=-1).astype(self.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        out = out.reshape(B, L, self.model_dim)
        return dense("wo")(out)


class Block(nn.Module):
    num_heads: int
    model_dim: int
    mlp_dim: int
    variant: str
    dropout: float
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "dense"
    mesh: Any = None

    @nn.compact
    def __call__(self, x, pad_mask, rel_bias, rel_bias_table=None,
                 deterministic: bool = True, seg=None):
        norm = (lambda n: RmsNorm(dtype=self.dtype, name=n)) if self.variant == "t5" \
            else (lambda n: nn.LayerNorm(dtype=self.dtype, name=n))
        use_bias = self.variant != "t5"

        h = norm("ln_attn")(x)
        h = Attention(self.num_heads, self.model_dim, use_bias,
                      dtype=self.dtype, kind=self.attention_kind,
                      mesh=self.mesh, name="attn")(h, pad_mask, rel_bias,
                                                   rel_bias_table, seg=seg)
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        x = x + h

        h = norm("ln_mlp")(x)
        if self.variant == "t5":  # gated GELU, no biases (mT5 geometry)
            # separate gate/value dots, DELIBERATELY not fused into one
            # [d, 2*mlp] projection: the fused variant needs a post-matmul
            # de-interleave of gate and value, a relayout of the widest
            # activation of the block. See docs/MFU.md "What does not help".
            wi0 = nn.Dense(self.mlp_dim, use_bias=False, dtype=self.dtype,
                           name="wi_0")(h)
            wi1 = nn.Dense(self.mlp_dim, use_bias=False, dtype=self.dtype,
                           name="wi_1")(h)
            h = nn.gelu(wi0) * wi1
            h = nn.Dense(self.model_dim, use_bias=False, dtype=self.dtype,
                         name="wo_mlp")(h)
        else:
            h = nn.Dense(self.mlp_dim, dtype=self.dtype, name="wi")(h)
            h = nn.gelu(h)
            h = nn.Dense(self.model_dim, dtype=self.dtype, name="wo_mlp")(h)
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        return x + h


class TransformerEncoder(nn.Module):
    vocab_size: int
    num_layers: int = 4
    num_heads: int = 4
    model_dim: int = 256
    mlp_dim: int = 1024
    out_dim: int = 256
    max_len: int = 128
    dropout: float = 0.1
    variant: str = "bert"          # bert | t5
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "dense"  # dense | flash | ring
    mesh: Any = None               # required for attention_kind='ring'

    @nn.compact
    def __call__(self, ids: jnp.ndarray, deterministic: bool = True,
                 seg: jnp.ndarray | None = None,
                 pos: jnp.ndarray | None = None,
                 nseg: int = 0) -> jnp.ndarray:
        # ids: [B, L] subword ids, 0 = pad.
        #
        # Sequence packing (train.pack_pages, data/loader.py pack_segments):
        # `seg` [B, L] marks which packed page each token belongs to
        # (0 = pad, 1..nseg = page slot); attention is restricted to
        # within-segment pairs and pooling runs PER SEGMENT, returning
        # [B, nseg, D] — one vector per packed page. `pos` [B, L] gives
        # per-segment LOCAL positions so BERT's absolute position
        # embedding restarts at 0 for every packed page (the T5 relative
        # bias needs no restart: segments are contiguous, so
        # within-segment relative distance equals global distance and
        # cross-segment entries are masked). seg=None is the unpacked
        # path, byte-identical to pre-packing behavior: [B, D].
        B, L = ids.shape
        pad_mask = ids > 0
        x = nn.Embed(self.vocab_size, self.model_dim, dtype=self.dtype,
                     name="tok_embed")(ids)
        rel_bias = None
        rel_bias_table = None
        if self.variant == "bert":
            pemb = self.param("pos_embed", nn.initializers.normal(0.02),
                              (self.max_len, self.model_dim))
            if pos is None:
                x = x + pemb[:L].astype(self.dtype)[None]
            else:
                x = x + pemb[pos].astype(self.dtype)        # [B, L, d]
        else:
            # shared-across-layers relative position bias (T5 style)
            table = self.param("rel_bias", nn.initializers.normal(0.02),
                               (32, self.num_heads))
            if self.attention_kind == "ring":
                # never materialise [L, L] here: the ring rebuilds its bias
                # block per step from global positions (ring_attention.py)
                rel_bias_table = table
            else:
                gpos = jnp.arange(L)
                buckets = _relative_position_bucket(
                    gpos[None, :] - gpos[:, None])
                rel_bias = table[buckets].transpose(2, 0, 1)[None]  # [1,H,L,L]
                rel_bias = rel_bias.astype(jnp.float32)
        x = nn.Dropout(self.dropout)(x, deterministic=deterministic)
        for i in range(self.num_layers):
            x = Block(self.num_heads, self.model_dim, self.mlp_dim,
                      self.variant, self.dropout, dtype=self.dtype,
                      attention_kind=self.attention_kind, mesh=self.mesh,
                      name=f"block{i}")(x, pad_mask, rel_bias, rel_bias_table,
                                        deterministic, seg=seg)
        x = (RmsNorm(dtype=self.dtype, name="ln_final") if self.variant == "t5"
             else nn.LayerNorm(dtype=self.dtype, name="ln_final"))(x)
        if seg is not None:
            # per-segment masked mean pool -> one vector per packed page
            assert nseg > 0, "seg requires nseg (segments per packed row)"
            onehot = (seg[:, :, None]
                      == jnp.arange(1, nseg + 1)[None, None, :]
                      ).astype(jnp.float32)                  # [B, L, S]
            tot = jnp.einsum("bld,bls->bsd", x.astype(jnp.float32), onehot)
            cnt = jnp.maximum(onehot.sum(1), 1.0)            # [B, S]
            pooled = tot / cnt[..., None]
            out = nn.Dense(self.out_dim, dtype=jnp.float32,
                           name="proj")(pooled)
            return out                                       # [B, S, D] f32
        # masked mean pool
        m = pad_mask[..., None].astype(jnp.float32)
        pooled = (x.astype(jnp.float32) * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
        out = nn.Dense(self.out_dim, dtype=jnp.float32, name="proj")(pooled)
        return out                                                  # [B, D] f32
