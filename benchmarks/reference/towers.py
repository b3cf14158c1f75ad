"""Plain reference of the two encoder towers: `jax.numpy`, float32, matmuls
at `highest` precision, no kernels, no batching tricks. It imports nothing of
the program. Parameters arrive as a nested dict in the layout the benchmark's
`weights.make_params` fills (tok_embed/embedding, block<i>/attn/wq/kernel,
...), made by the benchmark from the seed.

  bert  learned absolute positions, pre-norm LayerNorm blocks with biases,
        tanh-GELU MLP, masked mean pool, dense projection.
  t5    relative-position bias shared across layers (32 buckets, max
        distance 128, bidirectional, as in the T5 paper), RMSNorm, no
        biases, gated tanh-GELU MLP, masked mean pool, dense projection.

`quant` is the control's hook: a function applied to both operands of every
matrix product (identity for the reference; a float8 round trip for the
lower-precision control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6


def identity(x):
    return x


def to_fp8(x):
    """Round to float8 (e4m3) and back: the nearest precision below the
    bfloat16 the configurations state."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision="highest")


def _dense(p, x, quant):
    y = _mm(x, p["kernel"], quant)
    return y + p["bias"] if "bias" in p else y


def _layer_norm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _rms_norm(p, x):
    var = jnp.square(x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * p["scale"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def t5_buckets(length: int, num_buckets: int = 32, max_distance: int = 128):
    """Bidirectional T5 bucket of (key position - query position)."""
    pos = jnp.arange(length)
    rel = pos[None, :] - pos[:, None]
    half = num_buckets // 2
    out = (rel > 0).astype(jnp.int32) * half
    n = jnp.abs(rel)
    exact = half // 2
    large = exact + (jnp.log(n.astype(jnp.float32) / exact + 1e-6)
                     / math.log(max_distance / exact)
                     * (half - exact)).astype(jnp.int32)
    large = jnp.minimum(large, half - 1)
    return out + jnp.where(n < exact, n, large)


def tower(p: dict, ids, variant: str, num_layers: int, num_heads: int,
          quant=identity):
    """[B, L] token ids (0 = pad) -> [B, out_dim] float32."""
    B, L = ids.shape
    mask = ids > 0
    x = p["tok_embed"]["embedding"][ids]
    d = x.shape[-1]
    hd = d // num_heads
    bias = None
    if variant == "bert":
        x = x + p["pos_embed"][:L][None]
        norm = _layer_norm
    else:
        bias = p["rel_bias"][t5_buckets(L)].transpose(2, 0, 1)[None]
        norm = _rms_norm
    for i in range(num_layers):
        b = p[f"block{i}"]
        h = norm(b["ln_attn"], x)
        a = b["attn"]
        q = _dense(a["wq"], h, quant).reshape(B, L, num_heads, hd)
        k = _dense(a["wk"], h, quant).reshape(B, L, num_heads, hd)
        v = _dense(a["wv"], h, quant).reshape(B, L, num_heads, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", quant(q), quant(k),
                       precision="highest") / math.sqrt(hd)
        if bias is not None:
            s = s + bias
        s = jnp.where(mask[:, None, None, :], s, -1e9)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", quant(w), quant(v),
                       precision="highest").reshape(B, L, d)
        x = x + _dense(a["wo"], o, quant)
        h = norm(b["ln_mlp"], x)
        if variant == "t5":
            h = _gelu(_dense(b["wi_0"], h, quant)) * _dense(b["wi_1"], h,
                                                           quant)
        else:
            h = _gelu(_dense(b["wi"], h, quant))
        x = x + _dense(b["wo_mlp"], h, quant)
    x = norm(p["ln_final"], x)
    m = mask[..., None].astype(jnp.float32)
    pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    return _dense(p["proj"], pooled, quant)


def l2_normalize(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + EPS)


def contrastive_loss(q, p, log_scale, quant=identity):
    """Symmetric softmax cross-entropy over cosine similarities, in-batch
    negatives, inverse temperature min(exp(log_scale), 100)."""
    qn, pn = l2_normalize(q), l2_normalize(p)
    scale = jnp.minimum(jnp.exp(log_scale), 100.0)
    logits = scale * _mm(qn, pn.T, quant)
    diag = jnp.diagonal(logits)
    qp = (jax.nn.logsumexp(logits, axis=1) - diag).mean()
    pq = (jax.nn.logsumexp(logits, axis=0) - diag).mean()
    return 0.5 * (qp + pq)
