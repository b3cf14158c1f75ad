"""Plain reference of the Granite-4.0-H (`granitemoehybrid`) embedding tower:
`jax.numpy`, float32, matmuls at `highest` precision, the state-space
recurrence as a `lax.scan` over tokens (not chunked), a materialised causal
softmax, a Python loop over the experts held with masks; no kernel, no sort,
no chunk. It imports nothing of the program. Parameters arrive as the nested
dict `weights_ssm.make_params` fills (tok_embed/embedding,
block<i>/mixer/in_proj/kernel, block<i>/attn/wq/kernel, block<i>/moe/w_gate,
...); leaves held in bfloat16 are upcast where they are used, one layer at a
time (`ServeReference`), so that ten layers of the published widths fit.

The layer equations (source: the published config.json of
ibm-granite/granite-4.0-h-small, model_type granitemoehybrid, and the
Mamba-2 paper's recurrence). RMSNorm everywhere with eps `rms_norm_eps` and a
learned scale; h is a block's input [L, hidden]; m = `residual_multiplier`.

  Tower.  h0 = embedding_multiplier * Embed(ids) over the held rows; the
          blocks by `layer_types`; final RMSNorm; the hidden state of the
          last non-pad token; the repo's `proj` Dense (with a bias) to
          out_dim in float32; L2-normalised by the caller.
  Block.  x = h + m * Mix(RMSNorm(h)), Mix the mixer or attention by the
          layer's type;  y = x + m * (Routed(u) + Shared(u)), u = RMSNorm(x).
  Mamba-2 mixer.  [z | xBC | dt] = u W_in  (d_inner | d_inner + 2 N | heads,
          d_inner = mamba_expand * hidden = mamba_n_heads * mamba_d_head,
          N = mamba_d_state). xBC = silu(conv1d_causal(xBC; w[d_conv, .],
          b)), depthwise, left-padded with zeros. Split xBC into X (heads x
          d_head), B (N), C (N): mamba_n_groups 1, shared by all heads.
          delta = softplus(dt + dt_bias) per head (no clamp: the default
          time-step limits are 0 and infinity), A = -exp(A_log) per head.
          Per head, state S in R^{d_head x N}, S_0 = 0:
              S_t = exp(delta_t A) S_{t-1} + delta_t X_t B_t^T
              Y_t = S_t C_t + D X_t
          g = RMSNorm_{d_inner}(Y * silu(z)) (gate first, then the norm over
          all of d_inner with its scale); out = g W_out. `mamba_chunk_size`
          is how the program computes it, not what.
  Attention.  q = u W_q (heads x head_dim), k, v = u W_k, u W_v (kv heads;
          query head i reads key/value head i // (heads / kv heads)); no
          rotary (`position_embedding_type` nope), no bias;
          softmax(q k^T * attention_multiplier + causal + pad) v; W_o.
          The scale is attention_multiplier (1/128), not 1/sqrt(head_dim).
  Routed. l = u W_r in float32 (num_local_experts wide). S = the indices of
          the `num_experts_per_tok` largest l; w = softmax(l[S]) over those
          alone, held here or not. Routed(u) = sum_{i in S, i held} w_i
          E_i(u), E_i(u) = (silu(u W_g,i) * (u W_u,i)) W_d,i, width
          intermediate_size. No bias on selection, no scaling factor, no
          capacity, no dropped token, no auxiliary loss. What absent experts
          would add is left out (one expert-parallel rank's part, before the
          exchange), and that partial sum goes on to the next layer.
  Shared. The same SwiGLU at shared_intermediate_size, for every token (the
          published `input_linear` is [gate | up]; held here as two kernels).

Departures from the published model: the tied output head and
`logits_scaling` belong to the language-model objective and are unused;
`proj` is the repo's; dropout 0.0; only the first period of layers is held,
so the vector is the first pipeline stage's.

`quant` is the control's hook (both operands of every matrix product; the
carried state stays float32). `carry_state=False` (the state dropped at every
chunk boundary), `softmax_all=True` (softmax over all the logits in place of
the selected) and `residual=False` (m left out) are this model's planted
faults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import towers
from .glm4_moe_lite import _mm, _rms_norm, _swiglu

identity = towers.identity
_F32 = jnp.float32


def _up(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(_F32), tree)


def recurrence(x, delta, a, b, c, quant=identity, reset_every: int = 0):
    """x [B, L, H, P], delta [B, L, H], a [H], b and c [B, L, N] ->
    Y [B, L, H, P] with Y_t = S_t C_t, token by token. `reset_every` > 0
    zeroes the state at every multiple of it (the planted fault)."""
    B, L, H, P = x.shape
    N = b.shape[-1]

    def step(s, t):
        i, x_t, d_t, b_t, c_t = t
        if reset_every:
            s = jnp.where(i % reset_every == 0, 0.0, s)
        s = jnp.exp(d_t * a)[..., None, None] * s + jnp.einsum(
            "bhp,bk->bhpk", quant(x_t * d_t[..., None]), quant(b_t),
            precision="highest")
        return s, jnp.einsum("bhpk,bk->bhp", s, quant(c_t),
                             precision="highest")

    time_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), _F32),
                        (jnp.arange(L),) + tuple(
                            map(time_major, (x, delta, b, c))))
    return jnp.moveaxis(y, 0, 1)


def mixer(p, u, a: dict, quant=identity, carry_state: bool = True):
    B, L, _ = u.shape
    H, P, N = a["mamba_n_heads"], a["mamba_d_head"], a["mamba_d_state"]
    inner = H * P
    zxd = _mm(u, p["in_proj"]["kernel"], quant)
    z, xbc, dt = (zxd[..., :inner], zxd[..., inner:2 * inner + 2 * N],
                  zxd[..., 2 * inner + 2 * N:])
    K = p["conv_kernel"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + L] * p["conv_kernel"][i]
                          for i in range(K)) + p["conv_bias"])
    x = xbc[..., :inner].reshape(B, L, H, P)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, delta, -jnp.exp(p["A_log"]), xbc[..., inner:inner + N],
                   xbc[..., inner + N:], quant,
                   0 if carry_state else a["mamba_chunk_size"])
    y = y + p["D"][:, None] * x
    g = _rms_norm(p["norm"], y.reshape(B, L, inner) * jax.nn.silu(z),
                  a["rms_norm_eps"])
    return _mm(g, p["out_proj"]["kernel"], quant)


def attention(p, u, mask, a: dict, quant=identity):
    B, L, d = u.shape
    H, G = a["num_attention_heads"], a["num_key_value_heads"]
    dh = d // H
    q = _mm(u, p["wq"]["kernel"], quant).reshape(B, L, H, dh)
    k = _mm(u, p["wk"]["kernel"], quant).reshape(B, L, G, dh)
    v = _mm(u, p["wv"]["kernel"], quant).reshape(B, L, G, dh)
    q = q.reshape(B, L, G, H // G, dh)          # head i reads kv head i // r
    s = jnp.einsum("bqgrd,bkgd->bgrqk", quant(q), quant(k),
                   precision="highest") * a["attention_multiplier"]
    pos = jnp.arange(L)
    allowed = mask[:, None, None, None, :] \
        & (pos[None, :] <= pos[:, None])[None, None, None]
    w = jax.nn.softmax(jnp.where(allowed, s, -1e9), axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", quant(w), quant(v),
                   precision="highest").reshape(B, L, H * dh)
    return _mm(o, p["wo"]["kernel"], quant)


def route(p, u, a: dict, softmax_all: bool = False):
    """(chosen [T, k] expert indices, weight [T, k]): float32 at `highest`,
    never quantised (a selection is not a precision)."""
    logits = jnp.matmul(u, p["router"]["kernel"], precision="highest")
    picked, chosen = jax.lax.top_k(logits, a["num_experts_per_tok"])
    if softmax_all:      # the fault: weights of a softmax over every expert
        return chosen, jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                           chosen, axis=1)
    return chosen, jax.nn.softmax(picked, axis=-1)


def experts(p, u, a: dict, quant=identity, softmax_all: bool = False,
            held_start=None, shared: bool = True):
    """u [T, d] -> (Routed(u) + Shared(u) [T, d], assignments per held
    expert [held]): expert by expert over all tokens, masked. The experts
    held are `p`'s stacked kernels, from `held_start` (default: the arch's
    `experts_held_start`)."""
    start = a["experts_held_start"] if held_start is None else held_start
    chosen, weight = route(p, u, a, softmax_all)
    out = _swiglu(p["shared"], u, quant) if shared else jnp.zeros_like(u)
    counts = []
    for e in range(p["w_gate"].shape[0]):
        hit = chosen == start + e                               # [T, k]
        w_e = jnp.where(hit, weight, 0.0).sum(-1, keepdims=True)
        h = jax.nn.silu(_mm(u, p["w_gate"][e], quant)) \
            * _mm(u, p["w_up"][e], quant)
        out = out + w_e * _mm(h, p["w_down"][e], quant)
        counts.append(hit.sum())
    return out, jnp.stack(counts)


def block(p, h, mask, kind: str, a: dict, quant=identity,
          carry_state: bool = True, softmax_all: bool = False,
          residual: bool = True):
    """One layer on [B, L, d] float32 -> (the next h, assignments per held
    expert). `p` may hold bfloat16 leaves: they are upcast here."""
    p = _up(p)
    B, L, d = h.shape
    m = a["residual_multiplier"] if residual else 1.0
    eps = a["rms_norm_eps"]
    u = _rms_norm(p["ln_mix"], h, eps)
    mix = mixer(p["mixer"], u, a, quant, carry_state) if kind == "mamba" \
        else attention(p["attn"], u, mask, a, quant)
    x = h + m * mix
    y, counts = experts(p["moe"], _rms_norm(p["ln_ffn"], x, eps)
                        .reshape(B * L, d), a, quant, softmax_all)
    return x + m * y.reshape(B, L, d), counts


def embed(p, ids, a: dict):
    return a["embedding_multiplier"] * _up(p["tok_embed"]["embedding"][ids])


def head(p, h, mask, a: dict, quant=identity):
    x = _rms_norm(_up(p["ln_final"]), h, a["rms_norm_eps"])
    L = mask.shape[1]
    last = jnp.max(jnp.where(mask, jnp.arange(L)[None, :], 0), axis=1)
    pooled = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    proj = _up(p["proj"])
    return _mm(pooled, proj["kernel"], quant) + proj["bias"]


def tower(p: dict, ids, arch: dict, **how):
    """[B, L] token ids (0 = pad, pads last) -> ([B, out_dim] float32,
    [layers, held] assignments per held expert), whole: for sizes that fit
    whole (the tests). `arch` holds the published keys as run: `layer_types`
    as held, `experts_held_start`. `how`: quant and the planted faults."""
    mask = ids > 0
    h = embed(p, ids, arch)
    counts = []
    for i, kind in enumerate(arch["layer_types"]):
        h, c = block(p[f"block{i}"], h, mask, kind, arch, **how)
        counts.append(c)
    return head(p, h, mask, arch, how.get("quant", identity)), \
        jnp.stack(counts)


class ServeReference:
    """The tower at the published widths, layer by layer over all the rows,
    the rows in blocks: one layer's float32 copy and one block's
    activations are live at a time."""

    def __init__(self, arch: dict, block_rows: int, **how):
        self.arch, self.rows = arch, block_rows
        self._block = {
            kind: jax.jit(functools.partial(block, kind=kind, a=arch, **how))
            for kind in set(arch["layer_types"])}
        self._embed = jax.jit(functools.partial(embed, a=arch))
        self._head = jax.jit(functools.partial(
            head, a=arch, quant=how.get("quant", identity)))

    def vectors(self, p: dict, ids) -> tuple:
        """([n, out_dim] unit vectors, [layers, held] counts) of [n, L]
        ids through the tower `p` (`params["params"]["query_tower"]`)."""
        ids = jnp.asarray(ids)
        spans = [(s, min(s + self.rows, ids.shape[0]))
                 for s in range(0, ids.shape[0], self.rows)]
        hs = [self._embed(p, ids[a:b]) for a, b in spans]
        counts = []
        for i, kind in enumerate(self.arch["layer_types"]):
            layer, c = p[f"block{i}"], 0
            for j, (a, b) in enumerate(spans):
                hs[j], cj = self._block[kind](layer, hs[j], ids[a:b] > 0)
                c = c + cj
            counts.append(np.asarray(c))
        out = jnp.concatenate([self._head(p, h, ids[a:b] > 0)
                               for h, (a, b) in zip(hs, spans)])
        return towers.l2_normalize(out), np.stack(counts)
