"""Plain reference of the Falcon-H1 (`falcon_h1`) embedding tower: `jax.numpy`,
float32, matmuls at `highest` precision, the state-space recurrence as a
`lax.scan` over tokens (not chunked), the gated norm by groups written out,
rotary written out, a materialised causal softmax; no kernel, no chunk. It
imports nothing of the program and nothing of the other towers' references.
Parameters arrive as the nested dict `weights_h1.make_params` fills
(tok_embed/embedding, block<i>/mixer/in_proj/kernel, block<i>/attn/wq/kernel,
block<i>/mlp/wi_0/kernel, ...); leaves held in bfloat16 are upcast where they
are used, one layer at a time (`ServeReference`), so that six layers of the
published widths fit.

The layer equations (source: the published config.json of
tiiuae/Falcon-H1-34B-Instruct, model_type falcon_h1, its modelling code as
released with the checkpoint, and the Mamba-2 paper's recurrence). RMSNorm
everywhere with eps `rms_norm_eps` (1e-5) and a learned scale; h is a block's
input [L, 5120]; names in backticks are the published keys, numbers this
checkpoint's.

  Tower.  h0 = `embedding_multiplier` * Embed(ids) (5.657; all 261,120 rows);
          the blocks; final RMSNorm; the hidden state of the last non-pad
          token; the repo's `proj` Dense (with a bias) to out_dim in float32;
          L2-normalised by the caller. Causal.
  Block.  Both mixers read the same u; their outputs are scaled, then summed,
          then added:  u = RMSNorm(h);
          x = h + `ssm_out_multiplier` * Mamba(u)
                + `attention_out_multiplier`
                  * Attn(`attention_in_multiplier` * u);
          y = x + Mlp(RMSNorm(x)).
  Mamba-2 mixer.  p = ((`ssm_in_multiplier` * u) W_in) * mup, where mup is a
          constant vector over p's 9,248 columns: the five `ssm_multipliers`
          over the segments [z: 4096 | x: 4096 | B: 512 | C: 512 | dt: 32] in
          that order. Split p into z (4096 = `mamba_d_ssm`; `mamba_expand` 2
          would give 10,240 and is overridden), xBC (5120), dt (32).
          xBC = silu(conv1d_causal(xBC; w[4, 5120], b[5120])), depthwise,
          zeros on the left. Split into X (32 heads x 128), B (2 groups x
          256), C (2 groups x 256); head i reads group i // 16.
          delta = softplus(dt + dt_bias) per head (no clamp: the default
          time-step limits are 0 and infinity), A = -exp(A_log) per head.
          Per head, state S in R^{128 x 256}, S_0 = 0:
              S_t = exp(delta_t A) S_{t-1} + delta_t X_t B_t^T
              Y_t = S_t C_t + D X_t
          Gate first, then a GROUPED norm (`mamba_norm_before_gate` false,
          `mamba_rms_norm` true): g = Y * silu(z), each of the 2 groups of
          2,048 columns divided by its own root mean square, then the learned
          scale over all 4,096; out = g W_out. `mamba_chunk_size` is how the
          program computes it, not what.
  Attention.  a = `attention_in_multiplier` * u; q = a W_q (20 x 128),
          k = `key_multiplier` * (a W_k), v = a W_v (4 x 128 each; query head
          i reads key/value head i // 5); rotary over all 128 dims of q and
          k, half-split pairing (dim j rotates with j + 64), `rope_theta`
          1e11, positions 0..L-1; softmax(q k^T / sqrt(128) + causal + pad) v;
          W_o (2560 -> 5120).
  Mlp.    Mlp(v) = `mlp_multipliers`[1] * ((silu(`mlp_multipliers`[0] *
          (v W_gate)) * (v W_up)) W_down), width 21,504.

My reading of the released modelling code agrees with every line above (the
decoder layer scales the two mixers' outputs before it sums them; the mixer
multiplies its input by `ssm_in_multiplier` and its projection by the
`mup_vector`; `k_proj`'s output is multiplied by `key_multiplier` before the
rotary; the gate's pre-activation by `mlp_multipliers`[0] and `down_proj`'s
output by `mlp_multipliers`[1]), so `departures` lists no difference of
equations.

Departures from the published model: no output head, so
`lm_head_multiplier` is unused; `proj` is the repo's; dropout 0.0; only the
first six layers are held, so the vector is the first pipeline stage's;
sequences are right-padded single pages (no packed documents, so no state
reset inside a row).

`quant` is the control's hook (both operands of every matrix product; the
carried state stays float32). The planted faults, each a keyword of `block`:
`carry_state=False` (the state dropped at every chunk boundary),
`one_group=True` (both groups read group 0's B and C), `rotary=False`,
`key_multiplier=False`, `grouped_norm=False` (the norm over all 4,096) and
`mup=False` (the segment multipliers left out).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import towers

identity = towers.identity
_F32 = jnp.float32


def _up(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(_F32), tree)


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision="highest")


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps)


def _rms_norm(p, x, eps):
    return _rms(x, eps) * p["scale"]


def _rotary(x, theta: float):
    """x [B, L, H, R]: dim j and dim j + R/2 rotate by position x
    theta^(-j / (R/2)), positions 0..L-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = jnp.arange(x.shape[1], dtype=_F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def segment_widths(a: dict) -> tuple:
    """The widths of [z | x | B | C | dt] in the mixer's projection."""
    inner = a["mamba_d_ssm"]
    gn = a["mamba_n_groups"] * a["mamba_d_state"]
    return inner, inner, gn, gn, a["mamba_n_heads"]


def recurrence(x, delta, a, b, c, quant=identity, reset_every: int = 0):
    """x [B, L, H, P], delta [B, L, H], a [H], b and c [B, L, G, N] ->
    Y [B, L, H, P] with Y_t = S_t C_t, token by token; head h reads group
    h // (H / G). `reset_every` > 0 zeroes the state at every multiple of it
    (the planted fault)."""
    B, L, H, P = x.shape
    G, N = b.shape[2:]
    by_head = lambda t: jnp.repeat(t, H // G, axis=1)      # [B, G, N] -> H

    def step(s, t):
        i, x_t, d_t, b_t, c_t = t
        if reset_every:
            s = jnp.where(i % reset_every == 0, 0.0, s)
        s = jnp.exp(d_t * a)[..., None, None] * s + jnp.einsum(
            "bhp,bhk->bhpk", quant(x_t * d_t[..., None]),
            quant(by_head(b_t)), precision="highest")
        return s, jnp.einsum("bhpk,bhk->bhp", s, quant(by_head(c_t)),
                             precision="highest")

    time_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), _F32),
                        (jnp.arange(L),) + tuple(
                            map(time_major, (x, delta, b, c))))
    return jnp.moveaxis(y, 0, 1)


def mixer(p, u, a: dict, quant=identity, carry_state: bool = True,
          one_group: bool = False, grouped_norm: bool = True,
          mup: bool = True):
    B, L, _ = u.shape
    H, P, N, G = (a["mamba_n_heads"], a["mamba_d_head"], a["mamba_d_state"],
                  a["mamba_n_groups"])
    widths = segment_widths(a)
    proj = _mm(a["ssm_in_multiplier"] * u, p["in_proj"]["kernel"], quant)
    if mup:
        proj = proj * jnp.concatenate([
            jnp.full((w,), m, _F32)
            for m, w in zip(a["ssm_multipliers"], widths)])
    cuts = np.cumsum(widths)
    z, xbc, dt = proj[..., :cuts[0]], proj[..., cuts[0]:cuts[3]], \
        proj[..., cuts[3]:]
    K = p["conv_kernel"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + L] * p["conv_kernel"][i]
                          for i in range(K)) + p["conv_bias"])
    inner = H * P
    x = xbc[..., :inner].reshape(B, L, H, P)
    b = xbc[..., inner:inner + G * N].reshape(B, L, G, N)
    c = xbc[..., inner + G * N:].reshape(B, L, G, N)
    if one_group:       # the fault: every head reads the first group's
        b, c = (jnp.broadcast_to(t[:, :, :1], t.shape) for t in (b, c))
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, delta, -jnp.exp(p["A_log"]), b, c, quant,
                   0 if carry_state else a["mamba_chunk_size"])
    y = y + p["D"][:, None] * x
    g = y.reshape(B, L, inner) * jax.nn.silu(z)
    eps = a["rms_norm_eps"]
    if grouped_norm:    # each group's columns by their own root mean square
        g = jnp.concatenate([_rms(part, eps)
                             for part in jnp.split(g, G, axis=-1)], -1)
    else:
        g = _rms(g, eps)
    return _mm(g * p["norm"]["scale"], p["out_proj"]["kernel"], quant)


def attention(p, u, mask, a: dict, quant=identity, rotary: bool = True,
              key_multiplier: bool = True, scores_out: bool = False):
    B, L, _ = u.shape
    H, G, dh = (a["num_attention_heads"], a["num_key_value_heads"],
                a["head_dim"])
    q = _mm(u, p["wq"]["kernel"], quant).reshape(B, L, H, dh)
    k = _mm(u, p["wk"]["kernel"], quant).reshape(B, L, G, dh)
    v = _mm(u, p["wv"]["kernel"], quant).reshape(B, L, G, dh)
    if key_multiplier:
        k = a["key_multiplier"] * k
    if rotary:
        q, k = _rotary(q, float(a["rope_theta"])), \
            _rotary(k, float(a["rope_theta"]))
    q = q.reshape(B, L, G, H // G, dh)          # head i reads kv head i // r
    s = jnp.einsum("bqgrd,bkgd->bgrqk", quant(q), quant(k),
                   precision="highest") / math.sqrt(dh)
    pos = jnp.arange(L)
    allowed = mask[:, None, None, None, :] \
        & (pos[None, :] <= pos[:, None])[None, None, None]
    if scores_out:
        return s, jnp.broadcast_to(allowed, s.shape)
    w = jax.nn.softmax(jnp.where(allowed, s, -1e9), axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", quant(w), quant(v),
                   precision="highest").reshape(B, L, H * dh)
    return _mm(o, p["wo"]["kernel"], quant)


def mlp(p, v, a: dict, quant=identity):
    gate_m, down_m = a["mlp_multipliers"]
    gate = gate_m * _mm(v, p["wi_0"]["kernel"], quant)
    h = jax.nn.silu(gate) * _mm(v, p["wi_1"]["kernel"], quant)
    return down_m * _mm(h, p["wo_mlp"]["kernel"], quant)


def _branches(p, h, mask, a: dict, quant=identity, carry_state=True,
              one_group=False, rotary=True, key_multiplier=True,
              grouped_norm=True, mup=True):
    """(the mixer's, the attention's, the SwiGLU's part of a block's output,
    each after its out-multiplier, and x between the halves)."""
    eps = a["rms_norm_eps"]
    u = _rms_norm(p["ln_mix"], h, eps)
    ssm = a["ssm_out_multiplier"] * mixer(
        p["mixer"], u, a, quant, carry_state, one_group, grouped_norm, mup)
    att = a["attention_out_multiplier"] * attention(
        p["attn"], a["attention_in_multiplier"] * u, mask, a, quant, rotary,
        key_multiplier)
    x = h + ssm + att
    return ssm, att, mlp(p["mlp"], _rms_norm(p["ln_ffn"], x, eps), a,
                         quant), x


def block(p, h, mask, a: dict, **how):
    """One layer on [B, L, d] float32 -> the next h. `p` may hold bfloat16
    leaves: they are upcast here. `how`: quant and the planted faults."""
    _, _, ffn, x = _branches(_up(p), h, mask, a, **how)
    return x + ffn


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
    """[B, L] token ids (0 = pad, pads last) -> [B, out_dim] float32, whole:
    for sizes that fit whole (the tests). `arch` holds the published keys as
    run (`num_hidden_layers` as held)."""
    mask = ids > 0
    h = embed(p, ids, arch)
    for i in range(arch["num_hidden_layers"]):
        h = block(p[f"block{i}"], h, mask, arch, **how)
    return head(p, h, mask, arch, how.get("quant", identity))


def branch_ratios(p: dict, ids, arch: dict) -> dict:
    """What the weights' gains are chosen by, at layer 0 of the rows `ids`
    [B, L]: the standard deviation of the visible scores before the softmax,
    and each branch's root mean square (after its out-multiplier) over the
    residual's (h0's)."""
    def ratios(layer, h, mask):
        layer = _up(layer)
        ssm, att, ffn, _ = _branches(layer, h, mask, arch)
        u = _rms_norm(layer["ln_mix"], h, arch["rms_norm_eps"])
        s, seen = attention(layer["attn"],
                            arch["attention_in_multiplier"] * u, mask, arch,
                            scores_out=True)
        rms = lambda t: jnp.sqrt(jnp.mean(jnp.square(t)))
        n = seen.sum()
        mean = jnp.where(seen, s, 0.0).sum() / n
        std = jnp.sqrt(jnp.where(seen, jnp.square(s - mean), 0.0).sum() / n)
        return {"score_std": std, "mamba_over_residual": rms(ssm) / rms(h),
                "attn_over_residual": rms(att) / rms(h),
                "mlp_over_residual": rms(ffn) / rms(h)}

    ids = jnp.asarray(ids)
    out = jax.jit(ratios)(p["block0"], embed(p, ids, arch), ids > 0)
    return {k: float(v) for k, v in out.items()}


class ServeReference:
    """The tower at the published widths, layer by layer over all the rows,
    the rows in blocks: one layer's float32 copy and one block's activations
    (the SwiGLU's [rows x L, 21504] among them) are live at a time."""

    def __init__(self, arch: dict, block_rows: int, **how):
        self.arch, self.rows = arch, block_rows
        self._block = jax.jit(functools.partial(block, a=arch, **how))
        self._embed = jax.jit(functools.partial(embed, a=arch))
        self._head = jax.jit(functools.partial(
            head, a=arch, quant=how.get("quant", identity)))

    def vectors(self, p: dict, ids):
        """[n, out_dim] unit vectors of [n, L] ids through the tower `p`
        (`params["params"]["query_tower"]`)."""
        ids = jnp.asarray(ids)
        spans = [(s, min(s + self.rows, ids.shape[0]))
                 for s in range(0, ids.shape[0], self.rows)]
        hs = [self._embed(p, ids[a:b]) for a, b in spans]
        for i in range(self.arch["num_hidden_layers"]):
            layer = p[f"block{i}"]
            for j, (a, b) in enumerate(spans):
                hs[j] = self._block(layer, hs[j], ids[a:b] > 0)
        out = jnp.concatenate([self._head(p, h, ids[a:b] > 0)
                               for h, (a, b) in zip(hs, spans)])
        return towers.l2_normalize(out)
