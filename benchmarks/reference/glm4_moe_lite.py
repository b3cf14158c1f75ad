"""Plain reference of the GLM-4.7-Flash (`glm4_moe_lite`) embedding tower and
of its contrastive train step: `jax.numpy`, float32, matmuls at `highest`
precision, materialised causal softmax, a Python loop over the experts held
with masks; no kernel, no sort, no recomputation. It imports nothing of the
program. Parameters arrive as the nested dict `weights_moe.make_params` fills
(tok_embed/embedding, layers/block<i>_mix/attn/wq_a/kernel,
layers/block<i>_ffn/moe/w_gate, ...).

The layer equations (source: the published config.json of
zai-org/GLM-4.7-Flash, model_type glm4_moe_lite, and the DeepSeek-V3 family's
description of latent attention and of the `noaux_tc` router). All norms are
RMSNorm with eps `rms_norm_eps` and a learned scale; no biases; h is a
block's input, [T, hidden].

  MLA.   cq = RMSNorm(h Wdq)                      (q_lora_rank)
         q = cq Wuq -> heads of [q_nope | q_rope]  (qk_nope + qk_rope)
         [ckv | k_rope] = h Wdkv                  (kv_lora_rank | qk_rope)
         ckv = RMSNorm(ckv); ckv Wukv -> heads of [k_nope | v]
         q_rope and the single k_rope (shared by all heads) are rotated by
         RoPE at `rope_theta` over all qk_rope dims (partial_rotary_factor
         1; rope_scaling null: no extra scale). ASSUMED, not in the config:
         the pairing of rotated dims is half-split (dim i with i + rope/2).
         k = [k_nope | k_rope]
         a = softmax(q k^T / sqrt(qk_nope + qk_rope) + causal + pad) v
         out = concat_heads(a) Wo
  Block. x = h + MLA(RMSNorm(h));  y = x + FFN(RMSNorm(x))
  FFN, the first `first_k_dense_replace` layers:
         (silu(u Wg) * (u Wu)) Wd, width intermediate_size
  FFN, later layers (u = RMSNorm(x), float32 router):
         s = sigmoid(u Wr), n_routed_experts wide
         S = top-`num_experts_per_tok` of s + b   (b: the selection bias of
             noaux_tc; n_group 1, topk_group 1: no group limit)
         w_i = routed_scaling_factor * s_i / (sum_{j in S} s_j + 1e-20)
             (norm_topk_prob; b selects and never weighs; the sum runs over
             all selected experts, held here or not)
         FFN(u) = E_shared(u) + sum_{i in S, i held here} w_i E_i(u)
         every E a SwiGLU of width moe_intermediate_size. What absent
         experts would add is left out (one expert-parallel rank's part,
         before the exchange), and that partial sum goes on to the next
         layer. No token is dropped, there is no capacity factor and no
         auxiliary loss; b takes no gradient.
  Tower. embedding rows (the held slice) -> blocks -> final RMSNorm -> the
         hidden state of the last non-pad token -> dense projection (with a
         bias, the repo's `proj`) to out_dim.

Departures from the published model: the output head and the multi-token
prediction module (`num_nextn_predict_layers` 1) belong to the language-model
objective and are unused; the load-driven update of b is not run (b is seeded
and held); dropout 0.

`quant` is the control's hook (both operands of every matrix product);
`causal=False` and `scaling=False` are the two planted faults of this model.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import towers
from .train_ref import TrainReference
from ..weights import path_str

identity = towers.identity


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision="highest")


def _rms_norm(p, x, eps):
    var = jnp.square(x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def _rope(x, theta):
    """x [B, L, H, R], positions 0..L-1, half-split pairing."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(p, u, quant):
    g = _mm(u, p["wi_0"]["kernel"], quant)
    return _mm(jax.nn.silu(g) * _mm(u, p["wi_1"]["kernel"], quant),
               p["wo_mlp"]["kernel"], quant)


def _mla(p, h, mask, a: dict, quant, causal):
    B, L, _ = h.shape
    H, nope, rp, vd = (a["num_attention_heads"], a["qk_nope_head_dim"],
                       a["qk_rope_head_dim"], a["v_head_dim"])
    eps, theta = a["rms_norm_eps"], float(a["rope_theta"])
    cq = _rms_norm(p["q_norm"], _mm(h, p["wq_a"]["kernel"], quant), eps)
    q = _mm(cq, p["wq_b"]["kernel"], quant).reshape(B, L, H, nope + rp)
    kv = _mm(h, p["wkv_a"]["kernel"], quant)
    ckv = _rms_norm(p["kv_norm"], kv[..., :a["kv_lora_rank"]], eps)
    k_rope = _rope(kv[..., None, a["kv_lora_rank"]:], theta)   # [B, L, 1, R]
    kv = _mm(ckv, p["wkv_b"]["kernel"], quant).reshape(B, L, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, L, H, rp))], -1)
    v = kv[..., nope:]
    s = jnp.einsum("bqhd,bkhd->bhqk", quant(q), quant(k),
                   precision="highest") / math.sqrt(nope + rp)
    allowed = mask[:, None, None, :]
    if causal:
        pos = jnp.arange(L)
        allowed = allowed & (pos[None, :] <= pos[:, None])[None, None]
    w = jax.nn.softmax(jnp.where(allowed, s, -1e9), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", quant(w), quant(v),
                   precision="highest").reshape(B, L, H * vd)
    return _mm(o, p["wo"]["kernel"], quant)


def route(p, u, a: dict):
    """(chosen [T, k] expert indices, weight [T, k]) of the noaux_tc
    router; float32 at `highest`, never quantised (a selection is not a
    precision)."""
    s = jax.nn.sigmoid(jnp.matmul(u, p["router"]["kernel"],
                                  precision="highest"))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(p["select_bias"]),
                              a["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20)


def _experts(p, u, a: dict, quant, scaling):
    """u [T, d] -> (FFN(u) [T, d], assignments per held expert [held]): the
    shared expert plus the held experts' weighted parts, expert by expert
    over all tokens, masked."""
    chosen, weight = route(p, u, a)
    if scaling:
        weight = weight * a["routed_scaling_factor"]
    out = _swiglu(p["shared"], u, quant)
    counts = []
    for e in range(p["w_gate"].shape[0]):
        hit = chosen == a["experts_held_start"] + e            # [T, k]
        w_e = jnp.where(hit, weight, 0.0).sum(-1, keepdims=True)
        h = jax.nn.silu(_mm(u, p["w_gate"][e], quant)) \
            * _mm(u, p["w_up"][e], quant)
        out = out + w_e * _mm(h, p["w_down"][e], quant)
        counts.append(hit.sum())
    return out, jnp.stack(counts)


def tower(p: dict, ids, arch: dict, quant=identity, causal: bool = True,
          scaling: bool = True):
    """[B, L] token ids (0 = pad, pads last) -> ([B, out_dim] float32,
    [expert layers, held] assignments per held expert). `arch` holds the
    published keys as run (`num_hidden_layers` and `experts_held` /
    `experts_held_start` as held here)."""
    B, L = ids.shape
    mask = ids > 0
    x = p["tok_embed"]["embedding"][ids]
    eps = arch["rms_norm_eps"]
    counts = []
    for i in range(arch["num_hidden_layers"]):
        b = p["layers"][f"block{i}_mix"]
        x = x + _mla(b["attn"], _rms_norm(b["ln_attn"], x, eps), mask, arch,
                     quant, causal)
        b = p["layers"][f"block{i}_ffn"]
        u = _rms_norm(b["ln_mlp"], x, eps)
        if i < arch["first_k_dense_replace"]:
            x = x + _swiglu(b["mlp"], u, quant)
        else:
            y, c = _experts(b["moe"], u.reshape(B * L, -1), arch, quant,
                            scaling)
            x = x + y.reshape(x.shape)
            counts.append(c)
    x = _rms_norm(p["ln_final"], x, eps)
    last = jnp.max(jnp.where(mask, jnp.arange(L)[None, :], 0), axis=1)
    pooled = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    out = _mm(pooled, p["proj"]["kernel"], quant) + p["proj"]["bias"]
    return out, (jnp.stack(counts) if counts else jnp.zeros((0, 0), jnp.int32))


class MoeTrainReference(TrainReference):
    """`TrainReference` for ONE shared tower: queries and pages go through
    the same parameters, and both sides' vector-Jacobian products add into
    one gradient. The optimizer is the parent's, with b put back after each
    update (it is held: no gradient reaches it, and it takes no decay)."""

    def __init__(self, arch: dict, opt: dict, block_rows: int,
                 quant=identity, causal: bool = True, scaling: bool = True):
        self.arch, self.opt, self.block = arch, opt, block_rows
        fwd = functools.partial(tower, arch=arch, quant=quant, causal=causal,
                                scaling=scaling)
        self._fwd = jax.jit(fwd)

        def add_vjp(acc, p, ids, g):
            _, pull = jax.vjp(lambda tp: fwd(tp, ids)[0], p)
            return jax.tree_util.tree_map(jnp.add, acc, pull(g)[0])

        self._add_vjp = jax.jit(add_vjp, donate_argnums=(0,))
        self._loss_grad = jax.jit(jax.value_and_grad(
            functools.partial(towers.contrastive_loss, quant=quant),
            argnums=(0, 1, 2)))
        self._update = jax.jit(self._update_fn, donate_argnums=(0, 1, 2))

    def loss_and_grads(self, params: dict, query_ids, page_ids, rows=None):
        """(loss, grads, assignments per held expert [layers, held] summed
        over both sides) of one batch."""
        p = params["params"]
        if rows is not None:
            query_ids, page_ids = query_ids[rows], page_ids[rows]
        t = p["query_tower"]
        vecs, counts = [], 0
        for ids in (query_ids, page_ids):
            outs = [self._fwd(t, ids[a:b])
                    for a, b in self._blocks(ids.shape[0])]
            vecs.append(jnp.concatenate([o[0] for o in outs]))
            counts = counts + sum(o[1] for o in outs)
        loss, (gq, gp, gs) = self._loss_grad(vecs[0], vecs[1],
                                             p["log_scale"])
        acc = jax.tree_util.tree_map(jnp.zeros_like, t)
        for ids, g in ((query_ids, gq), (page_ids, gp)):
            for a, b in self._blocks(ids.shape[0]):
                acc = self._add_vjp(acc, t, ids[a:b], g[a:b])
        self.counts = counts
        return loss, {"params": {"log_scale": gs, "query_tower": acc}}

    def apply(self, params, mu, nu, grads, step_index: int):
        held = {path_str(path): jnp.array(leaf, copy=True) for path, leaf
                in jax.tree_util.tree_flatten_with_path(params)[0]
                if path_str(path).endswith("select_bias")}
        new, mu, nu, clip = super().apply(params, mu, nu, grads, step_index)
        new = jax.tree_util.tree_map_with_path(
            lambda path, n: held.get(path_str(path), n), new)
        return new, mu, nu, clip
