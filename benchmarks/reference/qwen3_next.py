"""Plain reference of the Qwen3-Next (`qwen3_next`) embedding tower and of
its contrastive train step: `jax.numpy`, float32, matmuls at `highest`
precision, the delta rule token by token, materialised causal softmax, a
Python loop over the experts held with masks; no kernel, no chunk, no sort.
It imports nothing of the program. Parameters arrive as the nested dict the
benchmark's weights fill (tok_embed/embedding, layers/block<i>_mix/
linear_attn/in_proj_qkvz/kernel, layers/block<i>_ffn/moe/w_gate, ...).

The layer equations (source: the published config.json of
Qwen/Qwen3-Next-80B-A3B-Instruct, model_type qwen3_next, and the Gated
DeltaNet and gated-attention layers it names). h is a block's input [B, L,
hidden]; Norm(x) = x / rms(x) * (1 + w), eps `rms_norm_eps`, w a learned
vector that starts at 0 (every norm but the gated one below); no biases.

  Block. x = h + Mix(Norm(h)), Mix by the layer: layer i is full attention
         where (i + 1) % full_attention_interval == 0, Gated DeltaNet
         elsewhere;  y = x + MoE(Norm(x)) (decoder_sparse_step 1,
         mlp_only_layers []: every layer).
  GDN.   [q | k | v | z] = h W_qkvz, laid out per key head as [q 128 | k 128
         | v 2x128 | z 2x128] (32 value heads over 16 key heads: the two
         value heads of a key head adjacent); [b | a] = h W_ba, per key head
         [b 2 | a 2]; c = silu(depthwise causal conv of [q | k | v] over
         `linear_conv_kernel_dim` taps, no bias); beta = sigmoid(b);
         g = -exp(A_log) softplus(a + dt_bias); q, k repeated to the value
         heads (value head j reads key head j // 2), L2-normalised (eps
         1e-6), q scaled by 128^-1/2; per value head, S_0 = 0 [128 x 128]:
             S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;
             o_t = S^T q_t
         o = o / rms(o) * w * silu(z) over each head (w starts at 1, NOT
         zero-centred); out = o W_out.
  Attn.  [q | gate] = h W_q per head (256 | 256); k, v = h W_k, h W_v (2
         key/value heads; query head i reads i // 8); q, k = Norm over each
         head; rotary at `rope_theta` on the first partial_rotary_factor x
         256 = 64 dims (half-split pairing inside them, positions 0..L-1);
         a = softmax(q k^T / sqrt(256) + causal + pad) v; out =
         (a * sigmoid(gate)) W_o.
  MoE.   p = softmax(u W_r) over all `num_experts` (float32); S = the top
         `num_experts_per_tok` of p; w_i = p_i / sum_{j in S} p_j
         (norm_topk_prob); out = sigmoid(u W_sg) E_shared(u)
         + sum_{i in S, i held} w_i E_i(u), every E a SwiGLU (width
         moe_intermediate_size, the shared one
         shared_expert_intermediate_size). What absent experts would add is
         left out (one expert-parallel rank's part, before the exchange).
  Tower. embedding rows (the held slice) -> blocks -> final Norm -> the
         hidden state of the last non-pad token -> dense projection (with a
         bias, the repo's `proj`) to out_dim.

Departures from the published model: the output head and the multi-token
prediction module belong to the language-model objective and are unused;
dropout 0. The recurrence's backward is rematerialised a segment of
`chunk` tokens at a time (the program's chunk), and each layer's as a whole
(`jax.checkpoint`): how the gradients are computed, not what, so that a
2,112-token row fits. The held experts are computed on every token at once
and weighted by the router (0 where a token did not pick one).

`sw`, the switches, are float32 scalars handed in at run time, so that one
compiled program serves the reference and every control: `fp8` rounds both
operands of every matrix product (the recurrence's included) to float8, the
control; the planted faults of this model are `reset_state` (the state
dropped every `chunk` tokens), `no_beta` (beta = 1), `no_l2norm` (q and k
not normalised), `no_attn_gate`, `no_shared_gate` and `w_not_1pw` (w in
place of 1 + w). None: every switch off.
"""
from __future__ import annotations

import functools
import json
import types

import jax
import jax.numpy as jnp

from . import towers
from .glm4_moe_lite import MoeTrainReference, _rope, _swiglu
from .train_ref import TrainReference

identity = towers.identity
FAULTS = ("reset_state", "no_beta", "no_l2norm", "no_attn_gate",
          "no_shared_gate", "w_not_1pw")
SWITCHES = ("fp8",) + FAULTS


def switches(fp8: bool = False, faults=()) -> dict:
    """The switches as the reference's programs take them."""
    bad = set(faults) - set(FAULTS)
    if bad:
        raise ValueError(f"unknown faults {sorted(bad)} (want {FAULTS})")
    on = set(faults) | ({"fp8"} if fp8 else set())
    return {k: jnp.float32(k in on) for k in SWITCHES}


def _on(sw, name):
    return 0.0 if sw is None else sw[name]


def _blend(sw, name, good, bad):
    """`good`, or `bad` where the switch is on."""
    return good if sw is None else good + sw[name] * (bad - good)


def _q(x, sw):
    """x, or x rounded to float8 where the control is on."""
    return x if sw is None else jnp.where(sw["fp8"] > 0, towers.to_fp8(x), x)


def _mm(a, b, sw):
    return jnp.matmul(_q(a, sw), _q(b, sw), precision="highest")


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps)


def _norm(p, x, eps, sw):
    w = p["centred_scale"]
    return _rms(x, eps) * (1.0 - _on(sw, "w_not_1pw") + w)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def layer_kinds(arch: dict) -> list:
    every = arch["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "gdn"
            for i in range(arch["num_hidden_layers"])]


def delta_rule(q, k, v, g, beta, segment: int, sw=None):
    """q, k [B, L, H, K], v [B, L, H, V], g, beta [B, L, H] -> o [B, L, H,
    V]: the recurrence token by token, in segments of `segment` tokens whose
    backward is rematerialised; with `reset_state` on, every segment starts
    from a zero state (the fault: the state dropped at every chunk boundary
    of the program, whose chunk the segment is)."""
    B, L, H, K = q.shape
    V = v.shape[-1]
    pad = (-L) % segment
    if pad:     # k 0, beta 0, g 0: a padded step changes nothing
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (t.ndim - 2)) for t in (q, k, v, g, beta))
    hi = dict(precision="highest")
    qt = lambda x: _q(x, sw)

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None, None] * s
        err = v_t - jnp.einsum("bhkv,bhk->bhv", qt(s), qt(k_t), **hi)
        s = s + jnp.einsum("bhk,bhv->bhkv", qt(k_t),
                           qt(b_t[..., None] * err), **hi)
        return s, jnp.einsum("bhkv,bhk->bhv", qt(s), qt(q_t), **hi)

    @jax.checkpoint
    def run(s, xs):
        return jax.lax.scan(token, s * (1.0 - _on(sw, "reset_state")), xs)

    n = (L + pad) // segment
    seg = lambda t: jnp.moveaxis(t, 1, 0).reshape((n, segment, B)
                                                  + t.shape[2:])
    _, o = jax.lax.scan(run, jnp.zeros((B, H, K, V), jnp.float32),
                        tuple(seg(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o.reshape((n * segment, B, H, V)), 0, 1)
    return o[:, :L]


def _gdn(p, h, a: dict, sw):
    B, L, _ = h.shape
    Hk, Hv = a["linear_num_key_heads"], a["linear_num_value_heads"]
    Dk, Dv = a["linear_key_head_dim"], a["linear_value_head_dim"]
    r = Hv // Hk
    qkvz = _mm(h, p["in_proj_qkvz"]["kernel"], sw).reshape(
        B, L, Hk, 2 * Dk + 2 * r * Dv)
    ba = _mm(h, p["in_proj_ba"]["kernel"], sw).reshape(B, L, Hk, 2 * r)
    q, k = qkvz[..., :Dk], qkvz[..., Dk:2 * Dk]
    v = qkvz[..., 2 * Dk:2 * Dk + r * Dv].reshape(B, L, Hv, Dv)
    z = qkvz[..., 2 * Dk + r * Dv:].reshape(B, L, Hv, Dv)
    b, a_ = ba[..., :r].reshape(B, L, Hv), ba[..., r:].reshape(B, L, Hv)
    x = jnp.concatenate([q.reshape(B, L, -1), k.reshape(B, L, -1),
                         v.reshape(B, L, -1)], axis=-1)
    w = p["conv_kernel"]                                   # [taps, channels]
    taps = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(xp[:, i:i + L] * w[i] for i in range(taps)))
    q = x[..., :Hk * Dk].reshape(B, L, Hk, Dk)
    k = x[..., Hk * Dk:2 * Hk * Dk].reshape(B, L, Hk, Dk)
    v = x[..., 2 * Hk * Dk:].reshape(B, L, Hv, Dv)
    beta = _blend(sw, "no_beta", jax.nn.sigmoid(b), 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a_ + p["dt_bias"])
    q, k = (_blend(sw, "no_l2norm", _l2(t), t) for t in (q, k))
    q = q * Dk ** -0.5
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    o = delta_rule(q, k, v, g, beta, a["chunk"], sw)
    o = _rms(o, a["rms_norm_eps"]) * p["norm"]["scale"] * jax.nn.silu(z)
    return _mm(o.reshape(B, L, Hv * Dv), p["out_proj"]["kernel"], sw)


def _attn(p, h, mask, a: dict, sw):
    B, L, _ = h.shape
    H, G, dh = (a["num_attention_heads"], a["num_key_value_heads"],
                a["head_dim"])
    eps, theta = a["rms_norm_eps"], float(a["rope_theta"])
    rot = int(dh * a["partial_rotary_factor"])
    qg = _mm(h, p["wq"]["kernel"], sw).reshape(B, L, H, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = _mm(h, p["wk"]["kernel"], sw).reshape(B, L, G, dh)
    v = _mm(h, p["wv"]["kernel"], sw).reshape(B, L, G, dh)
    q, k = _norm(p["q_norm"], q, eps, sw), _norm(p["k_norm"], k, eps, sw)
    turn = lambda t: jnp.concatenate([_rope(t[..., :rot], theta),
                                      t[..., rot:]], axis=-1)
    q, k = turn(q), turn(k)
    k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", _q(q, sw), _q(k, sw),
                   precision="highest") * dh ** -0.5
    pos = jnp.arange(L)
    allowed = mask[:, None, None, :] & (pos[None, :] <= pos[:, None])[None,
                                                                     None]
    w = jax.nn.softmax(jnp.where(allowed, s, -1e9), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(w, sw), _q(v, sw),
                   precision="highest").reshape(B, L, H * dh)
    o = o * _blend(sw, "no_attn_gate",
                   jax.nn.sigmoid(gate.reshape(B, L, H * dh)), 1.0)
    return _mm(o, p["wo"]["kernel"], sw)


def route(p, u, a: dict):
    """(chosen [T, k] expert indices, weight [T, k]): a softmax over every
    expert, the top k, renormalised; float32 at `highest`, never quantised
    (a selection is not a precision)."""
    probs = jax.nn.softmax(jnp.matmul(u, p["router"]["kernel"],
                                      precision="highest"), axis=-1)
    picked, chosen = jax.lax.top_k(probs, a["num_experts_per_tok"])
    return chosen, picked / picked.sum(-1, keepdims=True)


def experts(p, u, a: dict, sw=None):
    """u [T, d] -> (MoE(u) [T, d], assignments per held expert [held]):
    the gated shared expert plus every held expert on every token, each
    weighted by what the router gave it (0 where it was not picked)."""
    chosen, weight = route(p, u, a)
    gate = jax.nn.sigmoid(_mm(u, p["shared_expert_gate"]["kernel"], sw))
    out = _swiglu(p["shared"], u, lambda x: _q(x, sw)) \
        * _blend(sw, "no_shared_gate", gate, 1.0)
    held = p["w_gate"].shape[0]
    hit = chosen[:, :, None] == a["experts_held_start"] + jnp.arange(held)
    w = jnp.where(hit, weight[..., None], 0.0).sum(1)          # [T, held]
    hi = dict(precision="highest")
    up = lambda name: jnp.einsum("td,edf->etf", _q(u, sw), _q(p[name], sw),
                                 **hi)
    h = jax.nn.silu(up("w_gate")) * up("w_up")                 # [held, T, f]
    out = out + jnp.einsum("etf,efd->td", _q(h * w.T[..., None], sw),
                           _q(p["w_down"], sw), **hi)
    return out, hit.sum((0, 1))


def tower(p: dict, ids, arch: dict, sw=None):
    """[B, L] token ids (0 = pad, pads last) -> ([B, out_dim] float32,
    [layers, held] assignments per held expert). `arch` holds the published
    keys as run (`num_hidden_layers` and `experts_held_start` as held) and
    the program's `chunk`."""
    B, L = ids.shape
    mask = ids > 0
    x = p["tok_embed"]["embedding"][ids]
    eps = arch["rms_norm_eps"]
    counts = []

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def block(b_mix, b_ffn, kind, x, sw):
        u = _norm(b_mix["ln_mix"], x, eps, sw)
        if kind == "gdn":
            x = x + _gdn(b_mix["linear_attn"], u, arch, sw)
        else:
            x = x + _attn(b_mix["attn"], u, mask, arch, sw)
        u = _norm(b_ffn["ln_ffn"], x, eps, sw)
        y, c = experts(b_ffn["moe"], u.reshape(B * L, -1), arch, sw)
        return x + y.reshape(x.shape), c

    for i, kind in enumerate(layer_kinds(arch)):
        x, c = block(p["layers"][f"block{i}_mix"],
                     p["layers"][f"block{i}_ffn"], kind, x, sw)
        counts.append(c)
    x = _norm(p["ln_final"], x, eps, sw)
    last = jnp.max(jnp.where(mask, jnp.arange(L)[None, :], 0), axis=1)
    pooled = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    out = _mm(pooled, p["proj"]["kernel"], sw) + p["proj"]["bias"]
    return out, jnp.stack(counts)


@functools.lru_cache(maxsize=None)
def _programs(arch_json: str, opt_json: str) -> tuple:
    """The reference's jitted programs for one architecture and optimizer,
    shared by every `Qwen3NextTrainReference` (the switches are arguments,
    so the controls compile nothing of their own)."""
    arch, opt = json.loads(arch_json), json.loads(opt_json)
    fwd = functools.partial(tower, arch=arch)

    def add_vjp(acc, p, ids, g, sw):
        _, pull = jax.vjp(lambda tp: fwd(tp, ids, sw=sw)[0], p)
        return jax.tree_util.tree_map(jnp.add, acc, pull(g)[0])

    def loss(q, p, log_scale, sw):
        return towers.contrastive_loss(q, p, log_scale,
                                       quant=lambda x: _q(x, sw))

    update = functools.partial(TrainReference._update_fn,
                               types.SimpleNamespace(opt=opt))
    return (jax.jit(fwd), jax.jit(add_vjp, donate_argnums=(0,)),
            jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))),
            jax.jit(update, donate_argnums=(0, 1, 2)))


class Qwen3NextTrainReference(MoeTrainReference):
    """`MoeTrainReference` (one shared tower, both sides' vector-Jacobian
    products added into one gradient, the parent's AdamW) around this
    tower; `quant` is `identity` or `towers.to_fp8` (the control), `faults`
    names planted faults (`FAULTS`)."""

    def __init__(self, arch: dict, opt: dict, block_rows: int,
                 quant=identity, faults=()):
        if quant not in (identity, towers.to_fp8):
            raise ValueError("the reference rounds to float8 or not at all")
        self.arch, self.opt, self.block = arch, opt, block_rows
        sw = switches(quant is towers.to_fp8, faults)
        fwd, add_vjp, loss_grad, update = _programs(
            json.dumps(arch, sort_keys=True), json.dumps(opt, sort_keys=True))
        self._fwd = lambda p, ids: fwd(p, ids, sw=sw)
        self._add_vjp = lambda acc, p, ids, g: add_vjp(acc, p, ids, g, sw)
        self._loss_grad = lambda q, p, s: loss_grad(q, p, s, sw)
        self._update = update
