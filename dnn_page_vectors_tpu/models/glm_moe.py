"""GLM-4.7-Flash (`glm4_moe_lite`) as an embedding tower: latent attention
(MLA), a dense SwiGLU first layer, then routed-expert layers with one shared
expert, causal, RoPE, RMSNorm, no biases; the hidden state of the last
non-pad token is projected to the page/query vector.

The expert layer is told WHICH experts it holds (`experts_held` of the
published `n_routed_experts`, a contiguous range from `experts_held_start`):
it routes every token over all the published experts, computes the part of
the result that its own experts give, and leaves out what the absent ones
would add. With all experts held that is the whole layer; with a share it is
one expert-parallel rank's part, before the exchange that sums the ranks
(parallel/sharding.py has the expert axis's rule; the exchange itself is not
built, and nothing stands in for absent chips).

Per block (h the input, all norms RMSNorm with a learned scale; the two
halves are modules `layers/block<i>_mix` and `layers/block<i>_ffn`):
    x = h + MLA(norm(h));  y = x + FFN(norm(x))
MLA:  cq = norm(h Wdq); q = cq Wuq -> heads of [nope | rope];
      [ckv | k_rope] = h Wdkv; ckv = norm(ckv); ckv Wukv -> heads of
      [k_nope | v]; RoPE on q_rope and the one shared k_rope (half-split
      pairing: dim i rotates with dim i + rope/2); k = [k_nope | k_rope];
      softmax(q k^T / sqrt(nope + rope) + causal + pad) v; Wo.
FFN, dense layers: (silu(u Wg) * (u Wu)) Wd.
FFN, expert layers: s = sigmoid(u Wr) in float32; S = top-k of s + b;
      w_i = scale * s_i / (sum_{j in S} s_j + 1e-20);
      E_shared(u) + sum_{i in S, i held} w_i E_i(u). b selects and never
      weighs, and takes no gradient. No token is dropped.
How a token's router scores become a selection and weights is the part of
the expert layer that varies by architecture (`RoutedExperts.router`): the
above is `sigmoid_noaux_tc`; `softmax_topk` (models/granite_hybrid.py) takes
the k largest raw logits and a softmax over those k alone, with no bias and
no scaling. From the row plan on (`plan_rows`, `permute`, the grouped
products, `unpermute`, the counters) the layer has one body.

Device-side scopes (docs/OBSERVABILITY.md): `mla`, `mla.flash`, `moe`,
`moe.router`, `moe.dispatch`, `moe.experts`, `moe.shared`, `moe.combine`.
Counters are sown into the `moe_stats` collection by the tower, once per
call: `held` [expert layers, experts_held] assignments per held expert,
`absent`, `dropped` and `worst_case` (calls that needed the worst-case
buffers) [expert layers].
"""
from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dnn_page_vectors_tpu.models.transformer import RmsNorm
from dnn_page_vectors_tpu.ops import grouped_matmul as gm

STATS = "moe_stats"          # the counters' collection
_EXPERT_TILE = 256           # rows per tile of the grouped product
_ROW_GROUP_TOKENS = 4096     # tokens in a group of rows (GlmMoeEncoder)
# What a recomputed half block keeps from its first forward (`Blocks`), by
# `checkpoint_name`. The tower names four groups of values that cost a kernel
# or a wide product to make again and little to hold (bytes a token and layer
# at the published widths): flash's output and log-sum-exp
# (`ops/flash_attention.py:CAUSAL_RESIDUALS`, 10,320), the two up-products
# of the dense layer's SwiGLU (`mlp_*`, 40,960) and of a shared expert's
# (`shared_*`, 6,144), and MLA's two down-projections (`mla_*`, 2,688). Not
# q, k and v by head (30 KB).
# LISTED are the groups the step program of `glm47_flash_ep8` has room for
# (PERF.md section 6, PR 36): the compiler sizes that program at 11.65 GiB of
# a chip's 15.75 with nothing kept and at 12.97 with this list. Somewhere
# between 13.1 and 13.6 GiB it starts to make room by recomputing on its own
# (XLA's rematerialization), which costs more than a longer list saves (the
# dense pair beside these, or flash's pair alone: a slower step), and from
# 15.75 on it refuses the program (the four groups together). The runtime's
# `peak_bytes_in_use` sees none of this: it reads 9.4 GB with or without.
_KEPT = ("mla_q_a", "mla_kv_a", "shared_gate", "shared_up")
_FLASH_BLOCK = 512           # square tile of the causal flash kernels
ROUTERS = ("sigmoid_noaux_tc", "softmax_topk")


@dataclasses.dataclass(frozen=True)
class GlmSizes:
    """The published keys of a `glm4_moe_lite` config that shape a block,
    and the share of the routed experts held here."""
    num_heads: int
    model_dim: int
    mlp_dim: int                  # the dense layers' width
    moe_mlp_dim: int              # every expert's width
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    first_k_dense_replace: int
    experts_held: int
    experts_held_start: int = 0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary positions over ALL of x's last dim, positions 0..L-1 along
    axis 1 of [B, L, H, R]; half-split pairing (i with i + R/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def times(x: jnp.ndarray, m: float) -> jnp.ndarray:
    """x * m in x's dtype; a multiplier of 1 is not multiplied in."""
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


class SwiGlu(nn.Module):
    """down * ((silu(gate * (x Wg)) * (x Wu)) Wd), `gate` and `down` two
    constant multipliers (models/falcon_h1.py; 1.0 is not multiplied in).
    The two up-products carry names a recomputation can keep (`_KEPT`); the
    elementwise pass after them it makes again."""
    mlp_dim: int
    model_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    gate_multiplier: float = 1.0
    down_multiplier: float = 1.0

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         name=name)
        with jax.named_scope("mlp.gate_up"):
            gate = checkpoint_name(dense(self.mlp_dim, "wi_0")(x),
                                   f"{self.name}_gate")
            up = checkpoint_name(dense(self.mlp_dim, "wi_1")(x),
                                 f"{self.name}_up")
            h = nn.silu(times(gate, self.gate_multiplier)) * up
        with jax.named_scope("mlp.down"):
            return times(dense(self.model_dim, "wo_mlp")(h),
                         self.down_multiplier)


class MlaAttention(nn.Module):
    num_heads: int
    model_dim: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    kind: str = "flash"           # flash | dense

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask: jnp.ndarray) -> jnp.ndarray:
        B, L, _ = x.shape
        H, nope, rp, vd = (self.num_heads, self.qk_nope_head_dim,
                           self.qk_rope_head_dim, self.v_head_dim)
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         name=name)
        norm = lambda name: RmsNorm(dtype=self.dtype, eps=self.norm_eps,
                                    name=name)
        # the products carry the names, not the normed latents: a norm's
        # backward reads its input, so keeping what it returns would spare
        # the second forward the norm and not the product
        cq = norm("q_norm")(checkpoint_name(
            dense(self.q_lora_rank, "wq_a")(x), "mla_q_a"))
        q = dense(H * (nope + rp), "wq_b")(cq).reshape(B, L, H, nope + rp)
        kv = checkpoint_name(dense(self.kv_lora_rank + rp, "wkv_a")(x),
                             "mla_kv_a")
        ckv = norm("kv_norm")(kv[..., :self.kv_lora_rank])
        k_rope = rope(kv[..., None, self.kv_lora_rank:], self.rope_theta)
        kv = dense(H * (nope + vd), "wkv_b")(ckv).reshape(B, L, H, nope + vd)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], self.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, L, H, rp))],
            axis=-1)
        v = kv[..., nope:]
        bhld = lambda t: t.transpose(0, 2, 1, 3)
        if self.kind == "flash":
            if nope + rp != vd:
                raise ValueError(
                    "flash attention wants one head width for q, k and v; "
                    f"got {nope + rp} and {vd} (use model.attention=dense)")
            from dnn_page_vectors_tpu.ops.flash_attention import (
                flash_attention)
            with jax.named_scope("mla.flash"):
                out = flash_attention(bhld(q), bhld(k), bhld(v), pad_mask,
                                      block_q=_FLASH_BLOCK,
                                      block_kv=_FLASH_BLOCK, causal=True)
            out = bhld(out.astype(self.dtype))
        elif self.kind == "dense":
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
                / np.sqrt(nope + rp)
            pos = jnp.arange(L)
            allowed = (pos[None, :] <= pos[:, None])[None, None] \
                & pad_mask[:, None, None, :]
            s = jnp.where(allowed, s, jnp.asarray(-1e9, jnp.float32))
            out = jnp.einsum("bhqk,bkhd->bqhd",
                             nn.softmax(s, axis=-1).astype(self.dtype), v)
        else:
            raise ValueError(f"unknown attention kind {self.kind!r} for the "
                             "latent-attention tower (want dense | flash)")
        return dense(self.model_dim, "wo")(out.reshape(B, L, H * vd))


class RoutedExperts(nn.Module):
    """The expert layer's FFN: router over all `n_routed_experts`, the held
    experts' grouped SwiGLU, one shared expert."""
    model_dim: int
    mlp_dim: int                  # width of every expert
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    experts_held: int
    experts_held_start: int
    dtype: jnp.dtype = jnp.bfloat16
    router: str = "sigmoid_noaux_tc"    # how scores become a selection
    shared_dim: int = 0                 # the shared expert's width; 0: mlp_dim
    # the shared expert's output times sigmoid(u W), W [d, 1]
    # (`shared_expert_gate`), a scalar a token: Qwen3-Next's
    shared_gate: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """[B, L, d] -> (FFN(x), this call's counters: `held` [experts_held]
        assignments per held expert, `absent`, `dropped`, `worst_case`:
        1 where the routing needed the worst-case buffers)."""
        B, L, d = x.shape
        E, k, H = (self.n_routed_experts, self.num_experts_per_tok,
                   self.experts_held)
        if not 0 < H <= E - self.experts_held_start:
            raise ValueError(f"experts held {H} from "
                             f"{self.experts_held_start} do not lie in the "
                             f"{E} routed experts")
        u = x.reshape(B * L, d)
        stacked = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        w_gate = self.param("w_gate", stacked, (H, d, self.mlp_dim))
        w_up = self.param("w_up", stacked, (H, d, self.mlp_dim))
        w_down = self.param("w_down", stacked, (H, self.mlp_dim, d))
        if self.router not in ROUTERS:
            raise ValueError(f"unknown router {self.router!r} "
                             f"(want one of {ROUTERS})")
        if self.router == "sigmoid_noaux_tc":
            bias = self.param("select_bias", nn.initializers.zeros, (E,))

        with jax.named_scope("moe.router"):
            logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              precision="highest",
                              name="router")(u.astype(jnp.float32))
            if self.router == "sigmoid_noaux_tc":
                s = jax.nn.sigmoid(logits)                    # [T, E] f32
                _, chosen = jax.lax.top_k(
                    s + jax.lax.stop_gradient(bias)[None, :], k)
                picked = jnp.take_along_axis(s, chosen, axis=1)   # [T, k]
                weight = self.routed_scaling_factor * picked / (
                    picked.sum(-1, keepdims=True) + 1e-20)
            else:   # softmax_topk: over the selected alone, held or not
                picked, chosen = jax.lax.top_k(logits, k)
                weight = jax.nn.softmax(picked, axis=-1)
        # The sorted buffers have room for the load this chip expects; a
        # call whose routing needs more goes over buffers with room for the
        # worst case, and is counted (`_routed`). Where the two sizes are
        # one (half of the experts held, or more) there is one path.
        tiles = gm.expected_tiles(B * L, k, H, E, _EXPERT_TILE)
        worst = gm.num_tiles(B * L, k, H, _EXPERT_TILE)
        with jax.named_scope("moe.dispatch"):
            plan = gm.plan_rows(chosen, self.experts_held_start, H,
                                _EXPERT_TILE, tiles)
        kernels = (w_gate, w_up, w_down)
        if tiles == worst:
            fallback = jnp.zeros((), jnp.int32)
            with jax.named_scope("moe.experts"):
                kernels = tuple(w.astype(self.dtype) for w in kernels)
            routed, placed = _routed_part(_row_ops(), u, weight, kernels,
                                          plan)
        else:
            fallback = 1 - gm.fits(plan).astype(jnp.int32)
            routed, placed = _routed(_row_ops(), worst, u, weight, kernels,
                                     plan)
        with jax.named_scope("moe.shared"):
            shared = SwiGlu(self.shared_dim or self.mlp_dim, d,
                            dtype=self.dtype, name="shared")(u)
            if self.shared_gate:
                shared = shared * jax.nn.sigmoid(nn.Dense(
                    1, use_bias=False, dtype=self.dtype,
                    name="shared_expert_gate")(u))
        stats = {"held": plan.sizes, "absent": plan.absent,
                 "dropped": B * L * k - plan.absent - placed,
                 "worst_case": fallback}
        return (shared + routed.astype(self.dtype)).reshape(B, L, d), stats


def _row_ops() -> tuple:
    """The tile and the three operations of `ops/grouped_matmul.py` as the
    module has them at the call: the static argument `how` of the jitted
    parts below, so that a caller who replaces one (the tests plant faults
    so, and pick the kernels' mode) gets a trace of its own."""
    return _EXPERT_TILE, gm.permute, gm.grouped_matmul, gm.unpermute


@functools.partial(jax.jit, static_argnums=0)
def _routed_part(how: tuple, u, weight, kernels, plan: gm.RowPlan):
    """Dispatch -> the held experts' grouped SwiGLU -> combine, over a
    buffer of the plan's size: ([T, d] float32, the rows it placed).
    Jitted (as `_pull` is) so that the expert layers of a tower, which call
    it with the same shapes, are traced and lowered once: a step program
    holds it for two buffer sizes, forward and backward."""
    tile, permute, grouped_matmul, unpermute = how
    w_gate, w_up, w_down = kernels
    with jax.named_scope("moe.dispatch"):
        rows = permute(u, plan)
    with jax.named_scope("moe.experts"):
        mm = lambda a, w: grouped_matmul(a, w, plan, tile)
        rows = mm(nn.silu(mm(rows, w_gate)) * mm(rows, w_up), w_down)
    with jax.named_scope("moe.combine"):
        routed = unpermute(rows, weight, plan)                # [T, d] f32
    return routed, plan.valid.sum(dtype=jnp.int32)


@functools.partial(jax.jit, static_argnums=0)
def _pull(how: tuple, u, weight, kernels, plan: gm.RowPlan, g_routed):
    """`_routed_part`'s forward again, and its cotangents for u, weight and
    kernels."""
    _, vjp, _ = jax.vjp(lambda *a: _routed_part(how, *a, plan), u, weight,
                        kernels, has_aux=True)
    return vjp(g_routed)


def _either_size(how: tuple, worst: int, part):
    """The two branches of a `cond` over the buffer's size: `part`
    (`_routed_part` or `_pull`: the plan is their fourth array argument)
    with the plan as it is, and with the plan laid out over `worst` tiles.
    Each ends in a barrier: XLA otherwise moves what the two branches have
    in common out of the `cond` (the weighted sums over k and their masks,
    [T, k, d] arrays, become outputs of it, written and read back) and
    compiles the step to other roundings than the one-path program's."""
    def whole(u, weight, kernels, plan, *rest):
        with jax.named_scope("moe.dispatch"):
            plan = gm.with_tiles(plan, how[0], worst)
        return part(how, u, weight, kernels, plan, *rest)

    held = lambda f: lambda *a: jax.lax.optimization_barrier(f(*a))
    return held(functools.partial(part, how)), held(whole)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed(how: tuple, worst: int, u, weight, kernels, plan: gm.RowPlan):
    """`_routed_part` over the plan's buffer where the routing fits it, and
    over one of `worst` tiles where it does not: the same rows in the same
    tiles either way. `kernels` as they are stored (float32).

    It carries its own vjp because JAX differentiates a `cond` by handing
    every branch's residuals out of it, each branch writing zeros for the
    other's: the expected branch would write worst-case-sized zeros on
    every call, and copies of the operands that both save. Here nothing
    crosses a `cond`: the backward pass is a second `cond` whose branches
    run their forward again and pull the cotangent back. Under
    `model.remat_blocks` that forward is the one the block's recomputation
    would run anyway; without it, it is one forward more."""
    return jax.lax.cond(gm.fits(plan),
                        *_either_size(how, worst, _routed_part),
                        u, weight, kernels, plan)


def _routed_fwd(how, worst, u, weight, kernels, plan):
    return (_routed(how, worst, u, weight, kernels, plan),
            (u, weight, kernels, plan))


def _routed_bwd(how, worst, res, g):
    u, weight, kernels, plan = res
    du, dweight, dkernels = jax.lax.cond(
        gm.fits(plan), *_either_size(how, worst, _pull),
        u, weight, kernels, plan, g[0])
    # The kernels' gradients leave the branches as the float32 sums the
    # kernel wrote and are rounded to the compute dtype here, as the
    # transpose of a cast to it rounds them: outside the `cond` XLA fuses
    # the rounding into the sum over the row groups, inside it is a pass of
    # its own over every kernel (the barrier keeps it outside).
    dkernels = jax.lax.optimization_barrier(dkernels)
    with jax.named_scope("moe.experts"):
        dkernels = tuple(d.astype(u.dtype).astype(d.dtype) for d in dkernels)
    return du, dweight, dkernels, None


_routed.defvjp(_routed_fwd, _routed_bwd)


class MixHalf(nn.Module):
    """x + MLA(norm(x)): the first half of a block."""
    sizes: GlmSizes
    deterministic: bool = True
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"

    @nn.compact
    def __call__(self, x, pad_mask):
        c = self.sizes
        with jax.named_scope("mla"):
            h = RmsNorm(dtype=self.dtype, eps=c.norm_eps, name="ln_attn")(x)
            h = MlaAttention(
                c.num_heads, c.model_dim, c.q_lora_rank, c.kv_lora_rank,
                c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                c.rope_theta, c.norm_eps, dtype=self.dtype,
                kind=self.attention_kind, name="attn")(h, pad_mask)
        return x + nn.Dropout(self.dropout)(
            h, deterministic=self.deterministic)


class FfnHalf(nn.Module):
    """x + FFN(norm(x)): the second half of a block, dense or routed;
    returns it with the routed layer's counters (None for a dense one)."""
    sizes: GlmSizes
    routed: bool
    deterministic: bool = True
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        h = RmsNorm(dtype=self.dtype, eps=c.norm_eps, name="ln_mlp")(x)
        stats = None
        if self.routed:
            with jax.named_scope("moe"):
                h, stats = RoutedExperts(
                    c.model_dim, c.moe_mlp_dim, c.n_routed_experts,
                    c.num_experts_per_tok, c.routed_scaling_factor,
                    c.experts_held, c.experts_held_start, dtype=self.dtype,
                    name="moe")(h)
        else:
            h = SwiGlu(c.mlp_dim, c.model_dim, dtype=self.dtype,
                       name="mlp")(h)
        return x + nn.Dropout(self.dropout)(
            h, deterministic=self.deterministic), stats


class Blocks(nn.Module):
    """All the blocks, for one group of rows; a scan body, (carry, (x,
    pad_mask)) -> (carry, (y, the expert layers' counters stacked by
    layer)). With `remat` each half block is recomputed in the backward
    pass from its input and the values `_KEPT` lists, which the first
    forward hands over: the backward then holds one half's other
    activations at a time and does not make a listed product twice.
    Everything else in a half (norms, the up-projections to heads, RoPE,
    flash's forward, the relayouts around it, the router, the plan, the
    routed part, a SwiGLU's elementwise pass) is made again."""
    sizes: GlmSizes
    num_layers: int
    remat: bool = False
    deterministic: bool = True
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"

    @nn.compact
    def __call__(self, carry, xs):
        x, pad_mask = xs
        mix, ffn = MixHalf, FfnHalf
        if self.remat:
            keep = jax.checkpoint_policies.save_only_these_names(*_KEPT)
            mix, ffn = (nn.remat(half, policy=keep) for half in (mix, ffn))
        common = dict(deterministic=self.deterministic, dropout=self.dropout,
                      dtype=self.dtype)
        stats = []
        for i in range(self.num_layers):
            x = mix(self.sizes, attention_kind=self.attention_kind,
                    name=f"block{i}_mix", **common)(x, pad_mask)
            x, st = ffn(self.sizes,
                        routed=i >= self.sizes.first_k_dense_replace,
                        name=f"block{i}_ffn", **common)(x)
            if st is not None:
                stats.append(st)
        stats = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats) \
            if stats else {}
        return carry, (x, stats)


class GlmMoeEncoder(nn.Module):
    vocab_size: int
    sizes: GlmSizes
    num_layers: int
    out_dim: int
    dropout: float = 0.0
    # recompute each half block in the backward, but for what `_KEPT` lists
    # (8.8 KB a token and expert layer, 2.7 KB for a dense one, beside the 8
    # KB of the halves' inputs): memory for tokens in flight, not for weights
    remat: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    attention_kind: str = "flash"
    # no field: what Trainer and BulkEmbedder ask a tower before they apply
    # it with the `moe_stats` collection mutable
    sows_moe_stats = True

    @nn.compact
    def __call__(self, ids: jnp.ndarray,
                 deterministic: bool = True) -> jnp.ndarray:
        # ids: [B, L], 0 = pad, pads at the end of the row.
        B, L = ids.shape
        pad_mask = ids > 0
        x = nn.Embed(self.vocab_size, self.sizes.model_dim, dtype=self.dtype,
                     name="tok_embed")(ids)
        # A block is independent row by row. With recomputation on, a long
        # batch goes through the blocks in groups of rows, in sequence, so
        # that only one group's activations are live at a time.
        groups = B * L // _ROW_GROUP_TOKENS
        if groups < 2 or B % groups or not self.remat:
            groups = 1
        blocks = Blocks if groups == 1 else nn.scan(
            Blocks, variable_broadcast="params",
            split_rngs={"params": False, "dropout": True})
        split = lambda a: a if groups == 1 else a.reshape(
            (groups, B // groups) + a.shape[1:])
        _, (x, stats) = blocks(
            self.sizes, self.num_layers, remat=self.remat,
            deterministic=deterministic, dropout=self.dropout,
            dtype=self.dtype, attention_kind=self.attention_kind,
            name="layers")(None, (split(x), split(pad_mask)))
        x = x.reshape((B,) + x.shape[-2:])
        if stats and not self.is_initializing():
            for key, v in stats.items():      # [groups,] layers, ...
                self.sow(STATS, key, v if groups == 1 else v.sum(0))
        x = RmsNorm(dtype=self.dtype, eps=self.sizes.norm_eps,
                    name="ln_final")(x)
        pooled = last_token(x.astype(jnp.float32), pad_mask)
        return nn.Dense(self.out_dim, dtype=jnp.float32, name="proj")(pooled)


def last_token(x: jnp.ndarray, pad_mask: jnp.ndarray) -> jnp.ndarray:
    """[B, L, d] -> [B, d]: the row of the last non-pad position (position 0
    for a row that is all pad)."""
    L = pad_mask.shape[1]
    last = jnp.max(jnp.where(pad_mask, jnp.arange(L)[None, :], 0), axis=1)
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
