"""Pallas TPU flash attention for the transformer towers (encoder-only,
bidirectional, padding-masked, optional additive bias for T5 relative
positions).

Why a kernel: naive attention materialises [B, H, L, S] scores in HBM; for
long pages that array dominates HBM traffic. Here each grid program scores
one Q block against its FULL KV slice inside VMEM — the [block_q, S] score
tile never touches HBM, so HBM sees only Q, K, V and the output: the flash-
attention memory shape. Unlike GPU flash there is no online-softmax KV loop:
a [128, S] f32 tile fits VMEM to S ≈ 8k (this jax's Mosaic also lacks
in-kernel dynamic_slice, which a KV loop needs), and the exact one-shot
softmax is both simpler and faster at that scale. Beyond ~8k tokens the
sequence-parallel path (parallel/ring_attention.py) shards S over the mesh
'seq' axis, keeping each per-chip slice inside this kernel's bound. Matmuls
run on the MXU with f32 accumulation per /opt/skills/guides/pallas_guide.md.

Autodiff (VERDICT r1 #7): the backward is ALSO Pallas — kernels that
recompute attention probabilities per block from the saved log-sum-exp
(dq gridded over Q blocks, dk/dv gridded over KV blocks), so long-page
TRAINING keeps the flash memory shape too; no [B, H, L, S] tensor exists
in forward or backward. With a T5 relative-position `bias`, a third
kernel accumulates dbias[h,l,s] = sum_b ds[b,h,l,s] across a
batch-innermost sequential grid (VERDICT r3 Missing #3), so the biased
path also never materialises [B, H, L, S] — dbias itself is [H, L, S],
the same footprint as the bias input.

Sequence packing (train.pack_pages): the kernels optionally take packed-page
segment ids `seg` [B, L] — the q side rides lane-broadcast (the lse layout
trick), the kv side as a mask-like row, and each score tile is masked to
within-segment pairs by one broadcast compare in VMEM. The packed path
keeps the flash memory shape in forward and backward: no [B, L, S] segment
mask ever exists in HBM.

On CPU (tests, fake meshes) the kernels run in interpret mode automatically.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# Row vectors (lse, delta) are stored [B, H, L, _LSE_LANES] with the value
# broadcast across the trailing lane dim: Mosaic requires the last two block
# dims to be (sublane ÷ 8, lane ÷ 128) or equal to the array dims, so a
# [.., block_q] row-vector block is unlowerable ([.., block_q, 8] is fine —
# 8 lanes is the smallest legal trailing dim, kept small to bound HBM).
_LSE_LANES = 8


def reference_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        kv_mask: jnp.ndarray,
                        bias: Optional[jnp.ndarray] = None,
                        seg: Optional[jnp.ndarray] = None,
                        causal: bool = False) -> jnp.ndarray:
    """Plain-XLA attention; the kernel's oracle (and the bias-path backward).

    q: [B, H, L, Dh]; k, v: [B, H, S, Dh]; kv_mask: [B, S] (True = real
    token); bias: optional [H, L, S] additive (T5 relative positions);
    seg: optional [B, L(==S)] packed-page segment ids (0 = pad) — scores
    are additionally masked to within-segment pairs (sequence packing).
    Returns [B, H, L, Dh] float32.
    """
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhld,bhsd->bhls", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias[None].astype(jnp.float32)
    allowed = kv_mask[:, None, None, :]
    if seg is not None:
        allowed = allowed & ((seg[:, :, None] == seg[:, None, :])
                             & (seg > 0)[:, None, :])[:, None]
    if causal:
        L, S = s.shape[-2:]
        allowed = allowed & (jnp.arange(S)[None, :] <= jnp.arange(L)[:, None])
    s = jnp.where(allowed, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhls,bhsd->bhld", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _tile_mask(mask, sq_ref, sk_ref):
    """[rows, S] bool tile mask from the kv-pad row `mask` [1, S] plus,
    when segment refs are given (sequence packing), the within-segment
    restriction. sq_ref holds lane-broadcast q-side segment ids
    ([1, rows, LANE] view -> [rows, 1] column), sk_ref the kv-side row
    ([1, 1, S] view -> [1, S]); their broadcast equality is the
    block-diagonal packed-page mask, computed per score tile in VMEM —
    no [B, L, S] mask array ever exists in HBM."""
    ok = mask > 0                                            # [1, S]
    if sq_ref is None:
        return ok
    qs = sq_ref[0][:, 0:1]                                   # [rows, 1]
    ks = sk_ref[0]                                           # [1, S]
    return (qs == ks) & (ks > 0) & ok


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, bias_ref, sq_ref, sk_ref,
                  out_ref, lse_ref):
    # Block shapes (leading grid dims are 1):
    # q_ref: [1,1,BQ,Dh]; k_ref/v_ref: [1,1,S,Dh]; mask_ref: [1,1,S] int32;
    # bias_ref: [1,BQ,S] f32 or None; sq_ref: [1,BQ,LANE] int32 or None
    # (lane-broadcast q-side segment ids, same layout trick as lse_ref);
    # sk_ref: [1,1,S] int32 or None; out_ref: [1,1,BQ,Dh] f32;
    # lse_ref: [1,1,BQ,LANE] f32 (log-sum-exp, lane-broadcast — Mosaic's
    # tiling rule forbids row-vector [..,BQ] blocks, see _LSE_LANES).
    # All row statistics are kept 2D ([BQ,1], not [BQ]): Mosaic lowers 2D
    # vector ops; 1D shapes trip layout inference on real TPUs.
    bq = q_ref.shape[2]
    dh = q_ref.shape[3]
    scale = 1.0 / np.sqrt(dh)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)                      # [S, Dh]
    v = v_ref[0, 0].astype(jnp.float32)
    mask = mask_ref[0]                                       # [1, S] int32

    s = jax.lax.dot_general(                                 # [BQ, S]
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias_ref is not None:
        s = s + bias_ref[0]
    s = jnp.where(_tile_mask(mask, sq_ref, sk_ref), s, _NEG_INF)

    m = s.max(axis=1, keepdims=True)                         # [BQ,1]
    p = jnp.exp(s - m)                                       # [BQ, S]
    l = p.sum(axis=1, keepdims=True)                         # [BQ,1]
    acc = jax.lax.dot_general(                               # [BQ, Dh]
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # Fully-masked rows (all scores _NEG_INF): m == _NEG_INF, s - m == 0,
    # p == 1 everywhere, l == S — the output is mean(V), matching the
    # reference's uniform softmax over _NEG_INF scores (downstream pooling
    # masks those rows out; do NOT rely on zeros here). The epsilon only
    # guards l == 0, which cannot occur for S >= 1.
    out_ref[0, 0] = acc / jnp.maximum(l, 1e-30)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))                 # [BQ,1]
    lse_ref[0, 0] = jnp.broadcast_to(lse, (bq, lse_ref.shape[3]))


def _block_ds(q_ref, k_ref, v_ref, mask_ref, bias_ref, g_ref, lse_ref,
              delta_ref, sq_ref=None, sk_ref=None):
    """Recompute ds = p * (dp - delta) for one Q block against the full KV
    slice from the saved lse (no [B,H,L,S] in HBM). Shared by the dq and
    dbias kernels; returns (ds [BQ,S], k [S,Dh]) in float32.
    lse_ref/delta_ref: [1,1,BQ,LANE] lane-broadcast (see _LSE_LANES);
    sq_ref/sk_ref: optional segment ids (packing), same masking as fwd."""
    dh = q_ref.shape[3]
    scale = 1.0 / np.sqrt(dh)

    q = q_ref[0, 0].astype(jnp.float32)
    g = g_ref[0, 0].astype(jnp.float32)                       # [BQ, Dh]
    lse = lse_ref[0, 0][:, 0:1]                               # [BQ,1]
    delta = delta_ref[0, 0][:, 0:1]                           # [BQ,1]
    k = k_ref[0, 0].astype(jnp.float32)                       # [S, Dh]
    v = v_ref[0, 0].astype(jnp.float32)
    mask = mask_ref[0]                                        # [1, S]

    s = scale * jax.lax.dot_general(                          # [BQ, S]
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias_ref is not None:
        s = s + bias_ref[0]
    s = jnp.where(_tile_mask(mask, sq_ref, sk_ref), s, _NEG_INF)
    p = jnp.exp(s - lse)                                      # [BQ, S]
    dp = jax.lax.dot_general(                                 # g @ v^T
        g, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p * (dp - delta), k                                # ds, k


def _flash_dq_kernel(q_ref, k_ref, v_ref, mask_ref, sq_ref, sk_ref, g_ref,
                     lse_ref, delta_ref, dq_ref):
    # Unbiased path. Grid (B, H, Lp/BQ): one Q block vs the full KV slice.
    dh = q_ref.shape[3]
    scale = 1.0 / np.sqrt(dh)
    ds, k = _block_ds(q_ref, k_ref, v_ref, mask_ref, None, g_ref,
                      lse_ref, delta_ref, sq_ref, sk_ref)
    dq_ref[0, 0] = scale * jax.lax.dot_general(               # ds @ k
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _flash_dq_dbias_kernel(q_ref, k_ref, v_ref, mask_ref, bias_ref, sq_ref,
                           sk_ref, g_ref, lse_ref, delta_ref, dq_ref,
                           db_ref):
    # Biased path: ONE pass produces both dq and dbias from the same ds.
    # Grid (H, Lp/BQ, B) with the BATCH dim INNERMOST: dq's index map uses
    # all three dims, while db's drops b — consecutive grid steps revisit
    # the same [1, BQ, Sp] db block, and TPU grids run sequentially, so
    # `db += ds` accumulates the cross-batch reduction dbias[h,l,s] =
    # sum_b ds[b,h,l,s] without any [B,H,L,S] tensor — the piece the old
    # reference-VJP fallback re-materialised (VERDICT r3 Missing #3).
    dh = q_ref.shape[3]
    scale = 1.0 / np.sqrt(dh)
    ds, k = _block_ds(q_ref, k_ref, v_ref, mask_ref, bias_ref, g_ref,
                      lse_ref, delta_ref, sq_ref, sk_ref)
    dq_ref[0, 0] = scale * jax.lax.dot_general(               # ds @ k
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        db_ref[0] = ds

    @pl.when(b > 0)
    def _acc():
        db_ref[0] += ds


def _flash_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, bias_ref, sq_ref,
                      sk_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref):
    # Grid (B, H, Sp/BKV). Per program: one KV block vs the full Q slice.
    # sq_ref here is the FULL q-side segment column ([1, Lp, LANE] view),
    # sk_ref the KV block's segment row ([1, 1, BKV] view).
    dh = k_ref.shape[3]
    scale = 1.0 / np.sqrt(dh)

    k_blk = k_ref[0, 0].astype(jnp.float32)                   # [BKV, Dh]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    mask = mask_ref[0]                                        # [1, BKV]
    q = q_ref[0, 0].astype(jnp.float32)                       # [L, Dh]
    g = g_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, 0:1]                               # [L,1]
    delta = delta_ref[0, 0][:, 0:1]

    s = scale * jax.lax.dot_general(                          # [L, BKV]
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias_ref is not None:
        s = s + bias_ref[0]
    s = jnp.where(_tile_mask(mask, sq_ref, sk_ref), s, _NEG_INF)
    p = jnp.exp(s - lse)                                      # [L, BKV]
    dv_ref[0, 0] = jax.lax.dot_general(                       # p^T @ g
        p, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(                                 # g @ v^T
        g, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)                                     # [L, BKV]
    dk_ref[0, 0] = scale * jax.lax.dot_general(               # ds^T @ q
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash_attention(q, k, v, kv_mask, bias, seg, block_q, block_kv,
                     interpret):
    out, _ = _flash_forward(q, k, v, kv_mask, bias, seg, block_q, block_kv,
                            interpret)
    return out


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    kv_mask: jnp.ndarray, bias: Optional[jnp.ndarray] = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None,
                    seg: Optional[jnp.ndarray] = None,
                    causal: bool = False) -> jnp.ndarray:
    """Flash attention with optional T5 bias and optional packed-page
    segment ids `seg` [B, L] (sequence packing, train.pack_pages): scores
    are restricted to within-segment pairs, with the pairwise segment
    comparison computed per score tile inside the kernel — the packed
    path keeps the flash memory shape (no [B, L, S] mask in HBM) in
    forward AND backward. `causal=True` takes the KV-tiled kernels below
    (online softmax, tiles above the diagonal skipped)."""
    if causal:
        if bias is not None or seg is not None:
            raise ValueError("causal flash attention takes no bias and no "
                             "segment ids")
        return _flash_causal(q, k, v, kv_mask, min(block_q, block_kv),
                             interpret)
    return _flash_attention(q, k, v, kv_mask, bias, seg, block_q, block_kv,
                            interpret)


def _pad_inputs(q, k, v, kv_mask, bias, block_q, block_kv):
    B, H, L, Dh = q.shape
    S = k.shape[2]
    block_q = min(block_q, L)
    block_kv = min(block_kv, S)
    pad_l, pad_s = (-L) % block_q, (-S) % block_kv
    if pad_l:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_l), (0, 0)))
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad_s)))
    if bias is not None and (pad_l or pad_s):
        bias = jnp.pad(bias, ((0, 0), (0, pad_l), (0, pad_s)))
    return q, k, v, kv_mask, bias, block_q, block_kv, L, S


def _seg_operands(seg, Lp, Sp):
    """Kernel-ready segment operands from [B, L(==S)] ids: the q side is
    lane-broadcast to [B, Lp, _LSE_LANES] (the same Mosaic row-vector
    layout trick as lse), the kv side rides as a [B, 1, Sp] row like the
    pad mask. Pad ids are 0, which can never equal a real (>=1) segment,
    so padded tails mask themselves."""
    seg = seg.astype(jnp.int32)
    L = seg.shape[1]
    seg_q = seg if Lp == L else jnp.pad(seg, ((0, 0), (0, Lp - L)))
    seg_kv = seg if Sp == L else jnp.pad(seg, ((0, 0), (0, Sp - L)))
    seg_q = jnp.broadcast_to(seg_q[..., None],
                             seg_q.shape + (_LSE_LANES,))
    return seg_q, seg_kv[:, None, :]


# Single-device KV bound: each grid program holds the full [Sp, Dh] K/V
# slice plus a [block_q, Sp] f32 score tile in VMEM (~16 MB on v5e). Beyond
# this, Mosaic fails with an opaque allocation error, so raise a directed
# one instead (ADVICE r3). The BIASED path additionally holds [block_q, Sp]
# bias and (in backward) the revisited dbias output block — roughly 3x the
# per-program tile budget — so its bound is halved. The over-bound path is
# ring-attention sequence parallelism (parallel/ring_attention.py), which
# keeps each per-chip KV slice inside these bounds.
_MAX_KV_TOKENS = 8_192
_MAX_KV_TOKENS_BIASED = 4_096


def _flash_forward(q, k, v, kv_mask, bias, seg, block_q, block_kv,
                   interpret):
    """Returns (out [B,H,L,Dh] f32, lse [B,H,L] f32)."""
    if interpret is None:  # compiled on TPU, interpreted elsewhere
        interpret = jax.default_backend() != "tpu"
    (q, k, v, kv_mask, bias, block_q, block_kv, L, S) = _pad_inputs(
        q, k, v, kv_mask, bias, block_q, block_kv)
    B, H, Lp, Dh = q.shape
    Sp = k.shape[2]
    limit = _MAX_KV_TOKENS if bias is None else _MAX_KV_TOKENS_BIASED
    if not interpret and Sp > limit:
        raise ValueError(
            f"flash_attention: KV length {Sp} exceeds the single-device "
            f"VMEM bound (~{limit} tokens{' with bias' if bias is not None else ''}): "
            "the [block_q, S] score tile + full KV slice must fit VMEM. "
            "Shard the sequence over the mesh 'seq' axis instead "
            "(model.attention='ring', parallel/ring_attention.py), which "
            "keeps each per-chip KV slice inside this kernel's bound.")

    mask_i32 = kv_mask.astype(jnp.int32)[:, None, :]         # [B, 1, S]

    grid = (B, H, Lp // block_q)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, Sp, Dh), lambda b, h, i: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, Sp, Dh), lambda b, h, i: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, Sp), lambda b, h, i: (b, 0, 0)),
    ]
    args = [q, k, v, mask_i32]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((1, block_q, Sp), lambda b, h, i: (h, i, 0)))
        args.append(bias.astype(jnp.float32))
    if seg is not None:
        seg_q, seg_kv = _seg_operands(seg, Lp, Sp)
        in_specs.append(pl.BlockSpec((1, block_q, _LSE_LANES),
                                     lambda b, h, i: (b, i, 0)))
        in_specs.append(pl.BlockSpec((1, 1, Sp), lambda b, h, i: (b, 0, 0)))
        args.extend([seg_q, seg_kv])

    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref, m_ref = refs[:4]
        i = 4
        b_ref = None
        if bias is not None:
            b_ref = refs[i]
            i += 1
        sq_ref = sk_ref = None
        if seg is not None:
            sq_ref, sk_ref = refs[i], refs[i + 1]
            i += 2
        o_ref, l_ref = refs[i], refs[i + 1]
        _flash_kernel(q_ref, k_ref, v_ref, m_ref, b_ref, sq_ref, sk_ref,
                      o_ref, l_ref)

    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, _LSE_LANES),
                         lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lp, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Lp, _LSE_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out[:, :, :L], lse[:, :, :L, 0]


def _flash_backward(q, k, v, kv_mask, bias, seg, g, out, lse, block_q,
                    block_kv, interpret):
    """Pallas dq/dk/dv (+ dbias when `bias` is given) with per-block
    recompute from the saved lse. Returns (dq, dk, dv, db-or-None)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    in_dtypes = (q.dtype, k.dtype, v.dtype)
    bias_dtype = None if bias is None else bias.dtype
    (q, k, v, kv_mask, bias, block_q, block_kv, L, S) = _pad_inputs(
        q, k, v, kv_mask, bias, block_q, block_kv)
    B, H, Lp, Dh = q.shape
    Sp = k.shape[2]
    pad_l = Lp - L

    # delta_i = sum_d dO_i * O_i (the softmax-jacobian row term)
    delta = jnp.einsum("bhld,bhld->bhl", g.astype(jnp.float32), out)
    if pad_l:
        g = jnp.pad(g, ((0, 0), (0, 0), (0, pad_l), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_l)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_l)))
    mask_i32 = kv_mask.astype(jnp.int32)[:, None, :]
    # lane-broadcast the row vectors into Mosaic-lowerable layout
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (_LSE_LANES,))
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (_LSE_LANES,))
    bias_f = None if bias is None else bias.astype(jnp.float32)
    seg_q = seg_kv = None
    if seg is not None:
        seg_q, seg_kv = _seg_operands(seg, Lp, Sp)

    db = None
    if bias is None:
        qspec = pl.BlockSpec((1, 1, block_q, Dh),
                             lambda b, h, i: (b, h, i, 0))
        kfull = pl.BlockSpec((1, 1, Sp, Dh), lambda b, h, i: (b, h, 0, 0))
        rowspec = pl.BlockSpec((1, 1, block_q, _LSE_LANES),
                               lambda b, h, i: (b, h, i, 0))
        in_specs = [qspec, kfull, kfull,
                    pl.BlockSpec((1, 1, Sp), lambda b, h, i: (b, 0, 0))]
        args = [q, k, v, mask_i32]
        if seg is not None:
            in_specs.append(pl.BlockSpec((1, block_q, _LSE_LANES),
                                         lambda b, h, i: (b, i, 0)))
            in_specs.append(pl.BlockSpec((1, 1, Sp),
                                         lambda b, h, i: (b, 0, 0)))
            args.extend([seg_q, seg_kv])

        def dq_kernel(*refs):
            refs = list(refs)
            sq_ref = sk_ref = None
            i = 4
            if seg is not None:
                sq_ref, sk_ref = refs[4], refs[5]
                i = 6
            _flash_dq_kernel(refs[0], refs[1], refs[2], refs[3], sq_ref,
                             sk_ref, refs[i], refs[i + 1], refs[i + 2],
                             refs[i + 3])

        dq = pl.pallas_call(
            dq_kernel,
            name="flash_dq",
            grid=(B, H, Lp // block_q),
            in_specs=in_specs + [qspec, rowspec, rowspec],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct((B, H, Lp, Dh), jnp.float32),
            interpret=interpret,
        )(*args, g, lse, delta)
    else:
        # biased: ONE fused pass for dq + dbias, grid (H, Q-blocks, B) with
        # b innermost (see _flash_dq_dbias_kernel)
        qspec = pl.BlockSpec((1, 1, block_q, Dh),
                             lambda h, i, b: (b, h, i, 0))
        kfull = pl.BlockSpec((1, 1, Sp, Dh), lambda h, i, b: (b, h, 0, 0))
        rowspec = pl.BlockSpec((1, 1, block_q, _LSE_LANES),
                               lambda h, i, b: (b, h, i, 0))
        in_specs = [qspec, kfull, kfull,
                    pl.BlockSpec((1, 1, Sp), lambda h, i, b: (b, 0, 0)),
                    pl.BlockSpec((1, block_q, Sp),
                                 lambda h, i, b: (h, i, 0))]
        args = [q, k, v, mask_i32, bias_f]
        if seg is not None:
            in_specs.append(pl.BlockSpec((1, block_q, _LSE_LANES),
                                         lambda h, i, b: (b, i, 0)))
            in_specs.append(pl.BlockSpec((1, 1, Sp),
                                         lambda h, i, b: (b, 0, 0)))
            args.extend([seg_q, seg_kv])

        def dq_db_kernel(*refs):
            refs = list(refs)
            sq_ref = sk_ref = None
            i = 5
            if seg is not None:
                sq_ref, sk_ref = refs[5], refs[6]
                i = 7
            _flash_dq_dbias_kernel(refs[0], refs[1], refs[2], refs[3],
                                   refs[4], sq_ref, sk_ref, refs[i],
                                   refs[i + 1], refs[i + 2], refs[i + 3],
                                   refs[i + 4])

        dq, db = pl.pallas_call(
            dq_db_kernel,
            name="flash_dq_dbias",
            grid=(H, Lp // block_q, B),
            in_specs=in_specs + [qspec, rowspec, rowspec],
            out_specs=[qspec,
                       pl.BlockSpec((1, block_q, Sp),
                                    lambda h, i, b: (h, i, 0))],
            out_shape=[jax.ShapeDtypeStruct((B, H, Lp, Dh), jnp.float32),
                       jax.ShapeDtypeStruct((H, Lp, Sp), jnp.float32)],
            interpret=interpret,
        )(*args, g, lse, delta)
        db = db[:, :L, :S].astype(bias_dtype)

    kvspec = pl.BlockSpec((1, 1, block_kv, Dh), lambda b, h, j: (b, h, j, 0))
    qfull = pl.BlockSpec((1, 1, Lp, Dh), lambda b, h, j: (b, h, 0, 0))
    rowfull = pl.BlockSpec((1, 1, Lp, _LSE_LANES),
                           lambda b, h, j: (b, h, 0, 0))

    def dkv_kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref, m_ref = refs[:4]
        i = 4
        b_ref = None
        if bias is not None:
            b_ref = refs[i]
            i += 1
        sq_ref = sk_ref = None
        if seg is not None:
            sq_ref, sk_ref = refs[i], refs[i + 1]
            i += 2
        _flash_dkv_kernel(q_ref, k_ref, v_ref, m_ref, b_ref, sq_ref, sk_ref,
                          refs[i], refs[i + 1], refs[i + 2], refs[i + 3],
                          refs[i + 4])

    in_specs = [qfull, kvspec, kvspec,
                pl.BlockSpec((1, 1, block_kv), lambda b, h, j: (b, 0, j))]
    args = [q, k, v, mask_i32]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((1, Lp, block_kv), lambda b, h, j: (h, 0, j)))
        args.append(bias_f)
    if seg is not None:
        in_specs.append(pl.BlockSpec((1, Lp, _LSE_LANES),
                                     lambda b, h, j: (b, 0, 0)))
        in_specs.append(pl.BlockSpec((1, 1, block_kv),
                                     lambda b, h, j: (b, 0, j)))
        args.extend([seg_q, seg_kv])
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_dkv",
        grid=(B, H, Sp // block_kv),
        in_specs=in_specs + [qfull, rowfull, rowfull],
        out_specs=[kvspec, kvspec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sp, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, Sp, Dh), jnp.float32)],
        interpret=interpret,
    )(*args, g, lse, delta)

    dq = dq[:, :, :L].astype(in_dtypes[0])
    dk = dk[:, :, :S].astype(in_dtypes[1])
    dv = dv[:, :, :S].astype(in_dtypes[2])
    return dq, dk, dv, db


def _fwd(q, k, v, kv_mask, bias, seg, block_q, block_kv, interpret):
    out, lse = _flash_forward(q, k, v, kv_mask, bias, seg, block_q,
                              block_kv, interpret)
    return out, (q, k, v, kv_mask, bias, seg, out, lse)


def _bwd(block_q, block_kv, interpret, res, g):
    q, k, v, kv_mask, bias, seg, out, lse = res
    dq, dk, dv, db = _flash_backward(q, k, v, kv_mask, bias, seg, g, out,
                                     lse, block_q, block_kv, interpret)
    return dq, dk, dv, None, db, None


_flash_attention.defvjp(_fwd, _bwd)


# -- causal: KV-tiled, online softmax, tiles above the diagonal skipped -------
#
# Grid (B, H, q tiles, kv tiles) with square tiles; the innermost dimension
# runs in order, so the running max / sum / accumulator live in VMEM scratch
# and the output block is written when the diagonal tile is done. A tile
# above the diagonal (kv tile j > q tile i) is skipped by `pl.when`, and its
# index maps repeat the diagonal's block, so it costs a grid step and neither
# a DMA nor a matmul. Matmul operands keep the inputs' dtype (bfloat16 on the
# chip) with float32 accumulation; outputs and gradients are written in it.

def _causal_tile(mask_ref, i, j, block):
    """[block, block] bool: key visible to query (causal, and not pad)."""
    row = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    col = j * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return (col <= row) & (mask_ref[0] > 0)


def _scores(q, k, scale):
    return scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _causal_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                       m_sc, l_sc, acc_sc):
    i, j = pl.program_id(2), pl.program_id(3)
    block, dh = q_ref.shape[2], q_ref.shape[3]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        v = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], 1.0 / np.sqrt(dh))
        s = jnp.where(_causal_tile(mask_ref, i, j, block), s, _NEG_INF)
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == i)
    def _():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_sc[...] + jnp.log(l),
                                         (block, lse_ref.shape[3]))


def _causal_ds(q_ref, k_ref, v_ref, mask_ref, g_ref, lse_ref, delta_ref,
               i, j):
    """(p, ds) of one tile from the saved lse: [block, block] float32."""
    block, dh = q_ref.shape[2], q_ref.shape[3]
    s = _scores(q_ref[0, 0], k_ref[0, 0], 1.0 / np.sqrt(dh))
    s = jnp.where(_causal_tile(mask_ref, i, j, block), s, _NEG_INF)
    p = jnp.exp(s - lse_ref[0, 0][:, 0:1])
    dp = jax.lax.dot_general(                                 # g @ v^T
        g_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p, p * (dp - delta_ref[0, 0][:, 0:1])


def _causal_dq_kernel(q_ref, k_ref, v_ref, mask_ref, g_ref, lse_ref,
                      delta_ref, dq_ref, acc_sc):
    i, j = pl.program_id(2), pl.program_id(3)
    dh = q_ref.shape[3]

    @pl.when(j == 0)
    def _():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        k = k_ref[0, 0]
        _, ds = _causal_ds(q_ref, k_ref, v_ref, mask_ref, g_ref, lse_ref,
                           delta_ref, i, j)
        acc_sc[...] += jax.lax.dot_general(                   # ds @ k
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == i)
    def _():
        dq_ref[0, 0] = (acc_sc[...] / np.sqrt(dh)).astype(dq_ref.dtype)


def _causal_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, g_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_sc, dv_sc):
    # grid (B, H, kv tiles, q tiles): j is the kv tile, i the q tile
    j, i = pl.program_id(2), pl.program_id(3)
    dh = q_ref.shape[3]

    @pl.when(i == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    @pl.when(i >= j)
    def _():
        q, g = q_ref[0, 0], g_ref[0, 0]
        p, ds = _causal_ds(q_ref, k_ref, v_ref, mask_ref, g_ref, lse_ref,
                           delta_ref, i, j)
        dv_sc[...] += jax.lax.dot_general(                    # p^T @ g
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_sc[...] += jax.lax.dot_general(                    # ds^T @ q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dk_ref[0, 0] = (dk_sc[...] / np.sqrt(dh)).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _causal_params():
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))


def _causal_forward(q, k, v, kv_mask, block, interpret):
    """Returns (out [B,H,L,Dh] in q's dtype, lse [B,H,L] f32)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError("causal flash attention is self-attention: q, k and "
                         f"v share one shape, not {q.shape} {k.shape} "
                         f"{v.shape}")
    q, k, v, kv_mask, _, block, _, L, _ = _pad_inputs(
        q, k, v, kv_mask, None, block, block)
    B, H, Lp, Dh = q.shape
    n = Lp // block
    qspec = pl.BlockSpec((1, 1, block, Dh), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block, Dh),
                         lambda b, h, i, j: (b, h, jnp.minimum(i, j), 0))
    out, lse = pl.pallas_call(
        _causal_fwd_kernel,
        name="flash_fwd",
        grid=(B, H, n, n),
        in_specs=[qspec, kspec, kspec,
                  pl.BlockSpec((1, 1, block),
                               lambda b, h, i, j: (b, 0, jnp.minimum(i, j)))],
        out_specs=[qspec,
                   pl.BlockSpec((1, 1, block, _LSE_LANES),
                                lambda b, h, i, j: (b, h, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, H, Lp, Dh), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Lp, _LSE_LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, Dh), jnp.float32)],
        compiler_params=_causal_params(),
        interpret=interpret,
    )(q, k, v, kv_mask.astype(jnp.int32)[:, None, :])
    return out[:, :, :L], lse[:, :, :L, 0]


def _causal_backward(q, k, v, kv_mask, g, out, lse, block, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q, k, v, kv_mask, _, block, _, L, _ = _pad_inputs(
        q, k, v, kv_mask, None, block, block)
    B, H, Lp, Dh = q.shape
    n, pad = Lp // block, Lp - L
    delta = jnp.einsum("bhld,bhld->bhl", g.astype(jnp.float32),
                       out.astype(jnp.float32))
    g = g.astype(q.dtype)
    if pad:
        g = jnp.pad(g, ((0, 0), (0, 0), (0, pad), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad)))
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (_LSE_LANES,))
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (_LSE_LANES,))
    mask = kv_mask.astype(jnp.int32)[:, None, :]
    tile = lambda pick: pl.BlockSpec(
        (1, 1, block, Dh), lambda b, h, x, y: (b, h, pick(x, y), 0))
    row = lambda pick: pl.BlockSpec(
        (1, 1, block, _LSE_LANES), lambda b, h, x, y: (b, h, pick(x, y), 0))
    mask_spec = lambda pick: pl.BlockSpec(
        (1, 1, block), lambda b, h, x, y: (b, 0, pick(x, y)))
    shape = jax.ShapeDtypeStruct((B, H, Lp, Dh), q.dtype)
    acc = pltpu.VMEM((block, Dh), jnp.float32)

    # dq: grid (.., q tile i, kv tile j); kv blocks stop at the diagonal
    mine = lambda i, j: i
    upto = lambda i, j: jnp.minimum(i, j)
    dq = pl.pallas_call(
        _causal_dq_kernel,
        name="flash_dq",
        grid=(B, H, n, n),
        in_specs=[tile(mine), tile(upto), tile(upto), mask_spec(upto),
                  tile(mine), row(mine), row(mine)],
        out_specs=tile(mine),
        out_shape=shape,
        scratch_shapes=[acc],
        compiler_params=_causal_params(),
        interpret=interpret,
    )(q, k, v, mask, g, lse, delta)

    # dk, dv: grid (.., kv tile j, q tile i); q blocks start at the diagonal
    mine = lambda j, i: j
    frm = lambda j, i: jnp.maximum(i, j)
    dk, dv = pl.pallas_call(
        _causal_dkv_kernel,
        name="flash_dkv",
        grid=(B, H, n, n),
        in_specs=[tile(frm), tile(mine), tile(mine), mask_spec(mine),
                  tile(frm), row(frm), row(frm)],
        out_specs=[tile(mine), tile(mine)],
        out_shape=[shape, shape],
        scratch_shapes=[acc, acc],
        compiler_params=_causal_params(),
        interpret=interpret,
    )(q, k, v, mask, g, lse, delta)
    return dq[:, :, :L], dk[:, :, :L], dv[:, :, :L]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_causal(q, k, v, kv_mask, block, interpret):
    return _causal_forward(q, k, v, kv_mask, block, interpret)[0]


# The names the causal forward's two outputs carry into the residuals: a
# recomputation whose policy lists them (`jax.checkpoint_policies.
# save_only_these_names`) keeps both and does not launch `flash_fwd` a second
# time. Outside a recomputation `checkpoint_name` is the identity. (The
# sparse tower's list, `models/glm_moe.py:_KEPT`, leaves them out for now:
# its step program has no room for 10 KB a token and layer.)
CAUSAL_RESIDUALS = ("flash_out", "flash_lse")


def _causal_fwd(q, k, v, kv_mask, block, interpret):
    out, lse = _causal_forward(q, k, v, kv_mask, block, interpret)
    out, lse = map(checkpoint_name, (out, lse), CAUSAL_RESIDUALS)
    return out, (q, k, v, kv_mask, out, lse)


def _causal_bwd(block, interpret, res, g):
    q, k, v, kv_mask, out, lse = res
    return (*_causal_backward(q, k, v, kv_mask, g, out, lse, block,
                              interpret), None)


_flash_causal.defvjp(_causal_fwd, _causal_bwd)
