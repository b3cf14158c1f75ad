"""Ring attention: exact sequence/context parallelism over a 'seq' mesh axis.

Long pages are sharded along the sequence dimension across devices. Each
device keeps its local Q block resident and accumulates online-softmax
statistics (running max m, denominator l, f32 accumulator) against one KV
block at a time while `lax.ppermute` rotates the KV blocks (+ their padding
mask) around the ring — after axis_size steps every device has seen the full
global sequence and holds the exact attention output for its Q shard.
Communication rides ICI neighbor-to-neighbor (the ring), overlapping with
the per-block compute; peak memory per device is O(L_local) instead of O(L).

This is the TPU-native answer to the reference's long-context scaling
requirement: the collective is compiled by XLA (no user-level NCCL), and the
same function body runs under `jax.shard_map` on any ('data','model','seq')
mesh. Used by the transformer towers when model.attention == "ring".

T5 relative-position bias across the ring: materialising the global
[H, L, L] bias would reintroduce the O(L²) memory the ring removes, so each
step instead rebuilds its [L_loc, L_loc] bias block from global positions —
a device at ring position d processing ring step t holds the KV block of
device (d - t) mod n, so both sides' global offsets are known and the
bucket->table gather is recomputed per step in VMEM-sized pieces.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _ring_attention_local(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          kv_mask: jnp.ndarray,
                          bias_table: Optional[jnp.ndarray],
                          axis_name: str,
                          bucket_fn: Optional[Callable] = None) -> jnp.ndarray:
    """Per-shard body (runs under shard_map).

    q, k, v: [B, H, L_loc, Dh] local blocks; kv_mask: [B, L_loc];
    bias_table: optional [num_buckets, H] T5 relative-position table
    (replicated), with bucket_fn mapping signed distances to bucket ids.
    Returns [B, H, L_loc, Dh] float32 — the exact global-attention output
    for the local queries.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale = 1.0 / np.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32) * scale
    B, H, L, Dh = q.shape
    q_pos = my * L + jnp.arange(L)                           # global q rows

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        acc, m, l, k_cur, v_cur, mask_cur = carry
        s = jnp.einsum("bhld,bhsd->bhls", qf, k_cur.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        if bias_table is not None:
            # KV block now resident came from ring position (my - t) mod n
            kv_pos = ((my - t) % n) * L + jnp.arange(L)
            buckets = bucket_fn(kv_pos[None, :] - q_pos[:, None])  # [L, L]
            bias = bias_table[buckets]                       # [L, L, H]
            s = s + bias.transpose(2, 0, 1)[None].astype(jnp.float32)
        s = jnp.where(mask_cur[:, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhls,bhsd->bhld", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        # rotate KV + mask to the next device; overlaps with next compute
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = lax.ppermute(mask_cur, axis_name, perm)
        return (acc, m_new, l, k_nxt, v_nxt, mask_nxt), None

    acc0 = jnp.zeros((B, H, L, Dh), jnp.float32)
    m0 = jnp.full((B, H, L), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, L), jnp.float32)
    (acc, _, l, _, _, _), _ = lax.scan(
        step, (acc0, m0, l0, k, v, kv_mask), jnp.arange(n))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def ring_attention(mesh: Mesh, q: jnp.ndarray, k: jnp.ndarray,
                   v: jnp.ndarray, kv_mask: jnp.ndarray,
                   bias_table: Optional[jnp.ndarray] = None,
                   bucket_fn: Optional[Callable] = None,
                   seq_axis: str = "seq", batch_axis: Optional[str] = "data"
                   ) -> jnp.ndarray:
    """shard_map wrapper: q/k/v [B, H, L, Dh] with L sharded over `seq_axis`
    (and B over `batch_axis` if present in the mesh); kv_mask [B, L].
    bias_table [num_buckets, H] + bucket_fn enable the T5 variant (bias is
    rebuilt per ring step from global positions — see module docstring)."""
    n_seq = mesh.shape[seq_axis]
    if q.shape[2] % n_seq or k.shape[2] % n_seq:
        raise ValueError(
            f"ring attention: sequence length {q.shape[2]} must be divisible "
            f"by mesh axis '{seq_axis}' of size {n_seq}; pad "
            "data.page_len/query_len to a multiple of mesh.seq")
    if (bias_table is None) != (bucket_fn is None):
        raise ValueError("bias_table and bucket_fn must be given together")
    qkv_spec = P(batch_axis, None, seq_axis, None)
    mask_spec = P(batch_axis, seq_axis)
    fn = functools.partial(_ring_attention_local, axis_name=seq_axis,
                           bucket_fn=bucket_fn)
    if bias_table is None:
        fn_ = lambda q_, k_, v_, m_: fn(q_, k_, v_, m_, None)
        in_specs = (qkv_spec, qkv_spec, qkv_spec, mask_spec)
        args = (q, k, v, kv_mask)
    else:
        fn_ = fn
        in_specs = (qkv_spec, qkv_spec, qkv_spec, mask_spec, P())
        args = (q, k, v, kv_mask, bias_table)
    return jax.shard_map(
        fn_, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec,
        check_vma=False,
    )(*args)
