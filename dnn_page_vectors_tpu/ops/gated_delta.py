"""The gated delta rule of a Gated DeltaNet layer, computed in chunks (the
WY / UT form): chunked `jnp` products and one `lax.scan` over the chunks, no
kernel of its own.

Per head, with state S in R^{K x V}, S_0 = 0, a log-decay g_t <= 0 and a
write strength beta_t in (0, 1), token by token:

    S <- exp(g_t) S;   S <- S + k_t (beta_t (v_t - S^T k_t))^T;   o_t = S^T q_t

In chunks of Q tokens, with gc the running sum of g inside a chunk (the
decays within a chunk, exp(gc_i - gc_j) for j <= i, masked before the
exponential as `ops/ssd_scan.py:_within` masks them):

  A  = -tril(diag(beta) K K^T * exp(gc_i - gc_j), -1)      strictly lower
  T  = (I - A)^-1, by blocks that double (below)
  W  = T (beta K * exp(gc));   U = T (beta V)
  per chunk, S the state it starts from:
       V' = U - W S
       O  = (Q * exp(gc)) S + (Q K^T * exp(gc_i - gc_j), lower with the
            diagonal) V'
       S <- exp(gc_last) S + (K * exp(gc_last - gc))^T V'

Everything that does not read S (A, T, W, U, the within-chunk scores) is
computed for all chunks at once; the scan carries S alone, two products a
chunk, and hands back each chunk's starting state and V', from which the
outputs are computed for all chunks at once again.

`g`, `beta`, the decays, T and the carried state are float32; the operands of
the other products are in q's dtype (bfloat16 on the chip) with float32
accumulation. T is made in float32 at full precision, by blocks that double:
with the inverse D of each diagonal block of b tokens known (b = 1: the
identity), the inverse over blocks of 2b is D + D A' D, A' the part of A that
links a block's second half to its first. Every product it takes is of
inverses of its own blocks, which stay as small as T's entries; the product
(I + A)(I + A^2)(I + A^4)... that gives T as well sums powers of A that grow
like binomial coefficients where keys repeat (a run of one token), and cancels
them in float32 to nothing of T (tests/test_gated_delta.py). The chunk is
how it is computed, not what: any chunk gives the recurrence's output up to
rounding (tests/test_gated_delta.py). It differentiates by plain autodiff of
these products and of the scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dnn_page_vectors_tpu.ops.ssd_scan import _within

CHUNK = 64      # tokens a chunk: how the rule is computed, not what


def gated_delta(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                g: jnp.ndarray, beta: jnp.ndarray, chunk: int = CHUNK,
                carry_state: bool = True):
    """q, k [B, L, H, K] (as the rule reads them: normalised and scaled by
    the caller), v [B, L, H, V], g and beta [B, L, H] float32 -> (O [B, L,
    H, V] float32, the final state [B, H, K, V] float32).
    `carry_state=False` drops the state at every chunk boundary: a planted
    fault for the tests, never a mode of the program."""
    B, L, H, K = q.shape
    V = v.shape[-1]
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:     # k 0, beta 0 and g 0: a padded step neither decays nor writes
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (t.ndim - 2)) for t in (q, k, v, g, beta))
    n = (L + pad) // Q
    dt = q.dtype
    f32 = jnp.float32
    mm = lambda eq, *ops: jnp.einsum(eq, *ops, preferred_element_type=f32)
    by_chunk = lambda t: t.reshape(B, n, Q, H, -1).transpose(0, 3, 1, 2, 4)
    q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)     # [B, H, n, Q, .]
    g, beta = (t.astype(f32).reshape(B, n, Q, H).transpose(0, 3, 1, 2)
               for t in (g, beta))                      # [B, H, n, Q]
    gc = jnp.cumsum(g, axis=-1)
    decay = _within(gc)                                 # [B, H, n, Q, Q]
    eye = jnp.eye(Q, dtype=f32)

    # within a chunk: T, W, U and the masked scores
    kk = mm("bhnik,bhnjk->bhnij", k, k)
    a = -jnp.tril(beta[..., :, None] * kk * decay, -1)
    t = _unit_lower_inverse(a, eye).astype(dt)
    w = mm("bhnij,bhnjk->bhnik", t, (k.astype(f32) * (
        beta * jnp.exp(gc))[..., None]).astype(dt))     # [B, H, n, Q, K]
    u = mm("bhnij,bhnjv->bhniv", t,
           (v.astype(f32) * beta[..., None]).astype(dt))    # [B, H, n, Q, V]
    scores = (mm("bhnik,bhnjk->bhnij", q, k) * decay).astype(dt)
    if not carry_state:
        out = mm("bhnij,bhnjv->bhniv", scores, u.astype(dt))
        state = jnp.zeros((B, H, K, V), f32)
        return _unchunk(out, B, L, H), state

    # across chunks: the state alone
    last = gc[..., -1]                                  # [B, H, n]
    to_end = (k.astype(f32) * jnp.exp(last[..., None] - gc)[..., None]
              ).astype(dt)                              # [B, H, n, Q, K]

    def step(s, xs):
        w_c, u_c, k_c, last_c = xs
        vp = u_c - mm("bhik,bhkv->bhiv", w_c, s.astype(dt))
        s_next = jnp.exp(last_c)[..., None, None] * s \
            + mm("bhik,bhiv->bhkv", k_c, vp.astype(dt))
        return s_next, (s, vp)

    chunk_major = lambda x: jnp.moveaxis(x, 2, 0)
    state, (s_in, vp) = jax.lax.scan(
        step, jnp.zeros((B, H, K, V), f32),
        (chunk_major(w.astype(dt)), chunk_major(u), chunk_major(to_end),
         chunk_major(last)))
    s_in, vp = (jnp.moveaxis(x, 0, 2) for x in (s_in, vp))
    qg = (q.astype(f32) * jnp.exp(gc)[..., None]).astype(dt)
    out = mm("bhnik,bhnkv->bhniv", qg, s_in.astype(dt)) \
        + mm("bhnij,bhnjv->bhniv", scores, vp.astype(dt))
    return _unchunk(out, B, L, H), state


def _unit_lower_inverse(a: jnp.ndarray, eye: jnp.ndarray) -> jnp.ndarray:
    """(I - A)^-1 of a strictly lower A [..., Q, Q], float32, by diagonal
    blocks that double: 2 (ceil(log2 Q) - 1) products of Q x Q."""
    Q = a.shape[-1]
    i = jnp.arange(Q)
    hi = lambda x, y: jnp.matmul(x, y, precision="highest")
    t, b = eye, 1
    while b < Q:
        # A's part from the first half of each block of 2b to its second
        link = ((i[:, None] // (2 * b) == i[None, :] // (2 * b))
                & (i[:, None] // b % 2 == 1) & (i[None, :] // b % 2 == 0))
        a_link = jnp.where(link, a, 0.0)
        t = eye + a_link if b == 1 else t + hi(hi(t, a_link), t)
        b *= 2
    return t


def _unchunk(out, B, L, H):
    """[B, H, n, Q, V] -> [B, L, H, V], the padding cut off."""
    out = out.transpose(0, 2, 3, 1, 4)
    return out.reshape(B, -1, H, out.shape[-1])[:, :L]


def gated_delta_recurrence(q, k, v, g, beta):
    """The same output and final state token by token (`lax.scan` over L),
    float32 throughout: what the chunked form is tested against."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    B, L, H, K = q.shape
    hi = dict(precision="highest")

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None, None] * s
        err = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, **hi)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * err, **hi)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, **hi)

    s0 = jnp.zeros((B, H, K, v.shape[-1]), f32)
    time_major = lambda t: jnp.moveaxis(t, 1, 0)
    state, o = jax.lax.scan(step, s0, tuple(map(time_major,
                                                (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), state
