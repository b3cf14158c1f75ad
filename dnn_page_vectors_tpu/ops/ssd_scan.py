"""The state-space recurrence of a Mamba-2 mixer, computed in chunks (the
"state-space dual" form): chunked `jnp` products, no kernel of its own.

Per head h, with state S in R^{P x N}, S_0 = 0, a_t = delta_t A_h <= 0:

    S_t = exp(a_t) S_{t-1} + delta_t X_t B_t^T;    Y_t = S_t C_t

(`D X_t` is the caller's). B and C belong to a GROUP of heads: with G groups
head h reads group h // (H / G), and the [Q, Q] score tile below is one a
group. The body is one group's; more groups are that body mapped over the
group axis (`jax.vmap`: a batch dimension of every product). In chunks of Q
tokens that is, with cs the running sum of a inside a chunk:

  within a chunk   Y_i += sum_{j <= i} (C_i . B_j) exp(cs_i - cs_j) delta_j X_j
                   the masked-decay quadratic form: one [Q, Q] score tile a
                   chunk (shared by the heads), a decay tile a head, and a
                   [Q, Q] x [Q, P] product a head;
  a chunk's state  Z_c = sum_j exp(cs_last - cs_j) delta_j X_j B_j^T
  across chunks    S_in(c) = sum_{c' < c} exp(sum of a over chunks c'+1..c-1,
                   whole) Z_c'          (a [chunks, chunks] decay matrix: no loop)
  state to output  Y_i += exp(cs_i) S_in(c) C_i

`delta`, the decays and the carried state are float32; the operands of the
four products are in x's dtype (bfloat16 on the chip) with float32
accumulation. The chunk is how it is computed, not what: any chunk gives the
recurrence's Y up to rounding (tests/test_ssd_scan.py). It differentiates by
plain autodiff of these products.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _within(cs: jnp.ndarray) -> jnp.ndarray:
    """[..., Q] running sums -> [..., Q, Q] decays exp(cs_i - cs_j) for
    j <= i, 0 above the diagonal (masked before the exponential: above it
    the difference is positive and may overflow)."""
    Q = cs.shape[-1]
    diff = cs[..., :, None] - cs[..., None, :]
    keep = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


def _by_group(one_group, x, delta, a, b, c):
    """`one_group(x, delta, a, b, c)` (b and c [B, L, N]) on b and c
    [B, L, G, N]: the heads split into G runs of H / G, the group a batch
    axis of every product. [B, L, N], or one group, is the body itself."""
    if b.ndim == 3:
        return one_group(x, delta, a, b, c)
    B, L, H, P = x.shape
    G = b.shape[2]
    if H % G:
        raise ValueError(f"{H} heads do not split into {G} groups")
    if G == 1:
        return one_group(x, delta, a, b[:, :, 0], c[:, :, 0])
    y = jax.vmap(one_group, in_axes=(2, 2, 0, 2, 2), out_axes=2)(
        x.reshape(B, L, G, H // G, P), delta.reshape(B, L, G, H // G),
        a.reshape(G, H // G), b, c)
    return y.reshape(B, L, H, P)


def ssd_scan(x: jnp.ndarray, delta: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, chunk: int,
             carry_state: bool = True) -> jnp.ndarray:
    """x [B, L, H, P], delta [B, L, H] float32 (after the softplus),
    a [H] float32 (negative), b and c [B, L, G, N] ([B, L, N]: one group)
    -> Y [B, L, H, P] float32. `carry_state=False` drops the state at every
    chunk boundary: a planted fault for the tests, never a mode of the
    program."""
    return _by_group(
        lambda *one: _scan_group(*one, chunk, carry_state), x, delta, a, b, c)


def _scan_group(x, delta, a, b, c, chunk: int, carry_state: bool):
    """One group's heads: b and c [B, L, N]."""
    B, L, H, P = x.shape
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:     # delta 0: a padded step neither decays nor feeds the state
        x, delta, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                                  (t.ndim - 2)) for t in (x, delta, b, c))
    n = (L + pad) // Q
    dt = x.dtype
    f32 = jnp.float32
    mm = lambda eq, *ops: jnp.einsum(eq, *ops, preferred_element_type=f32)
    delta = delta.astype(f32).reshape(B, n, Q, H)
    x = x.reshape(B, n, Q, H, P)
    b, c = b.reshape(B, n, Q, -1), c.reshape(B, n, Q, -1)
    cs = jnp.cumsum(delta * a.astype(f32), axis=2)          # [B, n, Q, H]
    cs_h = cs.transpose(0, 1, 3, 2)                         # [B, n, H, Q]
    xd = (x.astype(f32) * delta[..., None]).astype(dt)      # delta_j X_j

    # within a chunk
    scores = mm("bnik,bnjk->bnij", c, b)                    # [B, n, Q, Q]
    m = (scores[:, :, None] * _within(cs_h)).astype(dt)     # [B, n, H, Q, Q]
    y = mm("bnhij,bnjhp->bnihp", m, xd)
    if n > 1 and carry_state:
        # each chunk's own state, and what reaches it from the chunks before
        to_end = jnp.exp(cs_h[..., -1:] - cs_h)             # [B, n, H, Q]
        z = mm("bnjhp,bnjk->bnhpk",
               (xd.astype(f32) * to_end.transpose(0, 1, 3, 2)[..., None]
                ).astype(dt), b)                            # [B, n, H, P, N]
        whole = cs_h[..., -1]                               # [B, n, H]
        # decay from the end of chunk c' to the start of chunk c > c'
        upto = jnp.cumsum(whole, axis=1)
        between = upto[:, :, None] - whole[:, :, None] - upto[:, None]
        reach = jnp.tril(jnp.ones((n, n), bool), -1)[None, :, :, None]
        decay = jnp.exp(jnp.where(reach, between, -jnp.inf))  # [B, n, n', H]
        s_in = jnp.einsum("bnmh,bmhpk->bnhpk", decay, z,
                          precision="highest")              # float32 state
        out = mm("bnik,bnhpk->bnihp", c, s_in.astype(dt))
        y = y + out * jnp.exp(cs)[..., None]
    return y.reshape(B, n * Q, H, P)[:, :L]


def ssd_recurrence(x, delta, a, b, c) -> jnp.ndarray:
    """The same Y token by token (`lax.scan` over L), float32 throughout:
    what the chunked form is tested against (b and c as `ssd_scan`'s)."""
    return _by_group(_recurrence_group, x, delta, a, b, c)


def _recurrence_group(x, delta, a, b, c):
    f32 = jnp.float32
    x, delta, b, c = (t.astype(f32) for t in (x, delta, b, c))
    B, L, H, P = x.shape

    def step(s, t):
        x_t, d_t, b_t, c_t = t
        s = jnp.exp(d_t * a)[..., None, None] * s + jnp.einsum(
            "bhp,bk->bhpk", x_t * d_t[..., None], b_t, precision="highest")
        return s, jnp.einsum("bhpk,bk->bhp", s, c_t, precision="highest")

    s0 = jnp.zeros((B, H, P, b.shape[-1]), f32)
    time_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, s0, tuple(map(time_major, (x, delta, b, c))))
    return jnp.moveaxis(y, 0, 1)
