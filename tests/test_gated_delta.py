"""ops/gated_delta.py: the chunked gated delta rule against the token-by-token
recurrence (outputs, final state, gradients), at several chunk lengths and
lengths that are no whole number of chunks; a state carried across chunks
that matters; bfloat16 operands with a float32 state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_page_vectors_tpu.ops.gated_delta import (gated_delta,
                                                  gated_delta_recurrence)


def _inputs(L, B=2, H=3, K=16, V=8, seed=0):
    """q and k L2-normalised (q scaled by K^-1/2, as the mixer hands them
    over), decays from strong to weak, beta in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, L, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, L, H, K)))
    v = jax.random.normal(ks[2], (B, L, H, V))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, L, H), minval=-4.0,
                                    maxval=1.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, H)))
    return q, k, v, g, beta


# float32 throughout: rounding of another order of summation
@pytest.mark.parametrize("L,chunk", [(64, 16), (64, 32), (64, 64),
                                     (100, 32), (37, 64), (130, 64)])
def test_chunked_rule_equals_the_recurrence(L, chunk):
    x = _inputs(L)
    out, state = gated_delta(*x, chunk)
    want, want_state = gated_delta_recurrence(*x)
    assert out.shape == want.shape and out.dtype == jnp.float32
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("L,chunk", [(100, 32), (48, 16)])
def test_chunked_rule_has_the_recurrences_gradients(L, chunk):
    x = _inputs(L, seed=1)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)[0])) \
        + jnp.sum(f(*a)[1] ** 2)
    got = jax.grad(loss(lambda *a: gated_delta(*a, chunk)),
                   argnums=(0, 1, 2, 3, 4))(*x)
    want = jax.grad(loss(gated_delta_recurrence), argnums=(0, 1, 2, 3, 4))(*x)
    for name, a, b in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("chunk", [32, 64])
def test_a_run_of_one_key_stays_the_recurrences(chunk):
    """A run of one token (a key repeated, decays weak, as padding or a
    repeated word gives them): T's entries stay small, while the powers of A
    grow like binomial coefficients; T made from those powers cancelled
    them in float32 to errors of 1e4 at a chunk of 64."""
    q, k, v, g, beta = _inputs(130, seed=3)
    k = k.at[:, 20:].set(k[:, 20:21])
    g = g.at[:, 20:].set(-1e-3)
    beta = beta.at[:, 20:].set(0.9)
    out, state = gated_delta(q, k, v, g, beta, chunk)
    want, want_state = gated_delta_recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5, rtol=1e-5)


def test_the_state_carried_across_chunks_matters():
    """Dropping the state at every chunk boundary (the planted fault) moves
    the output of every chunk but the first; one chunk has no boundary."""
    x = _inputs(96)
    want, _ = gated_delta_recurrence(*x)
    reset, _ = gated_delta(*x, 32, carry_state=False)
    np.testing.assert_allclose(reset[:, :32], want[:, :32], atol=2e-6)
    assert float(jnp.abs(reset[:, 32:] - want[:, 32:]).max()) > 1e-2
    whole, _ = gated_delta(*x, 96, carry_state=False)
    np.testing.assert_allclose(whole, want, atol=2e-6, rtol=1e-5)


def test_padded_steps_neither_decay_nor_write():
    """A length that is no whole number of chunks is padded with steps of
    k 0, beta 0 and g 0: the final state is the recurrence's own."""
    x = _inputs(40)
    _, state = gated_delta(*x, 32)
    _, want = gated_delta_recurrence(*x)
    np.testing.assert_allclose(state, want, atol=1e-5, rtol=1e-5)


def test_bfloat16_operands_keep_a_float32_state():
    x = _inputs(128, seed=2)
    low = [t.astype(jnp.bfloat16) for t in x[:3]] + list(x[3:])
    out, state = gated_delta(*low, 64)
    want, want_state = gated_delta_recurrence(*x)
    assert out.dtype == state.dtype == jnp.float32
    # 8 bits of mantissa in the operands of every product
    assert float(jnp.abs(out - want).max()) <= 0.02 * float(
        jnp.abs(want).max())
    assert float(jnp.abs(state - want_state).max()) <= 0.02 * float(
        jnp.abs(want_state).max())
