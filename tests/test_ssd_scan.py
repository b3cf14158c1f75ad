"""ops/ssd_scan.py: the chunked state-space scan against the token-by-token
recurrence, for lengths below, at and across chunk boundaries (and one that
no chunk divides), forward and gradients; and the planted fault (the state
dropped at chunk boundaries) shows as soon as a boundary is crossed."""
import jax
import jax.numpy as jnp
import pytest

from dnn_page_vectors_tpu.ops.ssd_scan import ssd_recurrence, ssd_scan

CHUNK = 8


def _inputs(L, B=2, H=4, P=8, N=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(k[0], (B, L, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, L, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (B, L, N)),
            jax.random.normal(k[4], (B, L, N)))


@pytest.mark.parametrize("L", [5, 8, 20, 40])
def test_chunked_scan_equals_the_recurrence(L):
    args = _inputs(L)
    want = ssd_recurrence(*args)
    got = ssd_scan(*args, CHUNK)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
    dropped = ssd_scan(*args, CHUNK, carry_state=False)
    gap = float(jnp.abs(dropped - want).max())
    assert (gap <= 1e-5 * scale) if L <= CHUNK else (gap > 1e-2 * scale)


@pytest.mark.parametrize("L", [5, 8, 20, 40])
def test_chunked_scan_has_the_recurrences_gradients(L):
    args = _inputs(L, seed=1)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(jnp.square(f(*a))),
                              argnums=(0, 1, 2, 3, 4))
    got = grad(lambda *a: ssd_scan(*a, CHUNK))(*args)
    want = grad(ssd_recurrence)(*args)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) <= 1e-5 * float(jnp.abs(w).max())


def test_bfloat16_operands_keep_a_float32_state():
    """bfloat16 operands into the products, float32 out: within bfloat16's
    rounding of the float32 answer, across four chunks."""
    x, d, a, b, c = _inputs(32, seed=2)
    want = ssd_recurrence(x, d, a, b, c)
    lo = lambda t: t.astype(jnp.bfloat16)
    got = ssd_scan(lo(x), d, a, lo(b), lo(c), CHUNK)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) <= 0.03 * float(
        jnp.abs(want).max())
