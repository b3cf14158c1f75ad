"""The causal flash kernels (KV-tiled, tiles above the diagonal skipped)
against dense causal attention at head width 256: forward and gradients,
with padding, at lengths that are and are not whole tiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_page_vectors_tpu.ops.flash_attention import (
    flash_attention, reference_attention)


@pytest.mark.parametrize("L,block,dtype", [(48, 16, jnp.float32),
                                            (40, 16, jnp.float32),
                                            (32, 32, jnp.float32),
                                            (48, 16, jnp.bfloat16)])
def test_causal_flash_equals_dense_causal(L, block, dtype):
    rng = np.random.default_rng(0)
    B, H, D = 2, 2, 256
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, L, D)), dtype)
               for _ in range(3))
    mask = jnp.asarray(np.arange(L)[None, :] < np.array([L, L - 11])[:, None])
    flash = lambda q, k, v: flash_attention(
        q, k, v, mask, causal=True, block_q=block, block_kv=block)
    dense = lambda q, k, v: reference_attention(q, k, v, mask, causal=True)
    # float32: rounding of a different summation order; bfloat16: the
    # kernel rounds p and ds to 8 bits of mantissa before their products
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    got, want = flash(q, k, v), dense(q, k, v)
    assert got.dtype == dtype
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) <= tol * 4
    w = jnp.asarray(rng.normal(size=want.shape), jnp.float32) \
        * mask[:, None, :, None]
    loss = lambda f: lambda *a: (f(*a).astype(jnp.float32) * w).sum()
    for a, b in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                    jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        scale = float(jnp.abs(b.astype(jnp.float32)).max())
        gap = float(jnp.abs(a.astype(jnp.float32)
                            - b.astype(jnp.float32)).max())
        assert gap <= tol * scale, (gap, scale)


def test_causal_flash_takes_no_bias_and_no_segments():
    q = jnp.zeros((1, 1, 8, 8))
    mask = jnp.ones((1, 8), bool)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, mask, bias=jnp.zeros((1, 8, 8)), causal=True)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, mask, seg=jnp.ones((1, 8), jnp.int32),
                        causal=True)


def test_future_keys_do_not_reach_the_past():
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, 32, 256)), jnp.float32)
               for _ in range(3))
    mask = jnp.ones((1, 32), bool)
    f = lambda k, v: flash_attention(q, k, v, mask, causal=True, block_q=8,
                                     block_kv=8)
    a = f(k, v)
    b = f(k.at[:, :, 20:].add(3.0), v.at[:, :, 20:].add(-2.0))
    np.testing.assert_array_equal(a[:, :, :20], b[:, :, :20])
    assert float(jnp.abs(a[:, :, 20:] - b[:, :, 20:]).max()) > 1e-3
