"""The causal flash kernels (KV-tiled, tiles above the diagonal skipped)
against dense causal attention at head width 256: forward and gradients,
with padding, at lengths that are and are not whole tiles."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_page_vectors_tpu.ops import flash_attention as fa
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


def test_the_residuals_names_are_inert_where_nothing_is_recomputed(
        monkeypatch):
    """`out` and `lse` go into the residuals under names that a
    recomputation's policy can list (models/glm_moe.py:Blocks). Without a
    recomputation they are the identity: the forward-only call lowers to a
    text that holds neither name, and values and gradients are those of the
    kernels with the names taken off, bit for bit."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 2, 40, 256)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(np.arange(40)[None, :] < np.array([40, 29])[:, None])

    def lower_and_run():
        f = lambda q, k, v: flash_attention(q, k, v, mask, causal=True,
                                            block_q=16, block_kv=16)
        loss = lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
        grad = jax.grad(loss, (0, 1, 2))
        # but for the numbers the lowering gives its private functions
        text, grad_text = (
            re.sub(r"@(\w+?)_\d+\b", r"@\1",
                   jax.jit(g).lower(q, k, v).as_text())
            for g in (f, grad))
        return text, grad_text, f(q, k, v), grad(q, k, v)

    text, grad_text, out, grads = lower_and_run()
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    text0, grad_text0, out0, grads0 = lower_and_run()
    assert fa.CAUSAL_RESIDUALS == ("flash_out", "flash_lse")
    for name in fa.CAUSAL_RESIDUALS:
        assert name not in text and name not in grad_text
    assert (text, grad_text) == (text0, grad_text0)
    np.testing.assert_array_equal(out, out0)
    for a, b in zip(grads, grads0):
        np.testing.assert_array_equal(a, b)
