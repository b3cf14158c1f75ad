"""ops/grouped_matmul.py: the grouped product against a loop of plain
matmuls (forward and both gradients, with an empty group), and the row plan
(no assignment dropped, whatever the routing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_page_vectors_tpu.ops import grouped_matmul as G

T, K_TOP, E, D, FF = 40, 2, 8, 16, 24


def _inputs(seed=0, empty=3):
    rng = np.random.default_rng(seed)
    expert = rng.integers(0, E, (T, K_TOP)).astype(np.int32)
    expert[expert == empty] = (empty + 2) % E        # an empty group
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    wt = jnp.asarray(rng.uniform(size=(T, K_TOP)), jnp.float32)
    return rng, expert, x, wt


def _loop(x, w1, w2, wt, expert, start):
    """sum over held experts of weight * silu(x w1[e]) w2[e], masked."""
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        hit = jnp.where(expert == start + e, wt, 0.0).sum(-1, keepdims=True)
        y = y + hit * (jax.nn.silu(x @ w1[e]) @ w2[e])
    return y


def _grouped(x, w1, w2, wt, expert, start, tile):
    plan = G.plan_rows(jnp.asarray(expert), start, w1.shape[0], tile)
    h = jax.nn.silu(G.grouped_matmul(G.permute(x, plan), w1, plan, tile))
    return G.unpermute(G.grouped_matmul(h, w2, plan, tile), wt, plan)


@pytest.mark.parametrize("start,held,tile", [(2, 3, 8), (0, 8, 8), (5, 3, 16),
                                             (3, 1, 8)])
def test_grouped_product_equals_a_loop_of_matmuls(start, held, tile):
    rng, expert, x, wt = _inputs()
    w1 = jnp.asarray(rng.normal(size=(held, D, FF)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(held, FF, D)), jnp.float32)
    args = (x, w1, w2, wt)
    got = jax.jit(lambda *a: _grouped(*a, expert, start, tile))(*args)
    want = _loop(*args, expert, start)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    loss = lambda f: (lambda *a: jnp.sum(f(*a, expert, start) ** 2)
                      if f is _loop else
                      jnp.sum(f(*a, expert, start, tile) ** 2))
    g_got = jax.jit(jax.grad(loss(_grouped), (0, 1, 2, 3)))(*args)
    g_want = jax.grad(loss(_loop), (0, 1, 2, 3))(*args)
    for a, b in zip(g_got, g_want):
        scale = float(jnp.abs(b).max()) + 1e-6
        assert float(jnp.abs(a - b).max()) <= 1e-5 * scale + 1e-4
    if held == 3 and start == 2:        # expert 3 is the empty group
        assert float(jnp.abs(g_got[1][1]).max()) == 0.0


@pytest.mark.parametrize("routing", ["uniform", "all_to_one", "none_held"])
def test_plan_places_every_held_assignment_once(routing):
    rng = np.random.default_rng(1)
    start, held, tile = 2, 4, 8
    if routing == "uniform":
        expert = rng.integers(0, E, (T, K_TOP))
    elif routing == "all_to_one":      # every token's first choice is one
        expert = np.stack([np.full(T, 3), rng.integers(4, 6, T)], 1)
    else:
        expert = np.stack([np.zeros(T), np.full(T, 7)], 1)
    plan = G.plan_rows(jnp.asarray(expert, jnp.int32), start, held, tile)
    is_held = (expert >= start) & (expert < start + held)
    assert int(plan.valid.sum()) == int(is_held.sum())       # none dropped
    assert int(plan.absent) == int((~is_held).sum())
    np.testing.assert_array_equal(
        plan.sizes, [(expert == start + e).sum() for e in range(held)])
    dest = np.asarray(plan.dest)[is_held]
    assert len(set(dest.tolist())) == len(dest)               # no collision
    np.testing.assert_array_equal(np.asarray(plan.src)[dest],
                                  np.nonzero(is_held)[0])
    # each row's tile belongs to its expert, and tiles are in expert order
    group = np.asarray(plan.tile_group)
    np.testing.assert_array_equal(group[dest // tile],
                                  expert[is_held] - start)
    n = int(plan.n_active[0])
    assert (np.diff(group[:n]) >= 0).all() and n <= len(group)
    assert int(np.asarray(plan.tile_first).sum()) == held
