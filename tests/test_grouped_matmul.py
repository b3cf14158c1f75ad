"""ops/grouped_matmul.py: the grouped product against a loop of plain
matmuls (forward and both gradients, with an empty group), and the row plan
(no assignment dropped, whatever the routing; the same rows in a buffer of
the expected load's size and in one of the worst case's)."""
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


def _grouped(x, w1, w2, wt, expert, start, tile, n_tiles=None):
    plan = G.plan_rows(jnp.asarray(expert), start, w1.shape[0], tile,
                       n_tiles)
    h = jax.nn.silu(G.grouped_matmul(G.permute(x, plan), w1, plan, tile))
    return G.unpermute(G.grouped_matmul(h, w2, plan, tile), wt, plan)


# the last two: a buffer of the expected load's size (11 tiles where the
# worst case takes 13, 7 where it takes 8) that this routing fits
@pytest.mark.parametrize("start,held,tile,n_tiles", [
    (2, 3, 8, None), (0, 8, 8, None), (5, 3, 16, None), (3, 1, 8, None),
    (2, 3, 8, 11), (5, 3, 16, 7)])
def test_grouped_product_equals_a_loop_of_matmuls(start, held, tile, n_tiles):
    rng, expert, x, wt = _inputs()
    w1 = jnp.asarray(rng.normal(size=(held, D, FF)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(held, FF, D)), jnp.float32)
    args = (x, w1, w2, wt)
    if n_tiles is not None:
        assert n_tiles == G.expected_tiles(T, K_TOP, held, E, tile) \
            < G.num_tiles(T, K_TOP, held, tile)
        assert bool(G.fits(G.plan_rows(jnp.asarray(expert), start, held,
                                       tile, n_tiles)))
    got = jax.jit(lambda *a: _grouped(*a, expert, start, tile, n_tiles))(
        *args)
    want = _loop(*args, expert, start)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    loss = lambda f: (lambda *a: jnp.sum(f(*a, expert, start) ** 2)
                      if f is _loop else
                      jnp.sum(f(*a, expert, start, tile, n_tiles) ** 2))
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


@pytest.mark.parametrize("tokens,top_k,held,experts,tile,want,worst", [
    (4096, 4, 8, 64, 256, 24, 72),     # a row group of the glm cell
    (1024, 4, 8, 64, 256, 12, 24),     # its queries
    (128, 4, 8, 64, 256, 9, 10),       # eight short queries
    (4096, 4, 64, 64, 256, 128, 128),  # all experts held: the worst case
    (4096, 4, 32, 64, 256, 96, 96),    # half of them: the worst case too
    (4096, 4, 16, 64, 256, 48, 80),    # a quarter
    (40, 2, 3, 8, 8, 11, 13)])
def test_expected_tiles_is_twice_the_even_share_plus_the_groups_padding(
        tokens, top_k, held, experts, tile, want, worst):
    assert G.num_tiles(tokens, top_k, held, tile) == worst
    assert G.expected_tiles(tokens, top_k, held, experts, tile) == want


# rows on the two held experts (of 8; 64 tokens, 2 a token, tiles of 8): the
# expected buffer has 2 * 32 / 8 + 2 = 10 tiles, the worst case 18
@pytest.mark.parametrize("loads,needs", [
    ((16, 16), 4), ((40, 40), 10), ((41, 40), 11), ((64, 64), 16),
    ((0, 0), 2)], ids=["under", "at", "one_over", "all_held", "none_held"])
def test_a_smaller_buffer_holds_the_same_rows_where_they_fit(loads, needs):
    tokens, start, held, tile = 64, 2, 2, 8
    expert = _routing(tokens, start, loads)
    small = G.expected_tiles(tokens, K_TOP, held, E, tile)
    worst = G.num_tiles(tokens, K_TOP, held, tile)
    assert (small, worst) == (10, 18)
    a = G.plan_rows(jnp.asarray(expert), start, held, tile, small)
    b = G.plan_rows(jnp.asarray(expert), start, held, tile)
    assert int(a.n_active[0]) == int(b.n_active[0]) == needs
    assert bool(G.fits(a)) == (needs <= small) and bool(G.fits(b))
    for name in ("dest", "held", "sizes", "absent"):     # not the buffer's
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    np.testing.assert_array_equal(a.sizes, loads)
    m = small * tile
    np.testing.assert_array_equal(a.valid, np.asarray(b.valid)[:m])
    np.testing.assert_array_equal(a.src, np.asarray(b.src)[:m])
    assert (int(a.valid.sum()) == sum(loads)) == bool(G.fits(a))
    if G.fits(a):           # the grid's scalars too, as far as it is used
        for name in ("tile_group", "tile_row", "tile_first"):
            np.testing.assert_array_equal(getattr(a, name)[:needs],
                                          getattr(b, name)[:needs])
    for x, y in zip(G.with_tiles(a, tile, worst), b):    # and back, whole
        np.testing.assert_array_equal(x, y)


def _routing(tokens, start, loads):
    """[tokens, 2] expert ids that put loads[0] rows on expert `start` (the
    first tokens) and loads[1] on `start + 1` (the last ones); the other
    choices go to experts 0 and 1, which are not held."""
    first = np.arange(tokens) < loads[0]
    second = np.arange(tokens) >= tokens - loads[1]
    return np.stack([np.where(first, start, 0),
                     np.where(second, start + 1, 1)], 1).astype(np.int32)
