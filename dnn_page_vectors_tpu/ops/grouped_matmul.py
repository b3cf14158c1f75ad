"""Grouped matrix product over the experts a chip holds (Pallas TPU), and the
row plan that feeds it.

A routed-expert layer multiplies each token-assignment's row by the kernel
of the expert it was sent to. Rows are laid out sorted by expert, each
expert's group padded up to whole row tiles (an empty group still gets one
tile of zero rows), so that a tile never straddles two experts and the
kernel needs no row masks:

    out[r] = x[r] @ w[expert_of_tile(r // tile_m)]

The number of assignments that land on the held experts is data, the buffer
is not. The kernels skip tiles past the last one in use: a skipped tile's
index maps point at the last tile in use, so it costs a grid step and neither
a DMA nor a matmul; its rows of the output are never written and never read
(`unpermute` reads only rows that hold an assignment). What XLA runs between
the kernels (the gathers, the elementwise passes, the plan's scatters) runs
over the whole buffer, so the buffer is sized for the load a chip expects:
`expected_tiles` has room for twice the held experts' even share of the
assignments, in whole tiles, plus one tile of padding a group. `plan_rows`
lays rows out in as many tiles as it is told and reports how many the routing
needs (`n_active`, `fits`); a caller whose routing needs more than the
expected buffer has takes the same rows over a buffer with room for the worst
case (`with_tiles` and `num_tiles`: every token sending min(k, held)
assignments here) and counts that call (models/glm_moe.py:RoutedExperts), so
no assignment is ever dropped.

Backward is two more launches of the same shape: dx = dy @ w[e]^T (the same
kernel with the kernel transposed in the dot), and dw[e] = sum over e's tiles
of x_tile^T @ dy_tile (tiles are sorted by expert, so consecutive grid steps
revisit one output block and accumulate into it).

`plan_rows` / `permute` / `unpermute` move rows between token order and that
layout with gathers only, forward and backward: the transpose of a gather is
a scatter-add, which a TPU serialises, so both carry their own vjp in which
the inverse permutation turns it back into a gather.

On CPU (tests) the kernels run in interpret mode automatically.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 64 * 1024 * 1024


class RowPlan(NamedTuple):
    """Where each token-assignment's row lives in the sorted buffer.

    dest [T, k] row of assignment (t, j), 0 where its expert is absent;
    held [T, k] whether the expert is held here; src [M] the token behind
    row r; valid [M] whether row r holds an assignment; sizes [H] rows per
    held expert; absent: assignments sent to experts that are not here;
    tile_group / tile_row / tile_first [n_tiles], n_active [1]: the grid's
    scalars (expert of tile i, its row block, whether it opens its group;
    tiles past n_active repeat the last one in use). n_active is what the
    routing needs, whatever the buffer holds: a plan is good for its buffer
    only where that many tiles are there (`fits`)."""
    dest: jnp.ndarray
    held: jnp.ndarray
    src: jnp.ndarray
    valid: jnp.ndarray
    sizes: jnp.ndarray
    absent: jnp.ndarray
    tile_group: jnp.ndarray
    tile_row: jnp.ndarray
    tile_first: jnp.ndarray
    n_active: jnp.ndarray


def num_tiles(tokens: int, top_k: int, held: int, tile_m: int) -> int:
    """Tiles that hold the worst case: sum_e max(ceil(n_e / tile_m), 1) is at
    most assignments / tile_m + held."""
    return -(-tokens * min(top_k, held) // tile_m) + held


def expected_tiles(tokens: int, top_k: int, held: int, experts: int,
                   tile_m: int) -> int:
    """Tiles for the load a chip expects: twice the held experts' even share
    of the assignments (tokens * top_k * held / experts rows), in whole
    tiles, plus one tile of padding a group; never more than the worst
    case."""
    return min(-(-2 * tokens * top_k * held // (experts * tile_m)) + held,
               num_tiles(tokens, top_k, held, tile_m))


def plan_rows(expert: jnp.ndarray, held_start: int, held: int, tile_m: int,
              n_tiles: Optional[int] = None) -> RowPlan:
    """`expert` [T, k] int32: the global expert index of every assignment.
    A stable counting sort by held expert (one-hot cumulative sums; no
    comparison sort), then each group padded to whole tiles, in a buffer of
    `n_tiles` tiles (default: the worst case). Rows that a smaller buffer
    has no room for are left out of `src` / `valid`: the caller checks
    `fits` before it uses such a plan."""
    T, k = expert.shape
    A = T * k
    if n_tiles is None:
        n_tiles = num_tiles(T, k, held, tile_m)
    local = expert.reshape(A) - held_start
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held)                     # [A]
    onehot = (key[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
    sizes = onehot.sum(0)                                     # [H]
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(1)
    starts, _ = _tile_spans(sizes, tile_m)
    dest = jnp.where(is_held,
                     starts[jnp.minimum(key, held - 1)] * tile_m + rank, 0)
    return _lay_out(dest.reshape(T, k).astype(jnp.int32),
                    is_held.reshape(T, k), sizes, A - sizes.sum(), tile_m,
                    n_tiles)


def with_tiles(plan: RowPlan, tile_m: int, n_tiles: int) -> RowPlan:
    """The same rows in a buffer of `n_tiles` tiles: where a row lies does
    not depend on the buffer's size, what the buffer holds does."""
    return _lay_out(plan.dest, plan.held, plan.sizes, plan.absent, tile_m,
                    n_tiles)


def fits(plan: RowPlan) -> jnp.ndarray:
    """Whether the plan's buffer has a tile for every tile the routing
    needs (scalar bool)."""
    return plan.n_active[0] <= plan.tile_row.shape[0]


def _tile_spans(sizes, tile_m: int):
    """First tile of each group and the one past its last: [H] each."""
    tiles = jnp.maximum(-(-sizes // tile_m), 1)
    ends = jnp.cumsum(tiles)
    return ends - tiles, ends


def _lay_out(dest, is_held, sizes, absent, tile_m: int,
             n_tiles: int) -> RowPlan:
    T, k = dest.shape
    held = sizes.shape[0]
    M = n_tiles * tile_m
    starts, ends = _tile_spans(sizes, tile_m)
    n_active = ends[-1]
    drop = jnp.where(is_held, dest, M).reshape(T * k)  # out of bounds: dropped
    token = jnp.arange(T * k, dtype=jnp.int32) // k
    src = jnp.zeros((M,), jnp.int32).at[drop].set(token, mode="drop")
    valid = jnp.zeros((M,), jnp.bool_).at[drop].set(True, mode="drop")
    i = jnp.arange(n_tiles, dtype=jnp.int32)
    active = i < n_active
    group = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                        held - 1).astype(jnp.int32)
    return RowPlan(
        dest=dest, held=is_held, src=src, valid=valid,
        sizes=sizes, absent=absent,
        tile_group=jnp.where(active, group, held - 1),
        tile_row=jnp.where(active, i, n_active - 1).astype(jnp.int32),
        tile_first=(active & (i == starts[group])).astype(jnp.int32),
        n_active=n_active.reshape(1).astype(jnp.int32))


# -- rows in and out of the sorted layout ------------------------------------

def _gather_rows(x, plan: RowPlan):
    return jnp.where(plan.valid[:, None], x[plan.src], 0)


@jax.custom_vjp
def permute(x, plan: RowPlan):
    """[T, d] token rows -> [M, d] sorted rows; rows that hold no assignment
    are zero."""
    return _gather_rows(x, plan)


def _permute_fwd(x, plan):
    return _gather_rows(x, plan), plan


def _permute_bwd(plan, g):
    return _picked_rows(g, plan).sum(1).astype(g.dtype), None


permute.defvjp(_permute_fwd, _permute_bwd)


def _picked_rows(rows, plan: RowPlan):
    """[T, k, d] float32: each assignment's row, zero where it is absent."""
    return jnp.where(plan.held[..., None], rows[plan.dest],
                     0).astype(jnp.float32)


def _weighted_sum(rows, weight, plan: RowPlan):
    return (_picked_rows(rows, plan) * weight[..., None]).sum(1)


@jax.custom_vjp
def unpermute(rows, weight, plan: RowPlan):
    """[M, d] sorted rows and [T, k] float32 weights -> [T, d] float32:
    y[t] = sum over t's held assignments of weight * its row."""
    return _weighted_sum(rows, weight, plan)


def _unpermute_fwd(rows, weight, plan):
    return _weighted_sum(rows, weight, plan), (rows, weight, plan)


def _unpermute_bwd(res, g):
    rows, weight, plan = res
    T, k = weight.shape
    w_rows = jnp.zeros(plan.valid.shape, jnp.float32).at[
        jnp.where(plan.held, plan.dest, plan.valid.shape[0]).reshape(T * k)
    ].set(weight.reshape(T * k), mode="drop")
    d_rows = jnp.where(plan.valid[:, None],
                       g[plan.src] * w_rows[:, None], 0).astype(rows.dtype)
    d_weight = (_picked_rows(rows, plan) * g[:, None, :]).sum(-1)
    return d_rows, d_weight, None


unpermute.defvjp(_unpermute_fwd, _unpermute_bwd)


# -- the kernels ---------------------------------------------------------------

def _gmm_kernel(group_ref, row_ref, first_ref, n_ref, x_ref, w_ref, o_ref,
                *, transpose_rhs: bool):
    del group_ref, row_ref, first_ref

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _tgmm_kernel(group_ref, row_ref, first_ref, n_ref, x_ref, g_ref, o_ref):
    del group_ref, row_ref
    i = pl.program_id(1)
    active = i < n_ref[0]

    def prod():
        return jax.lax.dot_general(                           # x^T @ g
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(active & (first_ref[i] == 1))
    def _():
        o_ref[0] = prod()

    @pl.when(active & (first_ref[i] == 0))
    def _():
        o_ref[0] += prod()


def _col_block(n: int, want: int) -> int:
    """The largest divisor of n that is at most `want` and a multiple of 128,
    or n itself."""
    if n <= want:
        return n
    for b in range(want - want % 128, 0, -128):
        if n % b == 0:
            return b
    return n


def _scalars(plan: RowPlan):
    return (plan.tile_group, plan.tile_row, plan.tile_first, plan.n_active)


def _gmm(x, w, plan: RowPlan, tile_m: int, transpose_rhs: bool,
         interpret: bool):
    """x [M, K] @ w[e] ([H, K, N], or [H, N, K] with transpose_rhs)."""
    M, K = x.shape
    N = w.shape[1] if transpose_rhs else w.shape[2]
    n_tiles = M // tile_m
    tn = _col_block(N, 2048)
    w_block = (1, tn, K) if transpose_rhs else (1, K, tn)
    w_map = ((lambda i, n, grp, row, first, na: (grp[i], n, 0))
             if transpose_rhs else
             (lambda i, n, grp, row, first, na: (grp[i], 0, n)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles, N // tn),
            in_specs=[
                pl.BlockSpec((tile_m, K),
                             lambda i, n, grp, row, first, na: (row[i], 0)),
                pl.BlockSpec(w_block, w_map),
            ],
            out_specs=pl.BlockSpec(
                (tile_m, tn), lambda i, n, grp, row, first, na: (row[i], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*_scalars(plan), x, w)


def _tgmm(x, g, plan: RowPlan, held: int, tile_m: int, interpret: bool):
    """dw[e] = sum over e's tiles of x_tile^T @ g_tile: [H, K, N] float32."""
    M, K = x.shape
    N = g.shape[1]
    n_tiles = M // tile_m
    tk = _col_block(K, 512)
    return pl.pallas_call(
        _tgmm_kernel,
        name="moe_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(K // tk, n_tiles),
            in_specs=[
                pl.BlockSpec((tile_m, tk),
                             lambda c, i, grp, row, first, na: (row[i], c)),
                pl.BlockSpec((tile_m, N),
                             lambda c, i, grp, row, first, na: (row[i], 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, N), lambda c, i, grp, row, first, na: (grp[i], c, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((held, K, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*_scalars(plan), x, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(x, w, plan, tile_m, interpret):
    return _gmm(x, w.astype(x.dtype), plan, tile_m, False, interpret)


def _gm_fwd(x, w, plan, tile_m, interpret):
    cast = w.astype(x.dtype)
    return (_gmm(x, cast, plan, tile_m, False, interpret),
            (x, cast, plan, jnp.zeros((0,), w.dtype)))


def _gm_bwd(tile_m, interpret, res, g):
    x, w, plan, stored = res
    dx = _gmm(g, w, plan, tile_m, True, interpret).astype(x.dtype)
    dw = _tgmm(x, g, plan, w.shape[0], tile_m, interpret).astype(stored.dtype)
    return dx, dw, None


_grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, plan: RowPlan,
                   tile_m: int, interpret: Optional[bool] = None
                   ) -> jnp.ndarray:
    """[M, K] rows in `plan`'s layout times the stacked kernels [H, K, N] of
    the experts held -> [M, N] in x's dtype (float32 accumulation). Rows of
    tiles past the last one in use are left unwritten. Kernels stored wider
    than x (float32 parameters under bfloat16 rows) are cast to x's dtype
    here, and their gradient comes back as wide as they are: the float32
    sums as `moe_tgmm` leaves them, where the transpose of a cast made by
    the caller would have rounded them to x's dtype."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _grouped_matmul(x, w, plan, tile_m, interpret)
