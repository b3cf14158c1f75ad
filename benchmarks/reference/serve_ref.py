"""Plain reference of query serving: tokenize (the benchmark's plain
wordpiece encoder), the query tower in float32 at `highest` precision,
cosine scores against every stored row, exact top-k. The rows are made anew
from the store's seed, shard by shard on the device, by the same generator
the benchmark fed the store with; nothing is read back from the program."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import towers


def _store_rows(seed31, shard, rows: int, dim: int):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(7), seed31),
                             shard)
    v = jax.random.normal(key, (rows, dim), jnp.float32)
    v = v * jax.lax.rsqrt((v * v).sum(-1, keepdims=True))
    return v.astype(jnp.float16)


_store_rows_jit = jax.jit(_store_rows, static_argnums=(2, 3))


def make_shard(seed: int, shard: int, rows: int, dim: int):
    """[rows, dim] float16 unit vectors of one shard, from the seed."""
    return _store_rows_jit(int(seed) & 0x7FFFFFFF, shard, rows, dim)


def query_vectors(params: dict, ids, arch: dict, quant=towers.identity):
    """[n, L] token ids -> [n, D] unit query vectors."""
    fwd = jax.jit(functools.partial(
        towers.tower, variant=arch["variant"], num_layers=arch["layers"],
        num_heads=arch["heads"], quant=quant))
    out = [fwd(params["params"]["query_tower"], jnp.asarray(ids[s:s + 64]))
           for s in range(0, len(ids), 64)]
    return towers.l2_normalize(jnp.concatenate(out))


@functools.partial(jax.jit, static_argnums=(3,))
def _scan_shard(q, pages, local_ids, k):
    """Scores of one shard: its top-k, and the scores of the rows named by
    `local_ids` ([n, m], -1 where the row lies in another shard)."""
    s = jnp.matmul(q, pages.astype(jnp.float32).T, precision="highest")
    top_s, top_i = jax.lax.top_k(s, k)
    got = jnp.take_along_axis(s, jnp.clip(local_ids, 0), axis=1)
    return top_s, top_i, jnp.where(local_ids >= 0, got, -jnp.inf)


def exact_topk(q, seed: int, total_rows: int, shard_rows: int, dim: int,
               k: int, served_ids: np.ndarray):
    """(best scores [n, k] descending, their row ids, the reference's score
    of every served row [n, m]) over all `total_rows` rows."""
    n = q.shape[0]
    best_s = np.full((n, k), -np.inf, np.float32)
    best_i = np.full((n, k), -1, np.int64)
    served = np.full(served_ids.shape, -np.inf, np.float32)
    for shard, lo in enumerate(range(0, total_rows, shard_rows)):
        rows = min(shard_rows, total_rows - lo)
        pages = make_shard(seed, shard, rows, dim)
        local = np.where((served_ids >= lo) & (served_ids < lo + rows),
                         served_ids - lo, -1)
        ts, ti, got = _scan_shard(q, pages, jnp.asarray(local), k)
        cat_s = np.concatenate([best_s, np.asarray(ts)], axis=1)
        cat_i = np.concatenate([best_i, np.asarray(ti, np.int64) + lo],
                               axis=1)
        order = np.argsort(-cat_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(cat_s, order, axis=1)
        best_i = np.take_along_axis(cat_i, order, axis=1)
        served = np.maximum(served, np.asarray(got))
    return best_s, best_i, served
