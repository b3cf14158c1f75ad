"""Sharded mini-batch k-means: the IVF coarse quantizer (docs/ANN.md).

Trains `nlist` centroids over the vector store's L2-normalized rows with
the SAME memory contract as `ops/topk.py:topk_over_store`: one disk shard
at a time, row-sharded over the mesh 'data' axis, scored on the MXU. Each
pass streams shards through a shard_mapped scan — per chunk, one
[chunk, nlist] row-vs-centroid matmul picks assignments and one
one-hot-transpose matmul accumulates per-centroid sums — then psums the
[nlist, D] sums / [nlist] counts over ICI, so device memory never exceeds
O(chunk * max(D, nlist)) per device and host memory never exceeds one
shard plus the centroid matrix.

Spherical k-means: store rows are unit-normalized (the store invariant, so
retrieval is a pure dot product), and centroids are re-normalized after
every update — assignment by max dot product IS cosine assignment, and the
per-row int8 dequant scale factors out of the argmax entirely, so int8
codes ship to the device at 1 B/dim and only the accumulation pass pays
the widening.

Determinism (test-pinned, tests/test_ivf_index.py): seeded init sample,
seeded empty-cluster reseed, fixed shard/chunk reduction order — the same
store + seed produces byte-identical centroids on the same backend.
"""
from __future__ import annotations

from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dnn_page_vectors_tpu.ops.topk import stage_shard

_PASS_CACHE: Dict[Tuple, object] = {}


def _build_shard_pass(mesh: Mesh, nlist: int, chunk: int, scaled: bool,
                      choices: int = 1):
    """Jitted (rows[, scales], valid, centroids) -> (sums [nlist, D] f32,
    counts [nlist] f32, assign i32) with rows row-sharded over 'data' and
    sums/counts psummed (replicated). Assignments come back in global row
    order; padding rows (>= valid) carry assignment -1 and contribute
    nothing to sums/counts. `choices` > 1 returns each row's top-`choices`
    centroids [rows, choices] instead of the bare argmax [rows] — the
    balanced final-assignment sweep (docs/ANN.md) spills overflow rows to
    their next choice; sums/counts always accumulate the FIRST choice."""

    def run(rows_local, scales_local, valid, centroids):
        rows = rows_local.shape[0]
        shard = lax.axis_index("data")
        valid_local = jnp.clip(valid - shard * rows, 0, rows).astype(jnp.int32)
        c = min(chunk, rows)
        pad = (-rows) % c
        if pad:
            rows_local = jnp.concatenate(
                [rows_local,
                 jnp.zeros((pad, rows_local.shape[1]), rows_local.dtype)])
            if scales_local is not None:
                scales_local = jnp.concatenate(
                    [scales_local, jnp.zeros((pad,), scales_local.dtype)])
        n_chunks = rows_local.shape[0] // c
        blocks = rows_local.reshape(n_chunks, c, -1)
        sblocks = (None if scales_local is None
                   else scales_local.astype(jnp.float32).reshape(n_chunks, c))
        D = centroids.shape[1]
        # carry starts as a constant; pcast marks it varying over 'data' so
        # the scan's in/out types agree under shard_map (see ops/topk.py)
        init = jax.tree_util.tree_map(
            lambda x: lax.pcast(x, ("data",), to="varying"),
            (jnp.zeros((nlist, D), jnp.float32),
             jnp.zeros((nlist,), jnp.float32)))

        def body(carry, inp):
            sums, counts = carry
            ci, block, scl = inp                         # block: [c, D]
            rf = block.astype(jnp.float32)
            if scl is not None:                          # int8 dequant
                rf = rf * scl[:, None]
            s = jnp.matmul(rf, centroids.T,
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)  # [c, nlist]
            if choices > 1:
                _, a_top = lax.top_k(s, min(choices, nlist))
                a_top = a_top.astype(jnp.int32)
                a = a_top[:, 0]
            else:
                a = jnp.argmax(s, axis=1).astype(jnp.int32)
                a_top = a[:, None]
            ridx = ci * c + jnp.arange(c, dtype=jnp.int32)
            w = (ridx < valid_local).astype(jnp.float32)
            oh = jax.nn.one_hot(a, nlist, dtype=jnp.float32) * w[:, None]
            sums = sums + jnp.matmul(oh.T, rf,
                                     precision=lax.Precision.HIGHEST)
            counts = counts + oh.sum(axis=0)
            out = jnp.where((ridx < valid_local)[:, None], a_top, -1)
            return (sums, counts), (out if choices > 1 else out[:, 0])

        (sums, counts), assign = lax.scan(
            body, init,
            (jnp.arange(n_chunks, dtype=jnp.int32), blocks, sblocks))
        sums = lax.psum(sums, "data")
        counts = lax.psum(counts, "data")
        assign = (assign.reshape(-1, choices)[:rows] if choices > 1
                  else assign.reshape(-1)[:rows])
        return sums, counts, assign

    if scaled:
        fn = run
        in_specs = (P("data"), P("data"), P(), P())
    else:
        fn = lambda rows, valid, cents: run(rows, None, valid, cents)  # noqa: E731
        in_specs = (P("data"), P(), P())
    # psum makes sums/counts replicated — a dynamic fact the static
    # varying-axis checker can't infer (same escape hatch as sharded_topk)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=(P(), P(), P("data")),
                           check_vma=False)
    return jax.jit(mapped)


def shard_pass(pages, scales, valid: int, centroids, mesh: Mesh,
               nlist: int, chunk: int = 8192, choices: int = 1):
    """One staged shard through the assignment/accumulation pass. `pages`
    and `scales` come from ops.topk.stage_shard (stored width, row-sharded);
    `centroids` is a replicated [nlist, D] f32 array."""
    key = (mesh, int(nlist), int(chunk), scales is not None, int(choices))
    fn = _PASS_CACHE.get(key)
    if fn is None:
        fn = _PASS_CACHE[key] = _build_shard_pass(
            mesh, nlist, chunk, scales is not None, choices=choices)
    v = jnp.int32(valid)
    return (fn(pages, v, centroids) if scales is None
            else fn(pages, scales, v, centroids))


def _normalize(c: np.ndarray) -> np.ndarray:
    n = np.linalg.norm(c, axis=1, keepdims=True)
    return (c / np.maximum(n, 1e-12)).astype(np.float32)


def _kmeans_pp(pool: np.ndarray, nlist: int,
               rng: np.random.Generator) -> np.ndarray:
    """Seeded k-means++ (Arthur & Vassilvitskii 2007) over the sampled
    pool: each next seed is drawn with probability proportional to its
    cosine distance from the nearest already-chosen seed, so seeds spread
    across the data instead of clumping where the density is — measurably
    lower list imbalance at large nlist than uniform seeding (the ROADMAP
    open item; `init_imbalance` in the build stats shows the delta).
    Incremental O(nlist * pool * D): one pool-vs-new-seed matvec per seed,
    never a full distance matrix. Deterministic for a given (pool, rng
    state); an already-chosen row has distance 0 and is never re-drawn."""
    n = pool.shape[0]
    out = np.empty((nlist, pool.shape[1]), np.float32)
    first = int(rng.integers(0, n))
    out[0] = pool[first]
    best = pool @ out[0]                     # nearest-seed cosine sim [n]
    for j in range(1, nlist):
        d = np.maximum(1.0 - best, 0.0)      # cosine distance to nearest
        total = d.sum()
        if total <= 0.0:                     # degenerate pool: uniform draw
            nxt = int(rng.integers(0, n))
        else:
            nxt = int(rng.choice(n, p=d / total))
        out[j] = pool[nxt]
        best = np.maximum(best, pool @ out[j])
    return out


def sample_rows(store, n: int, seed: int) -> np.ndarray:
    """Seeded deterministic sample of up to `n` dequantized f32 rows,
    proportional per shard, in (shard, row) order — the k-means init set
    and the empty-cluster reseed pool."""
    N = store.num_vectors
    out = []
    for entry in store.shards():
        cnt = entry["count"]
        if cnt == 0:
            continue
        quota = min(cnt, max(1, -(-n * cnt // max(N, 1))))
        rng = np.random.default_rng([seed, entry["index"]])
        rows = np.sort(rng.choice(cnt, size=quota, replace=False))
        _, vecs = store._load_entry(entry)           # dequantized rows
        out.append(np.asarray(vecs[rows], np.float32))
    if not out:
        return np.zeros((0, store.dim), np.float32)
    return np.concatenate(out)[:n]


def _padded_rows(store, mesh: Mesh) -> int:
    """One static row count for every staged shard -> one compiled pass."""
    rows = max((s["count"] for s in store.shards()), default=0)
    return rows + (-rows) % max(mesh.shape["data"], 1)


def _iter_staged(store, mesh: Mesh, rows: int, sample_per_shard=None,
                 rng_key=None, entries=None):
    """Yield (entry, valid_n, pages, scales) for every non-empty shard,
    staged at stored width. With `sample_per_shard`, a seeded per-shard row
    subset (the mini-batch) is staged instead of the full shard. `entries`
    restricts the sweep to a shard subset (the incremental index update's
    O(new shards) path); disk reads run one shard ahead on a reader
    thread either way."""
    from dnn_page_vectors_tpu.infer.vector_store import read_ahead
    entries = store.shards() if entries is None else entries

    def _load():
        for e in entries:
            ids, vecs, scl = store._load_entry(e, raw=True)
            yield e, np.asarray(vecs), (None if scl is None
                                        else np.asarray(scl))

    for entry, vecs, scl in read_ahead(_load(), depth=1):
        n = vecs.shape[0]
        if n == 0:
            continue
        if sample_per_shard is not None and n > sample_per_shard:
            rng = np.random.default_rng([*rng_key, entry["index"]])
            take = np.sort(rng.choice(n, size=sample_per_shard,
                                      replace=False))
            vecs = np.asarray(vecs)[take]
            scl = None if scl is None else np.asarray(scl)[take]
            n = sample_per_shard
        pages, scales = stage_shard(vecs, rows, store.dim, mesh, scales=scl)
        yield entry, n, pages, scales


def train_kmeans(store, mesh: Mesh, nlist: int, iters: int = 8,
                 seed: int = 0, chunk: int = 8192,
                 sample_per_shard: Optional[int] = None,
                 init_sample: int = 65_536,
                 init: str = "kmeans++") -> Tuple[np.ndarray, Dict]:
    """Train `nlist` unit-norm centroids over the store. Returns
    (centroids [nlist, D] f32, stats). Deterministic for a given
    (store bytes, seed, mesh, backend, init). `init` is "kmeans++"
    (default: D²-spread seeds, lower imbalance at large nlist) or
    "random" (uniform pool draw, the pre-update behavior); stats record
    `init_imbalance` — the faiss imbalance factor of the FIRST assignment
    pass — next to the final one so the seeding's contribution is
    measurable (`cli index` reports the delta)."""
    N = store.num_vectors
    if N == 0:
        raise ValueError("cannot train k-means over an empty store")
    nlist = int(min(max(1, nlist), N))
    pool = sample_rows(store, max(nlist, min(init_sample, N)), seed)
    rng = np.random.default_rng(seed)
    if init == "kmeans++":
        centroids = _normalize(_kmeans_pp(pool, nlist, rng))
    elif init == "random":
        centroids = _normalize(
            pool[rng.choice(pool.shape[0], size=nlist, replace=False)])
    else:
        raise ValueError(f"unknown k-means init {init!r} "
                         "(want kmeans++ or random)")
    rows = _padded_rows(store, mesh)
    reseeded = 0
    init_imbalance = 0.0
    for it in range(max(1, iters)):
        sums = np.zeros((nlist, store.dim), np.float64)
        counts = np.zeros((nlist,), np.float64)
        cdev = jnp.asarray(centroids)
        for _, n, pages, scales in _iter_staged(
                store, mesh, rows, sample_per_shard=sample_per_shard,
                rng_key=(seed, 1 + it)):
            s, c, _ = shard_pass(pages, scales, n, cdev, mesh, nlist,
                                 chunk=chunk)
            sums += np.asarray(s, np.float64)
            counts += np.asarray(c, np.float64)
        if it == 0:                    # seeding quality, before any update
            tot = counts.sum()
            init_imbalance = float(nlist * np.square(counts).sum()
                                   / max(tot, 1.0) ** 2)
        new = centroids.astype(np.float64).copy()
        nz = counts > 0
        new[nz] = sums[nz] / counts[nz, None]
        empty = np.nonzero(~nz)[0]
        if empty.size:                 # reseed dead clusters from the pool
            r2 = np.random.default_rng([seed, 2, it])
            new[empty] = pool[r2.integers(0, pool.shape[0], empty.size)]
            reseeded += int(empty.size)
        centroids = _normalize(new.astype(np.float32))
    return centroids, {"nlist": nlist, "iters": int(max(1, iters)),
                       "reseeded": reseeded, "init": init,
                       "init_imbalance": round(init_imbalance, 4),
                       "trained_rows": int(N if sample_per_shard is None
                                           else min(N, sample_per_shard
                                                    * len(store.shards())))}


# -- grouped per-subspace k-means (the PQ codebook trainer, index/pq.py) ----

_GROUPED_CACHE: Dict[Tuple, object] = {}


def _build_grouped_pass(m: int, k: int, dsub: int, chunk: int):
    """Jitted (X3 [n, m, dsub], valid, C [m, k, dsub]) ->
    (sums [m, k, dsub] f32, counts [m, k] f32, assign [n, m] i32): one
    EUCLIDEAN assignment + one-hot-accumulation pass over every subspace
    at once, chunked through a lax.scan so device memory stays
    O(chunk * m * k) — the same mini-batch MXU discipline as the coarse
    quantizer above, minus the mesh (codebook pools are host-sample
    sized). Euclidean, not spherical: sub-vectors of unit-norm rows are
    NOT unit-norm, so argmin ||x-c||^2 = argmax (x.c - ||c||^2/2)."""

    def run(x3, valid, cb):
        n = x3.shape[0]
        cn = -0.5 * jnp.sum(cb.astype(jnp.float32) ** 2, axis=-1)  # [m, k]
        blocks = x3.reshape(n // chunk, chunk, m, dsub)

        def body(carry, inp):
            sums, counts = carry
            ci, blk = inp                               # blk [chunk, m, dsub]
            bf = blk.astype(jnp.float32)
            s = jnp.einsum("cmd,mkd->cmk", bf, cb,
                           precision=lax.Precision.HIGHEST) + cn[None]
            a = jnp.argmax(s, axis=-1).astype(jnp.int32)        # [chunk, m]
            ridx = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
            w = (ridx < valid).astype(jnp.float32)
            oh = jax.nn.one_hot(a, k, dtype=jnp.float32) * w[:, None, None]
            sums = sums + jnp.einsum("cmk,cmd->mkd", oh, bf,
                                     precision=lax.Precision.HIGHEST)
            counts = counts + oh.sum(axis=0)
            return (sums, counts), jnp.where(ridx[:, None] < valid, a, -1)

        init = (jnp.zeros((m, k, dsub), jnp.float32),
                jnp.zeros((m, k), jnp.float32))
        (sums, counts), assign = lax.scan(
            body, init,
            (jnp.arange(n // chunk, dtype=jnp.int32), blocks))
        return sums, counts, assign.reshape(-1, m)

    return jax.jit(run)


def _grouped_pass(x3: np.ndarray, valid: int, cb, chunk: int = 2048):
    n, m, dsub = x3.shape
    k = cb.shape[1]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        x3 = np.concatenate([x3, np.zeros((pad, m, dsub), x3.dtype)])
    key = (int(m), int(k), int(dsub), int(chunk))
    fn = _GROUPED_CACHE.get(key)
    if fn is None:
        fn = _GROUPED_CACHE[key] = _build_grouped_pass(m, k, dsub, chunk)
    sums, counts, assign = fn(jnp.asarray(x3), jnp.int32(valid),
                              jnp.asarray(cb, jnp.float32))
    return sums, counts, assign[:valid]


def grouped_kmeans(x3: np.ndarray, k: int, iters: int = 8, seed: int = 0,
                   chunk: int = 2048) -> Tuple[np.ndarray, Dict]:
    """Train `m` independent k-means codebooks — one per PQ subspace —
    over the pool `x3` [n, m, dsub], all subspaces per pass (index/pq.py,
    docs/ANN.md). Seeded and byte-deterministic for a given (pool bytes,
    k, iters, seed): seeded distinct-row init per subspace, seeded
    empty-cluster reseed, fixed chunk reduction order. Returns
    (codebooks [m, k, dsub] f32, stats)."""
    n, m, dsub = x3.shape
    if k > n:
        raise ValueError(f"PQ codebook k={k} exceeds pool size {n}")
    x3 = np.asarray(x3, np.float32)
    skey = (tuple(int(s) for s in seed)
            if isinstance(seed, (tuple, list)) else (int(seed),))
    rng = np.random.default_rng(skey)
    cb = np.stack([x3[np.sort(rng.choice(n, size=k, replace=False)), j]
                   for j in range(m)])                     # [m, k, dsub]
    reseeded = 0
    for it in range(max(1, iters)):
        sums, counts, _ = _grouped_pass(x3, n, cb, chunk=chunk)
        sums = np.asarray(sums, np.float64)
        counts = np.asarray(counts, np.float64)
        new = cb.astype(np.float64).copy()
        nz = counts > 0
        new[nz] = sums[nz] / counts[nz][:, None]
        empty = np.argwhere(~nz)
        if empty.size:                 # reseed dead codewords from the pool
            r2 = np.random.default_rng([*skey, 2, it])
            rows = r2.integers(0, n, empty.shape[0])
            for (j, c), r in zip(empty, rows):
                new[j, c] = x3[r, j]
            reseeded += int(empty.shape[0])
        cb = new.astype(np.float32)
    return cb, {"k": int(k), "iters": int(max(1, iters)),
                "reseeded": reseeded}


def grouped_assign(x3: np.ndarray, cb: np.ndarray,
                   chunk: int = 2048) -> np.ndarray:
    """Nearest-codeword id per (row, subspace): [n, m] i32 — the PQ
    encode assignment, same compiled pass as the trainer."""
    if x3.shape[0] == 0:
        return np.zeros((0, cb.shape[0]), np.int32)
    _, _, assign = _grouped_pass(np.asarray(x3, np.float32), x3.shape[0],
                                 cb, chunk=chunk)
    return np.asarray(assign, np.int32)


def assign_store(store, mesh: Mesh, centroids: np.ndarray,
                 chunk: int = 8192, entries=None, choices: int = 1
                 ) -> Iterator[Tuple[Dict, np.ndarray]]:
    """Final assignment sweep: yield (shard entry, assign i32) for every
    non-empty shard, streaming one shard at a time through the same
    compiled pass the trainer used (sums/counts are discarded). `entries`
    restricts the sweep to a shard subset — the incremental index update
    assigns ONLY the new generation's shards this way (docs/UPDATES.md).
    `choices` > 1 yields each row's top-`choices` centroids
    [count, choices] for the balanced-assignment spill (docs/ANN.md)."""
    nlist = centroids.shape[0]
    rows = _padded_rows(store, mesh)
    cdev = jnp.asarray(centroids, jnp.float32)
    for entry, n, pages, scales in _iter_staged(store, mesh, rows,
                                                entries=entries):
        _, _, assign = shard_pass(pages, scales, n, cdev, mesh, nlist,
                                  chunk=chunk, choices=choices)
        yield entry, np.asarray(assign, np.int32)[:n]
