"""Brute-force top-k over page vectors (SURVEY.md §3 #21-22).

This is the TPU-native ANN substrate: instead of a CPU FAISS index, score
queries against the corpus with MXU matmuls and keep a running top-k via
`lax.scan` + `lax.top_k` — HBM never holds more than one [Bq, chunk] score
block, so the corpus side streams at HBM bandwidth while compute stays on
the MXU. Exact (brute-force) search, three tiers:

  * `chunked_topk`   — one device, pages resident in HBM.
  * `sharded_topk`   — pages row-sharded over the mesh 'data' axis; each
    device scores its slice, per-shard top-k candidates are all-gathered
    over ICI and merged. HBM per device holds only N/n_data rows.
  * `topk_over_store`— streams vector-store shards from disk through
    `sharded_topk`, merging on host. Peak footprint is ONE store shard
    spread over the mesh, so 1B-page retrieval (BASELINE.md:16) runs on a
    fixed memory budget. Used by evals/recall.py and mine/ann.py.

`rerank_candidates` is the exact half of the IVF ANN path (index/ivf.py,
docs/ANN.md): the same fused-widening matmul over a GATHERED candidate
block instead of the whole corpus, masked per query to its probed lists.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _topk_scan(q: jnp.ndarray, pages: jnp.ndarray, k: int, chunk: int,
               valid: jnp.ndarray, scales: jnp.ndarray | None = None,
               init=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Running top-k of q @ pages.T. pages [N, D] with N % chunk == 0;
    rows >= `valid` (traced scalar) are padding and score -inf. `init` lets
    shard_map callers pass a carry pcast to the right varying axes.

    pages may be narrow (fp16 rows, or int8 codes with per-row `scales`):
    the widening happens HERE, fused into the matmul's HBM read, so device
    memory and host->device traffic stay at the stored width. For int8 the
    per-row scale factors out of the dot product — score[b, j] =
    (q[b] . codes[j]) * scale[j] — so dequant is one [Bq, chunk] multiply
    on the score block, never a materialized fp32 page matrix."""
    Bq = q.shape[0]
    n_chunks = pages.shape[0] // chunk
    blocks = pages.reshape(n_chunks, chunk, -1)
    scale_blocks = (None if scales is None
                    else scales.astype(jnp.float32).reshape(n_chunks, chunk))

    if init is None:
        init = (jnp.full((Bq, k), -jnp.inf, jnp.float32),
                jnp.full((Bq, k), -1, jnp.int32))
    init_scores, init_idx = init

    def body(carry, inp):
        best_s, best_i = carry
        ci, block, scl = inp                             # block: [chunk, D]
        # HIGHEST precision: ranking fidelity matters more than the ~2x MXU
        # cost of the fp32-via-bf16-passes matmul on TPU. fp16->fp32 widening
        # is exact; int8 codes (<= 127 in magnitude) are exact in any float.
        s = jnp.matmul(q, block.T.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)  # [Bq, chunk]
        if scl is not None:
            s = s * scl[None, :]
        ids = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where(ids[None, :] < valid, s, -jnp.inf)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids[None], (Bq, chunk))], axis=1)
        top_s, pos = lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, pos, axis=1)
        # padding / -inf slots must not report a bogus row id
        top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
        return (top_s, top_i), None

    # None is a static empty pytree node: body sees scl=None when unscaled
    (scores, idx), _ = lax.scan(
        body, (init_scores, init_idx),
        (jnp.arange(n_chunks, dtype=jnp.int32), blocks, scale_blocks))
    return scores, idx


@partial(jax.jit, static_argnames=("k", "chunk"))
def chunked_topk(q: jnp.ndarray, pages: jnp.ndarray, k: int = 10,
                 chunk: int = 8192) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device running top-k of q @ pages.T.

    q: [Bq, D] (pre-normalized for cosine); pages: [N, D]; returns
    (scores [Bq, k], indices [Bq, k]) with indices into `pages` rows.
    N is padded up to a chunk multiple internally; pad rows score -inf.
    """
    N, D = pages.shape
    chunk = min(chunk, max(N, 1))
    pad = (-N) % chunk
    if pad:
        pages = jnp.concatenate(
            [pages, jnp.zeros((pad, D), pages.dtype)], axis=0)
    return _topk_scan(q, pages, k, chunk, jnp.int32(N))


def pack_topk(scores: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """(scores [Bq, k] float32, idx [Bq, k] int32) as ONE int32 [Bq, 2k]:
    the scores' bits in [:, :k], the ids in [:, k:]. A program that hands
    back one array costs the host one output buffer a launch and one
    transfer a pull. Packed as INT32, scores bitcast into int bits — NOT
    ids into float bits: small ids make denormal floats, and anything on
    the way that flushes denormals to zero would silently remap every
    result to row 0. Integers are byte-faithful."""
    return jnp.concatenate(
        [lax.bitcast_convert_type(scores, jnp.int32), idx], axis=1)


def unpack_topk(packed) -> Tuple:
    """`pack_topk`'s inverse: (scores [Bq, k] float32, idx [Bq, k] int32).
    A numpy array is split in place (two views, nothing copied), a jax
    array — a tracer inside a jitted caller — by slice and bitcast."""
    k = packed.shape[1] // 2
    if isinstance(packed, np.ndarray):
        return packed[:, :k].view(np.float32), packed[:, k:]
    return (lax.bitcast_convert_type(packed[:, :k], jnp.float32),
            packed[:, k:])


def empty_topk(batch: int, k: int) -> np.ndarray:
    """A running top-k that holds nothing yet, on the host in `pack_topk`'s
    layout (scores -inf, ids -1): what a chain of carried scans starts
    from, put on the device explicitly and not by a jitted program."""
    return np.concatenate(
        [np.full((batch, k), -np.inf, np.float32).view(np.int32),
         np.full((batch, k), -1, np.int32)], axis=1)


_SHARDED_CACHE: Dict[Tuple, Tuple] = {}


def _build_sharded_topk(mesh: Mesh, k: int, chunk: int, scaled: bool):
    """Jitted (q, pages[, scales], span, carry) -> packed [Bq, 2k] int32
    with pages (and int8 scales) row-sharded over 'data'. `span` is int32
    [2], replicated: the count of valid rows and the offset this launch's
    row ids get. `carry` is a running top-k in `pack_topk`'s layout
    (`empty_topk` to start one), replicated and DONATED: the one output,
    the carry with this launch's rows folded in, takes its buffer, so a
    launch allocates nothing and the caller's carry is gone. Carried
    entries come first in the fold and `lax.top_k` keeps the lower
    position among equal scores: the earlier launch wins a tie. Cached per
    (mesh, k, chunk, scaled); jit retraces per pages dtype within a key."""
    n_data = mesh.shape["data"]

    def run(q, pages_local, scales_local, span, carry):
        rows = pages_local.shape[0]                  # per-shard row count
        shard = lax.axis_index("data")
        valid_local = jnp.clip(span[0] - shard * rows, 0, rows)
        c = min(chunk, rows)
        pad = (-rows) % c
        if pad:
            pages_local = jnp.concatenate(
                [pages_local,
                 jnp.zeros((pad, pages_local.shape[1]), pages_local.dtype)])
            if scales_local is not None:
                scales_local = jnp.concatenate(
                    [scales_local, jnp.zeros((pad,), scales_local.dtype)])
        # the local scan starts from a constant, NOT from `carry`: every
        # device would bring the same earlier winners to the gather below,
        # n_data copies of each. pcast marks it varying over 'data' so the
        # scan's in/out types agree under shard_map
        init = jax.tree_util.tree_map(
            lambda x: lax.pcast(x, ("data",), to="varying"),
            (jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
             jnp.full((q.shape[0], k), -1, jnp.int32)))
        # named_scope: the two regions of this program carry their names
        # in every op's metadata, for --profile in xprof/Perfetto
        with jax.named_scope("sharded_topk.scan"):
            s, i = _topk_scan(q, pages_local, k, c, valid_local,
                              scales=scales_local, init=init)
        with jax.named_scope("sharded_topk.local_topk"):
            gi = jnp.where(i >= 0, i + (shard * rows + span[1]), -1)
            # gather every shard's k candidates over ICI and merge
            # everywhere, the carry's k ahead of them: folded ONCE
            flat = lambda x: jnp.transpose(                  # noqa: E731
                lax.all_gather(x, "data"),                   # [n_data, Bq, k]
                (1, 0, 2)).reshape(q.shape[0], n_data * k)
            carry_s, carry_i = unpack_topk(carry)
            cat_s = jnp.concatenate([carry_s, flat(s)], axis=1)
            cat_i = jnp.concatenate([carry_i, flat(gi)], axis=1)
            top_s, pos = lax.top_k(cat_s, k)
            top_i = jnp.take_along_axis(cat_i, pos, axis=1)
            top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
            return pack_topk(top_s, top_i)

    # After the all_gather every shard computes the identical merge, so the
    # P() output IS replicated over 'data' — but that's a dynamic fact the
    # static varying-axis checker can't infer; check_vma=False is the
    # documented escape hatch for exactly this collective-then-merge shape.
    if scaled:
        fn = run
        in_specs = (P(), P("data"), P("data"), P(), P())
    else:
        fn = lambda q, pages, span, carry: run(      # noqa: E731
            q, pages, None, span, carry)
        in_specs = (P(), P("data"), P(), P())
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)
    return jax.jit(mapped, donate_argnums=len(in_specs) - 1)


def sharded_topk_fn(mesh: Mesh, k: int, chunk: int = 8192,
                    scaled: bool = False):
    """The jitted scan `sharded_topk` launches, for a caller that resolves
    it once and then threads a running top-k through one launch per shard
    on arguments it already holds on the device
    (`SearchService._dispatch_bucket`): (q, pages, span, carry), or
    (q, pages, scales, span, carry) when `scaled`, giving ONE packed int32
    [Bq, 2k] array a launch, left on the device in the donated carry's
    buffer (`_build_sharded_topk`). The caller owns what the wrapper
    checks: pages rows divide mesh 'data', and a carry is passed once."""
    key = (mesh, int(k), int(chunk), bool(scaled))
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        fn = _SHARDED_CACHE[key] = _build_sharded_topk(mesh, k, chunk, scaled)
    return fn


def sharded_topk(q: jnp.ndarray, pages, mesh: Mesh, k: int = 10,
                 chunk: int = 8192, valid: int | None = None,
                 scales=None) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k with pages [N, D] row-sharded over the mesh 'data' axis.

    N must divide by mesh 'data'; rows >= `valid` are padding (score -inf,
    index -1). q is replicated. Returns (scores, indices) ON THE HOST,
    indices global into the sharded row order: the scan's one packed array
    pulled in one transfer and split there (`unpack_topk`). `pages` may be
    fp16 rows or int8 codes with per-row `scales` [N] — widened on-device
    (_topk_scan).

    One launch of the carried scan from an empty carry at offset 0, both
    put up from host constants: the call runs no program but the scan."""
    fn = sharded_topk_fn(mesh, k, chunk, scales is not None)
    N = pages.shape[0]
    if N % mesh.shape["data"]:
        raise ValueError(f"pages rows {N} must divide mesh data axis "
                         f"{mesh.shape['data']}; pad the input")
    span, carry = jax.device_put(
        (np.array([N if valid is None else valid, 0], np.int32),
         empty_topk(q.shape[0], k)), NamedSharding(mesh, P()))
    packed = (fn(q, pages, span, carry) if scales is None
              else fn(q, pages, scales, span, carry))
    return unpack_topk(np.asarray(packed))


@partial(jax.jit, static_argnames=("k",))
def rerank_candidates(q: jnp.ndarray, cand, scales, cand_cent: jnp.ndarray,
                      selected: jnp.ndarray, k: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact re-rank of gathered IVF candidates (index/ivf.py): one MXU
    matmul of q [B, D] against the candidate block cand [C, D] (fp16 rows
    or int8 codes with per-row `scales` — widening fused into the matmul,
    same contract as _topk_scan), masked so each query only keeps
    candidates whose centroid id (cand_cent [C], -1 = padding) is in ITS
    probed set (selected [B, nprobe]), then lax.top_k. Returns
    (scores [B, k], positions into cand [B, k], -1 where fewer than k
    candidates matched). nprobe is a static shape, so the mask is an
    unrolled OR over nprobe [B, C] comparisons — never an [B, nprobe, C]
    materialization."""
    s = jnp.matmul(q, cand.T.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)        # [B, C]
    if scales is not None:
        s = s * scales.astype(jnp.float32)[None, :]
    hit = cand_cent[None, :] == selected[:, 0:1]
    for p in range(1, selected.shape[1]):
        hit = hit | (cand_cent[None, :] == selected[:, p:p + 1])
    s = jnp.where(hit, s, -jnp.inf)      # padding (cent -1) never matches
    top_s, pos = lax.top_k(s, min(k, s.shape[1]))
    pos = jnp.where(jnp.isfinite(top_s), pos, -1)
    return top_s, pos


@partial(jax.jit, static_argnames=("k",))
def rerank_positions(q: jnp.ndarray, cand, scales, pos: jnp.ndarray, k: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k over PER-QUERY candidate positions into one gathered
    block — the final stage of the PQ/ADC path (index/pq.py, docs/ANN.md):
    `cand` [U, D] holds the union of every query's ADC-surviving rows at
    STORED width (fp16 rows or int8 codes with per-row `scales`, widening
    fused into the matmul exactly like _topk_scan), and `pos` [B, R] maps
    each query to ITS candidates (-1 = empty slot). One [B, U] matmul
    scores the whole block, take_along_axis keeps each query's own R, and
    lax.top_k picks the winners. Returns (scores [B, k], positions into
    `cand` [B, k], -1 where fewer than k candidates survived)."""
    s = jnp.matmul(q, cand.T.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)          # [B, U]
    if scales is not None:
        s = s * scales.astype(jnp.float32)[None, :]
    sp = jnp.take_along_axis(s, jnp.clip(pos, 0, None), axis=1)  # [B, R]
    sp = jnp.where(pos >= 0, sp, -jnp.inf)
    top_s, rpos = lax.top_k(sp, min(k, sp.shape[1]))
    out_pos = jnp.take_along_axis(pos, jnp.clip(rpos, 0, None), axis=1)
    out_pos = jnp.where(jnp.isfinite(top_s), out_pos, -1)
    return top_s, out_pos


def merge_topk_host(best_s: np.ndarray, best_i: np.ndarray,
                    new_s: np.ndarray, new_i: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side running-top-k merge of two [Nq, k] candidate sets (ids are
    global page ids; -1 = empty slot).

    O(W) argpartition down to the winning k, then an O(k log k) sort of
    just those — not a full-row argsort: this runs once per disk shard per
    query-batch on the streaming path, so at 1B-page scale it is the
    hottest host loop serving owns. Ties at the selection boundary may
    admit a different equal-scored candidate than a stable full sort would
    (scores are unchanged; only which of the tied ids survives)."""
    k = best_s.shape[1]
    cat_s = np.concatenate([best_s, new_s], axis=1)
    cat_i = np.concatenate([best_i, new_i], axis=1)
    cat_s = np.where(cat_i < 0, -np.inf, cat_s)
    if cat_s.shape[1] > k:
        part = np.argpartition(-cat_s, k - 1, axis=1)[:, :k]
        order = np.argsort(-np.take_along_axis(cat_s, part, axis=1),
                           axis=1, kind="stable")
        pos = np.take_along_axis(part, order, axis=1)
    else:
        pos = np.argsort(-cat_s, axis=1, kind="stable")
    return (np.take_along_axis(cat_s, pos, axis=1),
            np.take_along_axis(cat_i, pos, axis=1))


def merge_partition_topk(parts) -> Tuple[np.ndarray, np.ndarray]:
    """Balanced pairwise merge tree over per-partition top-k candidate
    sets — the host half of the partitioned scatter-gather
    (infer/partition.py, docs/SCALING.md "Partitioned serving").

    `parts` is a sequence of (scores [Nq, k], page_ids [Nq, k]) — one
    entry per partition, ids global (-1 = empty slot). Each partition
    already merged its own shards on device (the carried scan,
    `sharded_topk_fn`); this fold generalizes `merge_shard_topk`'s
    running merge to partition granularity: pairs merge through
    `merge_topk_host`, log2(P) levels deep, so the host-side merge cost
    per level stays O(Nq * k) regardless of partition count. With
    distinct scores the result is identical to a single global top-k
    over the union — the byte-identity contract tests/test_partition.py
    pins against the single-partition exact path."""
    merged = [(np.asarray(s, np.float32), np.asarray(i, np.int64))
              for s, i in parts]
    if not merged:
        raise ValueError("merge_partition_topk needs at least one partition")
    while len(merged) > 1:
        nxt = [merge_topk_host(merged[j][0], merged[j][1],
                               merged[j + 1][0], merged[j + 1][1])
               for j in range(0, len(merged) - 1, 2)]
        if len(merged) % 2:
            nxt.append(merged[-1])
        merged = nxt
    return merged[0]


def stage_shard(vecs, rows: int, dim: int, mesh: Mesh, scales=None
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Zero-pad one store shard to `rows` (the static compiled shape) and
    place it row-sharded over the mesh 'data' axis, AT ITS STORED WIDTH
    (fp16 rows / int8 codes + fp16 `scales`): host->device traffic and HBM
    per shard are 2x / 4x under the old fp32 staging, and the widening fuses
    into the device matmul (VERDICT r4 Weak #3). Shared by the streaming
    sweep below and the HBM-resident serving path (infer/serve.py).
    Returns (pages, scales-or-None)."""
    dtype = np.asarray(vecs).dtype
    if dtype not in (np.float16, np.int8):
        dtype = np.float32
    buf = np.zeros((rows, dim), dtype)
    buf[: vecs.shape[0]] = vecs
    pages = jax.device_put(buf, NamedSharding(mesh, P("data")))
    if scales is None:
        return pages, None
    sbuf = np.zeros((rows,), np.float16)
    sbuf[: scales.shape[0]] = scales
    return pages, jax.device_put(sbuf, NamedSharding(mesh, P("data")))


def merge_shard_topk(q: jnp.ndarray, pages, page_ids: np.ndarray, valid: int,
                     mesh: Mesh, k: int, best_s: np.ndarray,
                     best_i: np.ndarray, chunk: int = 8192, scales=None
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Fold ONE device-resident shard's top-k into the running host merge:
    sharded_topk over `pages` (rows >= valid are padding), row indices
    mapped through `page_ids`, -inf masking, merge. Shared by the streaming
    path below and the HBM-resident serving path (infer/serve.py) so the
    clip/mask edge cases live in exactly one place."""
    if valid == 0:          # empty shard (all-padding write): nothing to add
        return best_s, best_i
    sc, idx = sharded_topk(q, pages, mesh, k=k, chunk=chunk, valid=valid,
                           scales=scales)
    pids = np.where(
        idx >= 0, page_ids[np.clip(idx, 0, valid - 1)], -1)
    return merge_topk_host(best_s, best_i,
                           np.where(np.isfinite(sc), sc, -np.inf), pids)


def topk_over_store(query_vecs: np.ndarray, store, mesh: Mesh, k: int = 10,
                    chunk: int = 8192, query_batch: int = 1024,
                    entries=None) -> Tuple[np.ndarray, np.ndarray]:
    """Stream the vector store through `sharded_topk`, one disk shard at a
    time, merging a host-side running top-k. Returns (scores [Nq, k],
    page_ids [Nq, k] int64, -1 padded). This is the cross-shard merge path
    for 1B-page retrieval: peak HBM = one store shard / n_data per device,
    peak host memory = TWO store shards + the query matrix — the sweep is
    double-buffered (store.iter_shards(prefetch=1)): shard i+1's disk read
    runs on a background reader thread while shard i is staged and scored,
    so disk latency overlaps device top-k instead of serializing after it.
    `entries` sweeps an explicit shard-table snapshot instead of the live
    one (the serving hot-swap's old-view isolation, docs/UPDATES.md).
    """
    nq, dim = query_vecs.shape
    n_data = mesh.shape["data"]
    best_s = np.full((nq, k), -np.inf, np.float32)
    best_i = np.full((nq, k), -1, np.int64)
    if entries is None:
        entries = store.shards()
    if sum(s["count"] for s in entries) == 0 or nq == 0:
        return best_s, best_i
    # one static shape for every disk shard -> a single compiled program
    shard_rows = max((s["count"] for s in entries), default=0)
    shard_rows += (-shard_rows) % max(n_data, 1)
    qb = min(query_batch, nq)
    for ids, vecs, scl in store.iter_shards(raw=True, prefetch=1,
                                            entries=entries):
        n = vecs.shape[0]
        if n == 0:        # empty shard: nothing to score, don't stage it
            continue
        pages, scales = stage_shard(vecs, shard_rows, dim, mesh, scales=scl)
        ids = np.asarray(ids, np.int64)
        for s in range(0, nq, qb):
            q = query_vecs[s: s + qb]
            pad_q = qb - q.shape[0]
            if pad_q:                                # pad to compiled shape
                q = np.concatenate(
                    [q, np.zeros((pad_q, dim), q.dtype)])
            merged_s, merged_i = merge_shard_topk(
                jnp.asarray(q, jnp.float32), pages, ids, n, mesh, k,
                np.concatenate([best_s[s: s + qb],
                                np.full((pad_q, k), -np.inf, np.float32)]),
                np.concatenate([best_i[s: s + qb],
                                np.full((pad_q, k), -1, np.int64)]),
                chunk=chunk, scales=scales)
            keep = qb - pad_q
            best_s[s: s + qb] = merged_s[:keep]
            best_i[s: s + qb] = merged_i[:keep]
    return best_s, best_i
