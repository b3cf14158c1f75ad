"""Brute-force top-k over page vectors (SURVEY.md §3 #21-22).

This is the TPU-native ANN substrate: instead of a CPU FAISS index, score
queries against the corpus with MXU matmuls and keep a running top-k via
`lax.scan` + `lax.top_k` — HBM never holds more than one [Bq, chunk] score
block, so the corpus side streams at HBM bandwidth while compute stays on
the MXU. Exact (brute-force) search, three tiers:

  * `chunked_topk`   — one device, pages resident in HBM.
  * `sharded_topk`   — pages row-sharded over the mesh 'data' axis; each
    device scores its slice, per-shard top-k candidates are all-gathered
    over ICI and merged. HBM per device holds only N/n_data rows. Float16
    rows staged as pair words are scored by one Pallas kernel,
    `exact_scan` (below), other rows by the `lax.scan` above.
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _topk_scan(q: jnp.ndarray, pages: jnp.ndarray, k: int, chunk: int,
               valid: jnp.ndarray, scales: jnp.ndarray | None = None,
               init=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Running top-k of q @ pages.T. pages [N, D] with N % chunk == 0;
    rows >= `valid` (traced scalar) are padding and score -inf. `init` lets
    shard_map callers pass a carry pcast to the right varying axes.

    pages may be narrow (fp16 rows, their pair words [N, D/2] uint32
    (`pair_words`), or int8 codes with per-row `scales`): the widening
    happens HERE, fused into the matmul's HBM read, so device memory and
    host->device traffic stay at the stored width. For int8 the per-row
    scale factors out of the dot product — score[b, j] =
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
        s = jnp.matmul(q, _widen(block).T,
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


# The exact scan of float16 rows as ONE Pallas kernel (`exact_scan`): the
# shard streams from HBM through VMEM once, in blocks of _SCAN_BLOCK_BYTES
# that the grid's BlockSpec double-buffers; each block is scored on the MXU
# in exact bfloat16 pieces and folded into a running top-k that stays in the
# kernel's output blocks (VMEM) across the grid. The running top-k is
# `_LANES` wide (k <= 128), sorted by score, the lower row id first among
# equal scores, -inf / -1 in the slots nothing filled.
#
# Exactness. A float16 x has 11 significant bits: hi = x with its float32
# bits past bfloat16's cut to zero and lo = x - hi (at most 3 bits) are both
# bfloat16 values and hi + lo == x; subnormals included, since bfloat16 has
# float32's exponent range. A float32 q splits into three bfloat16 pieces
# that sum back to q (`split_query`; cut by masks, not by round trips
# through bfloat16, which XLA on the chip may drop as excess precision).
# Every piece product is exact in float32, so the score is the
# float32-accumulated sum of all 3 x 2 of them: a superset of the terms
# `Precision.HIGHEST` keeps for these operands, never fewer.
#
# The rows' layout. Mosaic takes no float16 operand, and on the chip XLA's
# bitcast of float16 to bfloat16 flushes the patterns that read as bfloat16
# subnormals (float16 values under 2^-17) to zero. So the kernel reads the
# rows as "pair words": uint32 [N, D/2], each word the bit patterns of
# columns 2c (low half) and 2c + 1 of a row (`pair_words`, a view of the
# host's float16 bytes that `stage_shard(words=True)` puts on the device:
# nothing is converted a launch), decodes each half with integer ops
# (`_f16_value`) and scores the even and the odd columns against the
# queries' even and odd columns. Float16 rows handed to the scan as they
# are, and pair words with k > _LANES, take `_topk_scan` (which decodes
# the words a chunk at a time, `_widen`).
_LANES = 128
_SCAN_BLOCK_BYTES = 4 << 20     # rows a grid step reads
_SCAN_TILE_ROWS = 256           # rows scored per inner step
_SCAN_TILE_WORDS = 128          # pair words (256 columns) per MXU product
_SCAN_QUERY_BLOCK = 128         # queries a pass over the shard serves
_SCAN_VMEM_LIMIT = 32 * 1024 * 1024


def pair_words(vecs: np.ndarray) -> np.ndarray:
    """float16 rows [N, D] (D even) -> uint32 [N, D/2], each word the bit
    patterns of columns 2c (low half) and 2c + 1: the layout `exact_scan`
    reads, as a view of the same bytes (nothing is copied)."""
    return np.ascontiguousarray(vecs, np.float16).view("<u4")


def _cut(x: jnp.ndarray) -> jnp.ndarray:
    """float32 x with every bit past bfloat16's cut to zero: its top 8
    significant bits, a bfloat16 value, by a mask (a round trip through
    bfloat16 would round, and XLA may drop such a round trip as excess
    precision)."""
    return lax.bitcast_convert_type(
        lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)


def split_query(q: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """float32 q -> (hi, mid, lo) bfloat16 with hi + mid + lo == q exactly:
    the 24 significant bits cut into three runs of 8."""
    hi = _cut(q)
    r = q - hi
    mid = _cut(r)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, r - mid))


def _f16_value(h: jnp.ndarray) -> jnp.ndarray:
    """uint32 holding a float16 bit pattern in its low 16 bits -> the
    float32 value, exactly: the exponent is rebiased from 15 to 127 in
    integer ops; a subnormal (exponent 0) is read as 2^-14 (1 + m/1024) and
    2^-14 taken off again, both exact."""
    mag = (h & jnp.uint32(0x7FFF)) << jnp.uint32(13)
    sign = (h & jnp.uint32(0x8000)) << jnp.uint32(16)
    sub = mag < jnp.uint32(0x00800000)
    base = jnp.where(sub, jnp.uint32(113 << 23), jnp.uint32(112 << 23))
    as_f32 = lambda u: lax.bitcast_convert_type(u, jnp.float32)  # noqa: E731
    return (as_f32((mag + base) | sign)
            - as_f32(jnp.where(sub, base | sign, jnp.uint32(0))))


def f16_pieces(h: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """uint32 holding float16 bit patterns in their low 16 bits -> (hi, lo)
    bfloat16 with float32(hi) + float32(lo) == the float16 value exactly."""
    x = _f16_value(h)
    hi = _cut(x)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _widen(block: jnp.ndarray) -> jnp.ndarray:
    """A block of rows as float32 [rows, D]: pair words [rows, D/2] decoded
    exactly by `_f16_value` (both halves, columns interleaved back), any
    other dtype converted, which is exact for float16 and int8."""
    if block.dtype != jnp.uint32:
        return block.astype(jnp.float32)
    halves = (block & jnp.uint32(0xFFFF), block >> jnp.uint32(16))
    return jnp.stack([_f16_value(h) for h in halves], axis=-1).reshape(
        block.shape[0], -1)


def _scan_kernel(valid_ref, q_ref, x_ref, s_ref, i_ref, blk_ref, *, k: int,
                 qb: int, tile: int, words: int):
    """One grid step (query block, row block): score the row block into
    `blk_ref` [qb, rows] (-inf past `valid`), then fold it into the running
    top-k (`s_ref` / `i_ref` [qb, _LANES]) by rounds of max, lowest row at
    the max, insert and mask, while some query's best in the block beats
    its running k-th: a block that no query's k-th admits costs no round.
    Blocks arrive in rising row order and an insert goes after every equal
    score already held, so the lower row wins a tie, as `lax.top_k`'s
    lower position does in `_topk_scan`. `q_ref` holds the query block's
    three pieces stacked by rows, its even columns in [0, 0] and its odd
    ones in [0, 1]."""
    j = pl.program_id(1)
    rows, width = x_ref.shape
    base = j * rows
    valid = valid_ref[0]

    @pl.when(j == 0)
    def _():
        s_ref[...] = jnp.full(s_ref.shape, -jnp.inf, jnp.float32)
        i_ref[...] = jnp.full(i_ref.shape, -1, jnp.int32)

    @pl.when(base < valid)
    def _():
        nt = (((1,), (1,)), ((), ()))
        for r in range(0, rows, tile):
            p = None
            for c in range(0, width, words):
                w = x_ref[r:r + tile, c:c + words]
                for half, h in enumerate((w & jnp.uint32(0xFFFF),
                                          w >> jnp.uint32(16))):
                    q = q_ref[0, half, :, c:c + words]
                    for piece in f16_pieces(h):
                        part = lax.dot_general(
                            q, piece, nt, preferred_element_type=jnp.float32)
                        p = part if p is None else p + part
            row = base + r + lax.broadcasted_iota(jnp.int32, (qb, tile), 1)
            blk_ref[:, r:r + tile] = jnp.where(
                row < valid, (p[2 * qb:3 * qb] + p[qb:2 * qb]) + p[:qb],
                -jnp.inf)
        row = base + lax.broadcasted_iota(jnp.int32, (qb, rows), 1)
        lane = lax.broadcasted_iota(jnp.int32, (qb, _LANES), 1)

        def kth():
            return jnp.max(jnp.where(lane == k - 1, s_ref[...], -jnp.inf),
                           axis=1, keepdims=True)

        def beats(best):
            return jnp.any(best > kth())

        def fold(best):
            blk = blk_ref[...]
            at = jnp.min(jnp.where(blk == best, row, jnp.int32(2 ** 31 - 1)),
                         axis=1, keepdims=True)
            blk = jnp.where(row == at, -jnp.inf, blk)
            blk_ref[...] = blk
            run_s, run_i = s_ref[...], i_ref[...]
            n_ge = jnp.sum((run_s >= best).astype(jnp.int32), axis=1,
                           keepdims=True)
            ins_s = jnp.where(lane < n_ge, run_s, jnp.where(
                lane == n_ge, best, pltpu.roll(run_s, 1, 1)))
            ins_i = jnp.where(lane < n_ge, run_i, jnp.where(
                lane == n_ge, at, pltpu.roll(run_i, 1, 1)))
            take = (best > kth()) & (lane < k)
            s_ref[...] = jnp.where(take, ins_s, run_s)
            i_ref[...] = jnp.where(take, ins_i, run_i)
            return jnp.max(blk, axis=1, keepdims=True)

        lax.while_loop(beats, fold,
                       jnp.max(blk_ref[...], axis=1, keepdims=True))


def _kernel_topk(q: jnp.ndarray, pages: jnp.ndarray, k: int,
                 valid: jnp.ndarray, interpret: Optional[bool] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`_topk_scan`'s answer for float16 rows from `exact_scan`: (scores
    [Bq, k] float32, row ids [Bq, k] int32, -inf / -1 past the rows <
    `valid` that exist; k <= _LANES). `pages` is pair words [N, D/2]
    (`pair_words`). A grid step reads _SCAN_BLOCK_BYTES of rows; steps
    past the last valid row repeat its block index, so Pallas fetches
    nothing for them. Queries go in blocks of up to _SCAN_QUERY_BLOCK, each
    its own pass over the rows."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    Bq = q.shape[0]
    N, width = pages.shape
    words = _SCAN_TILE_WORDS if width % _SCAN_TILE_WORDS == 0 else width
    tile = min(_SCAN_TILE_ROWS, -(-N // 8) * 8)
    rows = max(tile, _SCAN_BLOCK_BYTES // (4 * width) // tile * tile)
    rows = min(rows, -(-N // tile) * tile)
    if N % rows:
        pages = jnp.concatenate(
            [pages, jnp.zeros((rows - N % rows, width), pages.dtype)])
    qb = min(-(-Bq // 8) * 8, _SCAN_QUERY_BLOCK)
    nq = -(-Bq // qb)
    m = -(-3 * qb // 16) * 16
    qf = jnp.pad(q.astype(jnp.float32),
                 ((0, nq * qb - Bq), (0, 2 * width - q.shape[1])))
    # [nq, parity, m, width]: a query block's three pieces stacked by rows
    # (zero rows up to m), its even columns at parity 0, odd at 1
    lhs = jnp.stack(split_query(qf)).reshape(3, nq, qb, width, 2)
    lhs = jnp.pad(lhs.transpose(1, 4, 0, 2, 3).reshape(nq, 2, 3 * qb, width),
                  ((0, 0), (0, 0), (0, m - 3 * qb), (0, 0)))

    def row_block(i, j, v):
        return jnp.minimum(j, jnp.maximum(v[0] - 1, 0) // rows), 0

    s, ids = pl.pallas_call(
        partial(_scan_kernel, k=k, qb=qb, tile=tile, words=words),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nq, pages.shape[0] // rows),
            in_specs=[pl.BlockSpec((1, 2, m, width),
                                   lambda i, j, v: (i, 0, 0, 0)),
                      pl.BlockSpec((rows, width), row_block)],
            out_specs=[pl.BlockSpec((qb, _LANES), lambda i, j, v: (i, 0))] * 2,
            scratch_shapes=[pltpu.VMEM((qb, rows), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((nq * qb, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((nq * qb, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_SCAN_VMEM_LIMIT),
        interpret=interpret, name="exact_scan",
    )(jnp.reshape(valid, (1,)).astype(jnp.int32), lhs, pages)
    return s[:Bq, :k], ids[:Bq, :k]


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


def scans_in_kernel(dtype, k: int) -> bool:
    """Whether the carried scan answers rows of `dtype` with `exact_scan`:
    float16 rows staged as pair words (uint32), and a top-k that fits the
    kernel's lanes. Pair words with a wider top-k, float16 rows as they
    are, int8 codes with scales and float32 rows take `_topk_scan`."""
    return jnp.dtype(dtype) == jnp.uint32 and k <= _LANES


def _local_scan(q, pages, scales, k: int, chunk: int, valid):
    """`_topk_scan` over one device's rows inside `shard_map`, padded to a
    chunk multiple. The scan starts from a constant, NOT from the carry:
    every device would bring the same earlier winners to the gather that
    follows, n_data copies of each; pcast marks it varying over 'data' so
    the scan's in/out types agree."""
    pad = (-pages.shape[0]) % chunk
    if pad:
        pages = jnp.concatenate(
            [pages, jnp.zeros((pad, pages.shape[1]), pages.dtype)])
        if scales is not None:
            scales = jnp.concatenate([scales, jnp.zeros((pad,), scales.dtype)])
    init = jax.tree_util.tree_map(
        lambda x: lax.pcast(x, ("data",), to="varying"),
        (jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
         jnp.full((q.shape[0], k), -1, jnp.int32)))
    return _topk_scan(q, pages, k, chunk, valid, scales=scales, init=init)


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
    position among equal scores: the earlier launch wins a tie. Each
    device's rows are scanned by `exact_scan` where `scans_in_kernel` says
    so (pair words, k <= 128), else by `_topk_scan` in chunks of `chunk`. Cached per (mesh, k, chunk, scaled); jit retraces per pages
    dtype within a key."""
    n_data = mesh.shape["data"]

    def run(q, pages_local, scales_local, span, carry):
        rows = pages_local.shape[0]                  # per-shard row count
        shard = lax.axis_index("data")
        valid_local = jnp.clip(span[0] - shard * rows, 0, rows)
        # named_scope: the two regions of this program carry their names
        # in every op's metadata, for --profile in xprof/Perfetto
        with jax.named_scope("sharded_topk.scan"):
            if scales_local is None and scans_in_kernel(pages_local.dtype, k):
                s, i = _kernel_topk(q, pages_local, k, valid_local)
            else:
                s, i = _local_scan(q, pages_local, scales_local, k,
                                   min(chunk, rows), valid_local)
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
    the pair words of fp16 rows (`stage_shard(words=True)`), scanned by
    `exact_scan`, or fp16 rows or int8 codes with per-row `scales` [N],
    widened on-device (_topk_scan).

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


def stage_shard(vecs, rows: int, dim: int, mesh: Mesh, scales=None,
                words: bool = False
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Zero-pad one store shard to `rows` (the static compiled shape) and
    place it row-sharded over the mesh 'data' axis, AT ITS STORED WIDTH
    (fp16 rows / int8 codes + fp16 `scales`): host->device traffic and HBM
    per shard are 2x / 4x under the old fp32 staging, and the widening fuses
    into the device matmul (VERDICT r4 Weak #3). `words` stages float16
    rows as the pair words `exact_scan` reads (`pair_words`: the same
    bytes, uint32 [rows, dim/2]), for a caller that hands the pages to the
    scan alone. Shared by the streaming sweep below and the HBM-resident
    serving path (infer/serve.py). Returns (pages, scales-or-None)."""
    dtype = np.asarray(vecs).dtype
    if dtype not in (np.float16, np.int8):
        dtype = np.float32
    buf = np.zeros((rows, dim), dtype)
    buf[: vecs.shape[0]] = vecs
    if words and dtype == np.float16 and dim % 2 == 0:
        buf = pair_words(buf)
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
        pages, scales = stage_shard(vecs, shard_rows, dim, mesh, scales=scl,
                                    words=True)
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
