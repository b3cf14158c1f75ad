"""Unit tests: chunked / sharded / store-streaming top-k vs numpy reference,
the argpartition host merge, and the double-buffered shard read-ahead."""
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_page_vectors_tpu.ops.topk import (
    chunked_topk, merge_topk_host, sharded_topk, topk_over_store)
from dnn_page_vectors_tpu.parallel.mesh import make_mesh
from dnn_page_vectors_tpu.config import MeshConfig


def _np_topk(q, pages, k):
    s = q @ pages.T
    idx = np.argsort(-s, axis=1)[:, :k]
    return np.take_along_axis(s, idx, axis=1), idx


def test_chunked_topk_matches_numpy():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(5, 32)).astype(np.float32)
    pages = rng.normal(size=(1000, 32)).astype(np.float32)
    for chunk in (64, 128, 1000, 4096):
        s, i = chunked_topk(jnp.asarray(q), jnp.asarray(pages), k=7,
                            chunk=chunk)
        ns, ni = _np_topk(q, pages, 7)
        np.testing.assert_allclose(np.asarray(s), ns, rtol=1e-4, atol=1e-5)
        # indices can differ on exact ties; scores matching is the contract
        assert np.asarray(i).shape == (5, 7)
        top1_scores = (q * pages[np.asarray(i)[:, 0]]).sum(-1)
        np.testing.assert_allclose(top1_scores, ns[:, 0], rtol=1e-4)


def test_sharded_topk_matches_single_device(eight_devices):
    """VERDICT r1 #2: pages sharded over 'data' must reproduce the
    single-device ranking (cross-shard merge correctness)."""
    mesh = make_mesh(MeshConfig(data=8))
    rng = np.random.default_rng(1)
    q = rng.normal(size=(6, 16)).astype(np.float32)
    pages = rng.normal(size=(512, 16)).astype(np.float32)  # 64 rows/shard
    s1, i1 = chunked_topk(jnp.asarray(q), jnp.asarray(pages), k=9)
    s8, i8 = sharded_topk(jnp.asarray(q), jnp.asarray(pages), mesh, k=9,
                          chunk=32)
    np.testing.assert_allclose(np.asarray(s8), np.asarray(s1),
                               rtol=1e-4, atol=1e-5)
    # `valid` must mask the tail rows exactly like truncating the input
    sv, iv = sharded_topk(jnp.asarray(q), jnp.asarray(pages), mesh, k=9,
                          chunk=32, valid=200)
    st, _ = chunked_topk(jnp.asarray(q), jnp.asarray(pages[:200]), k=9)
    np.testing.assert_allclose(np.asarray(sv), np.asarray(st),
                               rtol=1e-4, atol=1e-5)
    assert (np.asarray(iv) < 200).all()


@pytest.mark.parametrize("n_data", [1, 4])
def test_sharded_topk_launches_the_scan_alone(eight_devices, launches,
                                              n_data):
    """`sharded_topk` is one launch of the carried scan: the row count with
    an offset of 0 and a carry that holds nothing go up from host
    constants by explicit puts, so on arguments already on the device the
    call runs under a guard that refuses every implicit host-to-device
    transfer and launches no program but the scan (the count cost a
    conversion program when the scan took it as a scalar)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh(MeshConfig(data=n_data))
    rng = np.random.default_rng(3)
    rows = rng.normal(size=(256, 16)).astype(np.float16)
    q = jax.device_put(rng.normal(size=(6, 16)).astype(np.float32),
                       NamedSharding(mesh, P()))
    pages = jax.device_put(rows, NamedSharding(mesh, P("data")))
    sharded_topk(q, pages, mesh, k=9, chunk=32, valid=200)         # warm
    with launches() as seen, jax.transfer_guard_host_to_device("disallow"):
        got = sharded_topk(q, pages, mesh, k=9, chunk=32, valid=200)
    assert seen["jitted"] == {"<lambda>"} and seen["programs"] == 1
    assert seen["pulls"] == 1
    want = chunked_topk(q, jnp.asarray(rows[:200]), k=9, chunk=32)
    np.testing.assert_allclose(got[0], np.asarray(want[0]), rtol=1e-5)
    assert (got[1] < 200).all() and (got[1] >= 0).all()


def _exact_rows(seed, n, dim, scaled):
    """Queries and rows whose dot products are exact in float32 whatever
    the blocking (multiples of 1/64, sums far inside 24 bits), so that two
    programs that chunk differently still give the same bits: float16
    rows, or int8 codes with power-of-two scales."""
    rng = np.random.default_rng(seed)
    q = (rng.integers(-256, 257, (6, dim)) / 64).astype(np.float32)
    if not scaled:
        rows = (rng.integers(-128, 129, (n, dim)) / 64).astype(np.float16)
        return q, rows, None, rows.astype(np.float32)
    codes = rng.integers(-127, 128, (n, dim)).astype(np.int8)
    scales = (2.0 ** rng.integers(-8, -4, n)).astype(np.float16)
    return q, codes, scales, (codes.astype(np.float32)
                              * scales.astype(np.float32)[:, None])


@pytest.mark.parametrize("valid", [40, 5])
@pytest.mark.parametrize("scaled", [False, True],
                         ids=["float16", "int8_scales"])
def test_scan_hands_back_one_packed_array(eight_devices, scaled, valid):
    """What a launch of the jitted scan returns: ONE int32 [Bq, 2k] array,
    the scores' bits then the row ids, for the unscaled and the scaled
    program alike; on a carry that holds nothing it unpacks to
    `chunked_topk` over the valid rows bit for bit, padding slots
    -inf / -1."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dnn_page_vectors_tpu.ops.topk import (
        empty_topk, sharded_topk_fn, unpack_topk)
    mesh = make_mesh(MeshConfig(data=2))
    k = 9
    q, rows, scales, wide = _exact_rows(11, 64, 16, scaled)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))  # noqa: E731
    args = [put(q, P()), put(rows, P("data"))]
    if scaled:
        args.append(put(scales, P("data")))
    args += [put(np.array([valid, 0], np.int32), P()),
             put(empty_topk(6, k), P())]
    packed = sharded_topk_fn(mesh, k, chunk=16, scaled=scaled)(*args)
    assert isinstance(packed, jax.Array)
    assert packed.dtype == jnp.int32 and packed.shape == (6, 2 * k)
    got_s, got_i = unpack_topk(np.asarray(packed))
    want_s, want_i = chunked_topk(jnp.asarray(q), jnp.asarray(wide[:valid]),
                                  k=k, chunk=16)
    assert got_s.dtype == np.float32 and got_i.dtype == np.int32
    np.testing.assert_array_equal(got_s.view(np.int32),
                                  np.asarray(want_s).view(np.int32))
    np.testing.assert_array_equal(got_i, np.asarray(want_i))
    if valid < k:
        assert np.isneginf(got_s[:, valid:]).all()
        assert (got_i[:, valid:] == -1).all()
    # the same split inside a jitted caller (the scan's, of its carry)
    dev_s, dev_i = jax.jit(unpack_topk)(packed)
    np.testing.assert_array_equal(np.asarray(dev_s).view(np.int32),
                                  got_s.view(np.int32))
    np.testing.assert_array_equal(np.asarray(dev_i), got_i)


@pytest.mark.parametrize("scaled", [False, True],
                         ids=["float16", "int8_scales"])
def test_merge_shard_topk_pulls_one_array(eight_devices, launches, scaled):
    """Folding a shard into the host merge brings ONE array down (the
    packed scan result; scores and ids came separately before) and gives
    what the two-array fold gave: `chunked_topk`'s scores and ids pulled
    apart, mapped through the page ids and merged."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dnn_page_vectors_tpu.ops.topk import merge_shard_topk
    mesh = make_mesh(MeshConfig(data=2))
    k, valid = 9, 50
    q, rows, scales, wide = _exact_rows(12, 64, 16, scaled)
    page_ids = np.arange(7000, 7000 + valid, dtype=np.int64)[::-1].copy()
    best_s = np.sort(np.random.default_rng(5).normal(size=(6, k)).astype(
        np.float32) * 20, axis=1)[:, ::-1].copy()
    best_i = np.arange(6 * k, dtype=np.int64).reshape(6, k)
    qd = jax.device_put(q, NamedSharding(mesh, P()))
    pages = jax.device_put(rows, NamedSharding(mesh, P("data")))
    scl = (None if scales is None
           else jax.device_put(scales, NamedSharding(mesh, P("data"))))
    merge_shard_topk(qd, pages, page_ids, valid, mesh, k, best_s, best_i,
                     chunk=16, scales=scl)                         # warm
    with launches() as seen:
        got_s, got_i = merge_shard_topk(qd, pages, page_ids, valid, mesh, k,
                                        best_s, best_i, chunk=16, scales=scl)
    assert seen["pulls"] == 1
    sc, idx = chunked_topk(jnp.asarray(q), jnp.asarray(wide[:valid]), k=k,
                           chunk=16)
    sc, idx = np.asarray(sc), np.asarray(idx)
    want_s, want_i = merge_topk_host(
        best_s, best_i, sc, np.where(idx >= 0, page_ids[idx], -1))
    np.testing.assert_array_equal(got_s.view(np.int32),
                                  want_s.view(np.int32))
    np.testing.assert_array_equal(got_i, want_i)


def _scan_shapes(mesh, scaled, batch=8, rows=64, dim=16, k=10):
    """The carried scan's arguments as shapes on `mesh`, for lowering."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    shape = lambda dims, dtype, spec: jax.ShapeDtypeStruct(   # noqa: E731
        dims, dtype, sharding=NamedSharding(mesh, spec))
    return ([shape((batch, dim), jnp.float32, P()),
             shape((rows, dim), jnp.int8 if scaled else jnp.float16,
                   P("data"))]
            + ([shape((rows,), jnp.float16, P("data"))] if scaled else [])
            + [shape((2,), jnp.int32, P()),
               shape((batch, 2 * k), jnp.int32, P())])


def test_scan_keeps_the_name_the_roofline_finds_it_by(eight_devices):
    """`sharded_topk_roofline` finds the scan in a trace by its compiled
    module's name (`benchmarks/workloads/bert_mini.serve_exact.json`,
    `trace_modules.scan`, read here and never written): the unscaled scan
    compiles under exactly that name, as the reducer strips it."""
    import json
    import os

    from benchmarks import trace_reduce
    from dnn_page_vectors_tpu.ops.topk import sharded_topk_fn
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "workloads",
                           "bert_mini.serve_exact.json")) as f:
        want = json.load(f)["trace_modules"]["scan"]
    mesh = make_mesh(MeshConfig(data=1))
    compiled = sharded_topk_fn(mesh, 10).lower(
        *_scan_shapes(mesh, False)).compile()
    name = compiled.runtime_executable().hlo_modules()[0].name
    assert trace_reduce.module_name(f"{name}(1234567890)") == want
    assert trace_reduce.module_name(name) == want


@pytest.mark.parametrize("scaled", [False, True],
                         ids=["float16", "int8_scales"])
def test_scan_donates_its_carry_to_its_one_output(eight_devices, scaled):
    """The carry is the scan's LAST argument and is donated: the compiled
    program aliases it, and no other argument, to the one output, so a
    launch makes no output buffer; the compiled module keeps its name
    (`jit__lambda` unscaled, `jit_run` with scales); and a carry that was
    passed is deleted, so nothing can read it again by mistake."""
    import re

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dnn_page_vectors_tpu.ops.topk import empty_topk, sharded_topk_fn
    mesh = make_mesh(MeshConfig(data=2))
    scan = sharded_topk_fn(mesh, 10, scaled=scaled)
    shapes = _scan_shapes(mesh, scaled)
    head = scan.lower(*shapes).compile().as_text().split("\n", 1)[0]
    assert head.startswith(
        "HloModule " + ("jit_run," if scaled else "jit__lambda,"))
    # the output (the module's one result, `{}`) lives in the last
    # parameter's buffer, and nothing else is aliased
    assert re.findall(r"input_output_alias=\{(.*?)\}, entry", head) == [
        " {}: (%d, {}, may-alias) " % (len(shapes) - 1)]
    q, rows, scales, _ = _exact_rows(13, 64, 16, scaled)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))  # noqa: E731
    args = [put(np.concatenate([q, q[:2]]), P()), put(rows, P("data"))]
    if scaled:
        args.append(put(scales, P("data")))
    span, carry = put(np.array([64, 0], np.int32), P()), put(
        empty_topk(8, 10), P())
    out = scan(*args, span, carry)
    assert carry.is_deleted() and not span.is_deleted()
    assert out.shape == (8, 20) and not out.is_deleted()


def _carried(mesh, k, q, shards, scaled, chunk=16):
    """Thread one running top-k through the carried scan, one launch a
    shard in slot order, as the serving loop does: `shards` are (rows,
    scales, valid), every one padded to the same row count; the ids come
    back in the combined numbering slot * rows + row."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dnn_page_vectors_tpu.ops.topk import (
        empty_topk, sharded_topk_fn, unpack_topk)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))  # noqa: E731
    scan = sharded_topk_fn(mesh, k, chunk=chunk, scaled=scaled)
    qd = put(q, P())
    packed = put(empty_topk(q.shape[0], k), P())
    for slot, (rows, scales, valid) in enumerate(shards):
        args = [qd, put(rows, P("data"))]
        if scaled:
            args.append(put(scales, P("data")))
        span = put(np.array([valid, slot * rows.shape[0]], np.int32), P())
        packed = scan(*args, span, packed)
    return unpack_topk(np.asarray(packed))


def _tied_shards(seed, scaled, rows=32, dim=16):
    """Four shards of `rows` padded rows: 32, 20, 0 (all padding) and 32
    valid, the last holding copies of rows of the first two, so that the
    same score turns up in two shards. Returns the shards, the float32
    rows of the valid ones end to end, and each of those rows' combined
    id."""
    valids = (rows, 20, 0, rows)
    q, raw, scales, wide = _exact_rows(seed, 4 * rows, dim, scaled)
    raw, wide = raw.copy(), wide.copy()
    for dst, src in ((3 * rows + 1, 4), (3 * rows + 7, rows + 3),
                     (3 * rows + 8, 9)):
        raw[dst], wide[dst] = raw[src], wide[src]
        if scaled:
            scales[dst] = scales[src]
    shards, cat, ids = [], [], []
    for slot, valid in enumerate(valids):
        sl = slice(slot * rows, (slot + 1) * rows)
        shards.append((raw[sl], None if scales is None else scales[sl],
                       valid))
        cat.append(wide[sl][:valid])
        ids.append(slot * rows + np.arange(valid))
    return q, shards, np.concatenate(cat), np.concatenate(ids)


@pytest.mark.parametrize("scaled", [False, True],
                         ids=["float16", "int8_scales"])
def test_carried_scan_is_chunked_topk_over_the_shards_end_to_end(
        eight_devices, scaled):
    """A running top-k threaded through one launch a shard IS
    `chunked_topk` over the valid rows of all shards end to end, bit for
    bit, ids in the combined numbering: a row that two shards hold comes
    back under the lower slot's id first (the carried entry wins a tie),
    and a shard of all padding leaves the carry as it was."""
    mesh = make_mesh(MeshConfig(data=2))
    k = 30                      # wide enough to hold both copies of a tie
    q, shards, cat, ids = _tied_shards(21, scaled)
    got_s, got_i = _carried(mesh, k, q, shards, scaled)
    want_s, want_pos = chunked_topk(jnp.asarray(q), jnp.asarray(cat), k=k,
                                    chunk=16)
    np.testing.assert_array_equal(got_s.view(np.int32),
                                  np.asarray(want_s).view(np.int32))
    np.testing.assert_array_equal(got_i, ids[np.asarray(want_pos)])
    # the planted copies: wherever both are in, the lower slot's is first
    for low, high in ((4, 97), (35, 103), (9, 104)):
        for row in got_i:
            at = {int(i): n for n, i in enumerate(row)}
            if low in at and high in at:
                assert at[low] + 1 == at[high]
    # a carry that meets a shard of all padding comes out as it went in
    two = _carried(mesh, k, q, shards[:2], scaled)
    three = _carried(mesh, k, q, shards[:3], scaled)
    np.testing.assert_array_equal(two[0].view(np.int32),
                                  three[0].view(np.int32))
    np.testing.assert_array_equal(two[1], three[1])


def test_carry_is_folded_once_on_a_wide_mesh(eight_devices):
    """On a mesh whose 'data' axis is wider than 1 every device scans its
    slice from an empty start and the carry joins ONCE, after the gather:
    the answer holds no id twice (a carry that started every device's
    scan would come back eight times over) and is still `chunked_topk`
    over the rows end to end; with fewer valid rows than k the rest stays
    -inf / -1."""
    mesh = make_mesh(MeshConfig(data=8))
    k = 12
    q, shards, cat, ids = _tied_shards(22, False, rows=64)
    got_s, got_i = _carried(mesh, k, q, shards, False)
    for row in got_i:
        assert len(set(row.tolist())) == k and (row >= 0).all()
    want_s, want_pos = chunked_topk(jnp.asarray(q), jnp.asarray(cat), k=k,
                                    chunk=16)
    np.testing.assert_array_equal(got_s.view(np.int32),
                                  np.asarray(want_s).view(np.int32))
    np.testing.assert_array_equal(got_i, ids[np.asarray(want_pos)])
    few = [(rows, scl, min(valid, 3)) for rows, scl, valid in shards]
    few_s, few_i = _carried(mesh, k, q, few, False)
    assert np.isfinite(few_s[:, :9]).all() and np.isneginf(few_s[:, 9:]).all()
    assert (few_i[:, 9:] == -1).all()
    assert sorted(few_i[0, :9].tolist()) == [0, 1, 2, 64, 65, 66,
                                             192, 193, 194]


def test_empty_topk_is_the_packed_layout_of_nothing():
    """`empty_topk` is `pack_topk` of -inf scores and -1 ids, made on the
    host with no program: what a chain of carried scans starts from."""
    from dnn_page_vectors_tpu.ops.topk import (
        empty_topk, pack_topk, unpack_topk)
    host = empty_topk(3, 5)
    assert host.dtype == np.int32 and host.shape == (3, 10)
    s, i = unpack_topk(host)
    assert np.isneginf(s).all() and (i == -1).all()
    np.testing.assert_array_equal(host, np.asarray(pack_topk(
        jnp.full((3, 5), -jnp.inf, jnp.float32),
        jnp.full((3, 5), -1, jnp.int32))))


def test_topk_over_store_matches_brute_force(eight_devices, tmp_path):
    """Streaming the store shard-by-shard over the mesh must equal one giant
    in-memory search — no step materializes the full store."""
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore

    mesh = make_mesh(MeshConfig(data=8))
    rng = np.random.default_rng(2)
    dim, n = 16, 700                       # 3 shards: 256, 256, 188
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    ids = np.arange(1000, 1000 + n)        # page ids != row numbers
    store = VectorStore(str(tmp_path / "store"), dim=dim, shard_size=256)
    for si in range(3):
        sl = slice(si * 256, min((si + 1) * 256, n))
        store.write_shard(si, ids[sl], vecs[sl])
    q = rng.normal(size=(33, dim)).astype(np.float32)
    scores, pids = topk_over_store(q, store, mesh, k=10, chunk=64,
                                   query_batch=8)
    # the store rounds vectors to fp16; the oracle must score what it stores
    ref_s = q @ vecs.astype(np.float16).astype(np.float32).T
    ref_idx = np.argsort(-ref_s, axis=1)[:, :10]
    np.testing.assert_allclose(
        scores, np.take_along_axis(ref_s, ref_idx, axis=1),
        rtol=1e-4, atol=1e-4)
    # ids must be the store's page ids, not row numbers
    assert set(np.unique(pids)) <= set(ids.tolist())


def test_chunked_topk_small_corpus():
    # N < k: pad columns must come back as -inf / -1
    q = jnp.ones((2, 4))
    pages = jnp.ones((3, 4))
    s, i = chunked_topk(q, pages, k=5, chunk=8)
    s, i = np.asarray(s), np.asarray(i)
    assert (i[:, :3] >= 0).all()
    assert (i[:, 3:] == -1).all()
    assert np.isinf(s[:, 3:]).all()


def test_merge_topk_host_partition_matches_full_sort():
    """The O(W) argpartition merge must select exactly the scores a full
    stable argsort selects (ids may differ only on exact ties), keep the
    row sorted descending, and keep -1 empty slots masked to -inf."""
    rng = np.random.default_rng(11)
    for nq, k in ((1, 1), (4, 10), (33, 7)):
        best_s = rng.normal(size=(nq, k)).astype(np.float32)
        best_i = rng.integers(0, 10_000, size=(nq, k)).astype(np.int64)
        new_s = rng.normal(size=(nq, k)).astype(np.float32)
        new_i = rng.integers(0, 10_000, size=(nq, k)).astype(np.int64)
        # empty slots (running merge mid-sweep) must never win
        best_i[:, -1] = -1
        new_i[0, 0] = -1
        ms, mi = merge_topk_host(best_s, best_i, new_s, new_i)
        cat_s = np.concatenate([best_s, new_s], axis=1)
        cat_i = np.concatenate([best_i, new_i], axis=1)
        cat_s = np.where(cat_i < 0, -np.inf, cat_s)
        ref = np.take_along_axis(
            cat_s, np.argsort(-cat_s, axis=1, kind="stable")[:, :k], axis=1)
        np.testing.assert_array_equal(ms, ref)
        assert (ms[:, :-1] >= ms[:, 1:]).all()
        assert (mi[np.isneginf(ms)] == -1).all() if np.isneginf(ms).any() \
            else True
        # every surviving id scores what the merge says it scores
        lookup = {}
        for r in range(nq):
            lookup.clear()
            for s, i in zip(cat_s[r], cat_i[r]):
                if i >= 0:
                    lookup.setdefault(int(i), set()).add(float(s))
            for s, i in zip(ms[r], mi[r]):
                if i >= 0:
                    assert float(s) in lookup[int(i)]


def test_read_ahead_order_and_error_propagation():
    from dnn_page_vectors_tpu.infer.vector_store import read_ahead

    assert list(read_ahead(iter(range(20)), depth=1)) == list(range(20))
    assert list(read_ahead(iter([]), depth=2)) == []

    def _boom():
        yield 1
        yield 2
        raise IOError("disk died mid-sweep")

    it = read_ahead(_boom(), depth=1)
    got = []
    with pytest.raises(IOError, match="disk died"):
        for x in it:
            got.append(x)
    assert got == [1, 2]    # items before the fault are delivered in order
    # an abandoning consumer must not deadlock against a blocked reader
    it = read_ahead(iter(range(1000)), depth=1)
    assert next(it) == 0
    it.close()


def test_topk_over_store_read_fault_reraises(eight_devices, tmp_path):
    """The prefetched sweep keeps the serial exception surface: a shard
    read failing on the reader thread re-raises at the consumer."""
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    from dnn_page_vectors_tpu.utils import faults

    mesh = make_mesh(MeshConfig(data=8))
    rng = np.random.default_rng(5)
    vecs = rng.normal(size=(64, 16)).astype(np.float32)
    store = VectorStore(str(tmp_path / "store"), dim=16, shard_size=32)
    store.write_shard(0, np.arange(32), vecs[:32])
    store.write_shard(1, np.arange(32, 64), vecs[32:])
    q = rng.normal(size=(3, 16)).astype(np.float32)
    faults.install(faults.FaultPlan.parse("shard_read:io_error:1", seed=0))
    try:
        with pytest.raises(IOError):
            topk_over_store(q, store, mesh, k=5, chunk=16)
    finally:
        faults.reset()


def test_topk_over_store_skips_empty_shard(eight_devices, tmp_path):
    """A zero-count shard (a writer whose whole range was padding) holds an
    empty page_ids array; the merge must skip it instead of indexing into it
    (ADVICE r4: page_ids[0] raised IndexError)."""
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore

    mesh = make_mesh(MeshConfig(data=8))
    rng = np.random.default_rng(3)
    dim = 16
    vecs = rng.normal(size=(40, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    store = VectorStore(str(tmp_path / "store"), dim=dim, shard_size=64)
    store.write_shard(0, np.arange(40), vecs)
    # an all-padding write records a count=0 shard entry
    store.write_shard(1, np.full(8, -1, np.int64), np.zeros((8, dim)))
    assert [s["count"] for s in store.shards()] == [40, 0]
    q = rng.normal(size=(5, dim)).astype(np.float32)
    scores, pids = topk_over_store(q, store, mesh, k=10, chunk=16)
    ref_s = q @ vecs.astype(np.float16).astype(np.float32).T
    ref_idx = np.argsort(-ref_s, axis=1)[:, :10]
    np.testing.assert_allclose(
        scores, np.take_along_axis(ref_s, ref_idx, axis=1),
        rtol=1e-4, atol=1e-4)
    assert (pids >= 0).all() and (pids < 40).all()


def _f64_topk(q, rows, k, valid):
    """The float64 reference: every row's exact score, rows >= valid out,
    the lower row first among equal scores; -inf / -1 past what exists."""
    s = q.astype(np.float64) @ rows.astype(np.float64).T
    s[:, valid:] = -np.inf
    pos = np.argsort(-s, axis=1, kind="stable")[:, :k]
    top = np.take_along_axis(s, pos, axis=1)
    return top, np.where(np.isfinite(top), pos, -1)


def _kernel_case(case):
    """(q, float16 rows, valid, k, block rows) for one case of the kernel's
    test; a small block makes several grid steps of a few hundred rows."""
    rng = np.random.default_rng(40)
    dim = {"d1024": 1024}.get(case, 256)
    n, valid, k, block = 1024, 1024, 10, 256
    rows = (rng.normal(size=(n, dim)) / np.sqrt(dim)).astype(np.float16)
    q = rng.normal(size=(8, dim)).astype(np.float32)
    if case == "valid_inside":
        valid = 601                          # inside the third block
    elif case == "duplicate_rows":
        best = int(np.argmax(q[0] @ rows.astype(np.float32).T))
        rows[[(best + 300) % n, (best + 700) % n]] = rows[best]
    elif case == "best_equals_kth":
        # exact scores: the first block's 10th best is copied into the
        # second block as that block's best, a tie the earlier row wins
        q = (rng.integers(-64, 65, (8, dim)) / 64).astype(np.float32)
        rows = (rng.integers(-64, 65, (n, dim)) / 256).astype(np.float16)
        s = q @ rows.astype(np.float32).T
        kth = int(np.argsort(-s[0, :block], kind="stable")[k - 1])
        rows[block:2 * block] = rows[block:2 * block] * np.float16(0.25)
        rows[block + 5] = rows[kth]
    elif case == "padded_query_block":
        q = q[:5]                            # zero rows up to 8
    return q, rows, valid, k, block


@pytest.mark.parametrize("case", [
    "d256", "d1024", "valid_inside", "duplicate_rows", "best_equals_kth",
    "padded_query_block", "carry_folded"])
def test_exact_scan_kernel_matches_float64(eight_devices, monkeypatch, case):
    """`exact_scan` (Pallas interpret mode here) against a float64 numpy
    reference: the same ids in the same order, scores to float32 rounding,
    over 256- and 1,024-wide rows in blocks of 256; rows at or past `valid`
    score -inf with id -1; of two equal rows the lower id comes first; a
    block whose best only ties the running k-th adds nothing; a query
    block padded with zero queries answers the real ones; and through the
    carried scan on a two-device mesh the carry folds in after the scan,
    the earlier launch first."""
    import jax

    from dnn_page_vectors_tpu.ops import topk
    q, rows, valid, k, block = _kernel_case(case)
    monkeypatch.setattr(topk, "_SCAN_BLOCK_BYTES", block * rows.shape[1] * 2)
    want_s, want_i = _f64_topk(q, rows, k, valid)
    if case == "carry_folded":
        mesh = make_mesh(MeshConfig(data=2))
        half = rows.shape[0] // 2
        shards = [(rows[:half], None, half), (rows[half:], None, 900 - half)]
        got_s, got_i = _carried(mesh, k, q, shards, False)
        want_s, want_i = _f64_topk(q, rows, k, 900)
    else:
        got_s, got_i = jax.jit(topk._kernel_topk, static_argnums=2)(
            jnp.asarray(q), jnp.asarray(topk.pair_words(rows)), k,
            jnp.int32(valid))
    got_s, got_i = np.asarray(got_s), np.asarray(got_i)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-6, atol=1e-6)
    if case == "valid_inside":
        assert (got_i < valid).all()
    if case == "duplicate_rows":
        same = np.nonzero((rows == rows[got_i[0, 0]]).all(axis=1))[0]
        assert len(same) == 3 and list(got_i[0, :3]) == sorted(same)
    if case == "best_equals_kth":
        assert block + 5 not in got_i[0] and np.isfinite(got_s).all()


@pytest.mark.parametrize("side", ["float16_rows", "float32_queries"])
def test_the_split_into_bfloat16_pieces_is_exact(side):
    """The kernel's arithmetic rests on two exact splits: every finite
    float16 bit pattern, subnormals and both zeros included, equals
    float32(hi) + float32(lo) of its two bfloat16 pieces; and the three
    bfloat16 pieces of a float32 query sum back to it, bit for bit."""
    import jax

    from dnn_page_vectors_tpu.ops.topk import f16_pieces, split_query
    if side == "float16_rows":
        bits = np.arange(65536, dtype=np.uint32)
        bits = bits[np.isfinite(bits.astype(np.uint16).view(np.float16))]
        hi, lo = jax.jit(f16_pieces)(jnp.asarray(bits))
        got = np.asarray(hi, np.float32) + np.asarray(lo, np.float32)
        want = bits.astype(np.uint16).view(np.float16).astype(np.float32)
    else:
        rng = np.random.default_rng(41)
        want = np.concatenate([
            rng.normal(size=4096), rng.normal(size=4096) * 1e-3,
            rng.uniform(-1, 1, 4096) / 16]).astype(np.float32)
        pieces = jax.jit(split_query)(jnp.asarray(want))
        got = sum(np.asarray(p, np.float32).astype(np.float64)
                  for p in pieces).astype(np.float32)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("form", ["float16", "pair_words", "pair_words_k200",
                                  "int8_scales", "float32"])
def test_the_pages_dtype_picks_the_scan_body(eight_devices, form):
    """The pages' dtype picks the body: a float16 store staged as pair
    words (`stage_shard(words=True)`, the same bytes) runs `exact_scan`
    when k fits its 128 lanes; a wider k over pair words, float16 rows as
    they are, int8 codes with scales (`jit_run`) and float32 rows run the
    XLA scan. No option chooses it."""
    import jax

    from dnn_page_vectors_tpu.ops.topk import (
        pair_words, sharded_topk_fn, stage_shard)
    mesh = make_mesh(MeshConfig(data=2))
    rng = np.random.default_rng(42)
    rows = rng.normal(size=(64, 16)).astype(np.float16)
    scaled = form == "int8_scales"
    words = form.startswith("pair_words")
    k = 200 if form == "pair_words_k200" else 10
    raw = {"int8_scales": rows.astype(np.int8),
           "float32": rows.astype(np.float32)}.get(form, rows)
    pages, scales = stage_shard(raw, 64, 16, mesh,
                                scales=np.ones(64, np.float16) if scaled
                                else None, words=words)
    if words:
        assert pages.dtype == jnp.uint32 and pages.shape == (64, 8)
        np.testing.assert_array_equal(np.asarray(pages), pair_words(rows))
    args = [jax.ShapeDtypeStruct((8, 16), jnp.float32), pages]
    args += [scales] if scaled else []
    args += [jax.ShapeDtypeStruct((2,), jnp.int32),
             jax.ShapeDtypeStruct((8, 2 * k), jnp.int32)]
    jaxpr = str(jax.make_jaxpr(sharded_topk_fn(mesh, k, scaled=scaled))(
        *args))
    assert ("exact_scan" in jaxpr) == (form == "pair_words")


@pytest.mark.parametrize("path", ["carried", "topk_over_store"])
def test_pair_words_answer_a_top_k_past_the_lanes(eight_devices, tmp_path,
                                                  path):
    """A float16 store staged as pair words answers k = 200, wider than
    `exact_scan`'s 128 lanes, through the XLA scan, which decodes the words
    a chunk at a time: on the carried path bit for bit what the same rows
    as float16 give, and through `topk_over_store` the float64 ranking of
    the stored vectors, page ids and all."""
    from dnn_page_vectors_tpu.ops.topk import pair_words
    mesh = make_mesh(MeshConfig(data=2))
    rng = np.random.default_rng(43)
    dim, n, k = 16, 700, 200
    rows = (rng.normal(size=(n, dim)) / 4).astype(np.float16)
    rows[5, :3] = [6e-8, -3e-6, 1e-5]             # float16 subnormals
    q = rng.normal(size=(8, dim)).astype(np.float32)
    if path == "carried":
        pad = np.zeros((768 - n, dim), np.float16)
        full = np.concatenate([rows, pad])
        shards = [(full[s:s + 256], None, min(256, n - s))
                  for s in range(0, 768, 256)]
        got_s, got_i = _carried(mesh, k, q, [
            (pair_words(r), None, v) for r, _, v in shards], False)
        want_s, want_i = _carried(mesh, k, q, shards, False)
        np.testing.assert_array_equal(got_s.view(np.int32),
                                      want_s.view(np.int32))
        np.testing.assert_array_equal(got_i, want_i)
        ref_s, ref_i = _f64_topk(q, rows, k, n)
    else:
        from dnn_page_vectors_tpu.infer.vector_store import VectorStore
        ids = np.arange(5000, 5000 + n)
        store = VectorStore(str(tmp_path / "store"), dim=dim, shard_size=256)
        for si in range(3):
            sl = slice(si * 256, min((si + 1) * 256, n))
            store.write_shard(si, ids[sl], rows[sl])
        got_s, got_i = topk_over_store(q, store, mesh, k=k, chunk=64,
                                       query_batch=8)
        ref_s, ref_i = _f64_topk(q, rows, k, n)
        ref_i = ids[ref_i]
    np.testing.assert_array_equal(got_i, ref_i)
    np.testing.assert_allclose(got_s, ref_s, rtol=2e-6, atol=1e-6)
