"""Profiling produces an actual trace (VERDICT r3 Weak #4: `--profile` was
smoke-only; nothing asserted a trace appears) — plus the LatencyStats /
PipelineProfiler contracts the observability PR leans on: bounded-memory
reservoir with nearest-rank percentile semantics stable across the change,
per-stage call counts next to the cumulative seconds, and the collector's
hook under the stages."""
import glob
import os
import threading
import time

import pytest

from dnn_page_vectors_tpu.config import get_config
from dnn_page_vectors_tpu.train.loop import Trainer
from dnn_page_vectors_tpu.utils.profiling import (
    LatencyStats, PipelineProfiler, maybe_profile)


def _tree_files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]


@pytest.mark.slow
def test_maybe_profile_writes_trace_around_train_step(tmp_path):
    cfg = get_config("cdssm_toy", {
        "data.num_pages": 64, "data.trigram_buckets": 512,
        "model.embed_dim": 16, "model.conv_channels": 16,
        "model.out_dim": 16,
        "train.batch_size": 16, "train.log_every": 1000,
    })
    trainer = Trainer(cfg, workdir=str(tmp_path))
    with maybe_profile(True, str(tmp_path)):
        trainer.train(steps=1)
    trace_dir = os.path.join(str(tmp_path), "trace")
    assert os.path.isdir(trace_dir)
    files = _tree_files(trace_dir)
    assert files, "profiler produced an empty trace directory"
    # jax.profiler writes TensorBoard-readable artifacts under
    # plugins/profile/<run>/
    assert any("plugins" in f for f in files), files


def test_maybe_profile_disabled_is_a_no_op(tmp_path):
    with maybe_profile(False, str(tmp_path / "w")):
        pass
    assert not os.path.exists(str(tmp_path / "w" / "trace"))


# -- LatencyStats: nearest-rank percentile edges on the bounded reservoir --

def _ref_percentile_ms(samples, q):
    """The pre-reservoir implementation, verbatim: nearest rank over ALL
    samples. The bounded version must match it exactly below the cap."""
    if not samples:
        return 0.0
    s = sorted(samples)
    rank = max(0, min(len(s) - 1, int(-(-q * len(s) // 100)) - 1))
    return s[rank] * 1000.0


def test_percentile_empty_and_single_sample():
    lat = LatencyStats()
    assert lat.percentile_ms(50) == 0.0 and lat.percentile_ms(99) == 0.0
    lat.add(0.004)
    for q in (0, 1, 50, 99, 100):    # n=1: every percentile IS the sample
        assert lat.percentile_ms(q) == pytest.approx(4.0)


@pytest.mark.parametrize("n", [2, 3, 4, 5, 10, 11])
def test_percentile_q0_q100_even_odd_match_unbounded_semantics(n):
    samples = [(i * 7 % n + 1) / 1000.0 for i in range(n)]   # shuffled-ish
    lat = LatencyStats()
    for s in samples:
        lat.add(s)
    assert len(lat) == n
    for q in (0, 25, 50, 75, 99, 100):
        assert lat.percentile_ms(q) == pytest.approx(
            _ref_percentile_ms(samples, q)), (n, q)
    # q=0 is the min, q=100 the max, even-count p50 the LOWER middle
    assert lat.percentile_ms(0) == pytest.approx(min(samples) * 1000.0)
    assert lat.percentile_ms(100) == pytest.approx(max(samples) * 1000.0)
    if n % 2 == 0:
        assert lat.percentile_ms(50) == pytest.approx(
            sorted(samples)[n // 2 - 1] * 1000.0)


def test_latency_stats_summary_keys_stable_and_memory_bounded():
    """summary() keys are byte-identical to the pre-reservoir version, and
    a long-lived service stops growing: past `cap` samples the buffer is
    bounded while count/mean stay exact."""
    lat = LatencyStats(cap=64, seed=0)
    for i in range(10_000):
        lat.add((i % 100 + 1) / 1000.0)
    assert list(lat.summary()) == ["lat_count", "lat_mean_ms",
                                   "lat_p50_ms", "lat_p99_ms"]
    s = lat.summary()
    assert s["lat_count"] == 10_000                 # exact, not sampled
    assert s["lat_mean_ms"] == pytest.approx(50.5, abs=0.1)
    assert len(lat._res._buf) == 64                 # bounded buffer
    assert 1.0 <= s["lat_p50_ms"] <= 100.0          # a delivered sample


def test_pipeline_profiler_summary_emits_counts_next_to_seconds():
    prof = PipelineProfiler()
    for _ in range(3):
        prof.add("tokenize", 0.5)
    prof.add("h2d", 0.25)
    s = prof.summary()
    assert s["stage_tokenize_s"] == pytest.approx(1.5)
    assert s["stage_tokenize_n"] == 3               # mean-per-call from
    assert s["stage_h2d_s"] == pytest.approx(0.25)  # ONE metrics line
    assert s["stage_h2d_n"] == 1
    assert list(s) == ["stage_h2d_s", "stage_h2d_n",
                       "stage_tokenize_s", "stage_tokenize_n"]


def _host_events(trace_dir):
    """{event name: [(thread line's index, duration_ns)]} over the host
    planes of the one .xplane.pb under `trace_dir`, read with jax alone
    (a line's name is the OS thread's, the same for every Python thread)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.setdefault(ev.name, []).append((i, ev.duration_ns))
    return out


def test_stage_is_an_event_on_the_profilers_clock(tmp_path):
    """Every stage() is also a TraceAnnotation: under a profiler session
    the event is in the trace under `prefix + stage`, on the thread that
    ran it, as long as the seconds the profiler summed for it — while the
    dictionary keys stay bare and add() alone records no event."""
    prof = PipelineProfiler(prefix="serve.")
    bare = PipelineProfiler()

    def on_other_thread():
        with prof.stage("merge"):
            time.sleep(0.03)

    with maybe_profile(True, str(tmp_path)):
        for _ in range(3):
            with prof.stage("topk"):
                time.sleep(0.02)
        t = threading.Thread(target=on_other_thread, name="other")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with bare.stage("h2d"):
            time.sleep(0.01)
        prof.add("queue_wait", 0.5)
    ev = _host_events(os.path.join(str(tmp_path), "trace"))
    assert len(ev["serve.topk"]) == 3 and len(ev["serve.merge"]) == 1
    assert len(ev["h2d"]) == 1                   # no prefix: the bare name
    for name, p in (("topk", prof), ("merge", prof), ("h2d", bare)):
        event = p._prefix + name
        traced_s = sum(d for _, d in ev[event]) / 1e9
        assert traced_s == pytest.approx(p.stages()[name], rel=0.05), event
    assert {ln for ln, _ in ev["serve.merge"]}.isdisjoint(
        ln for ln, _ in ev["serve.topk"])        # its own thread's line
    assert not {"serve.queue_wait", "queue_wait", "topk"} & set(ev)
    assert set(prof.stages()) == {"topk", "merge", "queue_wait"}
    assert prof.counts() == {"topk": 3, "merge": 1, "queue_wait": 1}


def _lowered_text(fn, *args):
    return fn.lower(*args).as_text(debug_info=True)


def test_named_scopes_reach_the_lowered_step_and_scan(tmp_path):
    """Where Flax gives no module path the program names the region itself:
    `loss` and `optimizer` in the train step, `sharded_topk.scan` and
    `sharded_topk.local_topk` in the scan — found in the op metadata of
    the programs lowered on the CPU, which is what a --profile trace shows
    per device op. The compiled programs keep their names."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from dnn_page_vectors_tpu.ops import topk
    from dnn_page_vectors_tpu.train.loop import make_train_step
    cfg = get_config("cdssm_toy", {
        "data.num_pages": 64, "data.trigram_buckets": 512,
        "model.embed_dim": 16, "model.conv_channels": 16,
        "model.out_dim": 16, "train.batch_size": 16,
    })
    trainer = Trainer(cfg, workdir=str(tmp_path))
    state = trainer.init_state()
    extra = trainer._tok_extra()
    batch = {"query": jnp.zeros((16, cfg.data.query_len) + extra, jnp.int32),
             "page": jnp.zeros((16, cfg.data.page_len) + extra, jnp.int32)}
    step = jax.jit(make_train_step(trainer.model, trainer.tx))
    text = _lowered_text(step, state, batch, trainer.base_rng())
    assert "jit_train_step" in text
    # under value_and_grad the forward ops read jvp(loss), the backward
    # ones transpose(jvp(loss))
    for scope in ("jit(train_step)/jvp(loss)/",
                  "jit(train_step)/transpose(jvp(loss))/",
                  "jit(train_step)/optimizer/"):
        assert scope in text, scope

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("data", "model", "seq"))
    scan = topk._build_sharded_topk(mesh, 5, 64, False)
    text = _lowered_text(scan, jnp.zeros((4, 16)),
                         jnp.zeros((256, 16), jnp.float16),
                         jnp.array([256, 0], jnp.int32),
                         jnp.zeros((4, 10), jnp.int32))
    assert "jit__lambda" in text     # trace_modules.scan finds it by this
    for scope in ("sharded_topk.scan/", "sharded_topk.local_topk/"):
        assert scope in text, scope


def test_flash_kernels_carry_their_names():
    """Each pallas_call of ops/flash_attention.py has a `name=`: forward,
    dq (no bias), dq + dbias, and dk/dv — read off the traced program."""
    import re

    import jax
    import jax.numpy as jnp

    from dnn_page_vectors_tpu.ops.flash_attention import flash_attention
    q = jnp.zeros((1, 2, 128, 32))
    mask = jnp.ones((1, 128), bool)

    def plain(q, k, v):
        return flash_attention(q, k, v, mask, interpret=True).sum()

    def biased(q, k, v, b):
        return flash_attention(q, k, v, mask, bias=b, interpret=True).sum()

    def names(jaxpr):
        return set(re.findall(r"flash_\w+", str(jaxpr)))

    assert names(jax.make_jaxpr(jax.grad(plain, argnums=(0, 1, 2)))(
        q, q, q)) == {"flash_fwd", "flash_dq", "flash_dkv"}
    assert names(jax.make_jaxpr(jax.grad(biased, argnums=(0, 3)))(
        q, q, q, jnp.zeros((2, 128, 128)))) == {
            "flash_fwd", "flash_dq_dbias", "flash_dkv"}


def test_train_marks_its_steps_and_logs_rates_without_the_compile(tmp_path):
    """Trainer.train under --profile: one `train` step mark per iteration
    and the loop's own stages as `train.<stage>` events; the logged
    pages_per_sec_per_chip covers the steps since the barrier behind the
    first one, so the compile (most of this toy run's wall time) is not in
    it."""
    import json
    cfg = get_config("cdssm_toy", {
        "data.num_pages": 64, "data.trigram_buckets": 512,
        "model.embed_dim": 16, "model.conv_channels": 16,
        "model.out_dim": 16,
        "train.batch_size": 16, "train.log_every": 4,
    })
    trainer = Trainer(cfg, workdir=str(tmp_path))
    state = trainer.init_state()
    t0 = time.perf_counter()
    with maybe_profile(True, str(tmp_path)):
        _, last = trainer.train(steps=4, state=state)
    wall = time.perf_counter() - t0
    ev = _host_events(os.path.join(str(tmp_path), "trace"))
    assert len(ev["train"]) == 4
    assert len(ev["train.compute"]) == 4 and len(ev["train.sync"]) == 1
    # 3 of the 4 steps lie behind the barrier; had the compile been inside
    # the interval the rate would be under 4 steps' pages over the wall
    since_t0 = 4 * 16 / wall / trainer.mesh.devices.size
    assert last["pages_per_sec_per_chip"] > 3 * since_t0, (last, wall)
    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        assert json.loads(f.readlines()[-1])["step"] == 4


def test_gc_event_marks_second_generation_passes_alone(tmp_path):
    """In a recorded trace the collector's hook names each second-
    generation pass `<prefix>gc` and leaves the younger ones out, which
    the tracer's own allocations make hundreds a second: every pass is
    still summed as stage `gc`."""
    import gc
    prof = PipelineProfiler(prefix="serve.")
    prof.watch_gc()
    try:
        with maybe_profile(True, str(tmp_path)):
            for _ in range(3):
                gc.collect(0)
            gc.collect()
        n = prof.counts()
    finally:
        prof.unwatch_gc()
    assert n["gc"] >= 4 and n["gc_gen2"] >= 1
    ev = _host_intervals(os.path.join(str(tmp_path), "trace"))
    assert len(ev["serve.gc"]) == n["gc_gen2"]


def test_watch_gc_counts_passes_and_unwatch_restores_callbacks():
    import gc
    before = list(gc.callbacks)
    prof = PipelineProfiler(prefix="serve.")
    prof.watch_gc()
    prof.watch_gc()                                  # idempotent
    try:
        assert len(gc.callbacks) == len(before) + 1
        assert prof.counts()["gc"] == 0 == prof.counts()["gc_gen2"]
        gc.collect()
        gc.collect(0)
        sec, n = prof.stages(), prof.counts()
        assert n["gc"] >= 2 and n["gc_gen2"] >= 1
        assert sec["gc"] >= sec["gc_gen2"] > 0.0
        assert prof.gc_seconds() == pytest.approx(sec["gc"])
        s = prof.summary()
        assert s["stage_gc_n"] == n["gc"] and "stage_gc_gen2_s" in s
        prof.reset()
        assert prof.counts()["gc"] == 0 and prof.gc_seconds() > 0.0
    finally:
        prof.unwatch_gc()
    assert gc.callbacks == before
    gc.collect()
    assert "gc" not in prof.counts()
    # a profiler dropped while watching takes its hook with it
    lost = PipelineProfiler()
    lost.watch_gc()
    del lost
    gc.collect()
    assert gc.callbacks == before


def test_collection_during_add_on_another_thread_does_not_deadlock():
    """The hook takes no lock: passes forced on this thread while another
    thread loops over add() (holding the profiler's lock most of the time)
    all finish, and every add is counted."""
    import gc
    import sys
    prof = PipelineProfiler()
    prof.watch_gc()
    stop = threading.Event()
    adds = []

    def adder():
        k = 0
        while not stop.is_set():
            prof.add("topk", 1e-6)
            junk = [[] for _ in range(50)]       # cycles of work for gen 0
            junk[0].append(junk)
            k += 1
        adds.append(k)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t = threading.Thread(target=adder, daemon=True)
        t.start()
        done = threading.Event()

        def collect():
            for _ in range(30):
                gc.collect()
            done.set()

        c = threading.Thread(target=collect, daemon=True)
        c.start()
        assert done.wait(timeout=60), "a collection deadlocked"
        stop.set()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
        prof.unwatch_gc()
    assert prof.counts()["topk"] == adds[0]


@pytest.fixture(scope="module")
def toy_service(tmp_path_factory):
    """An untrained toy tower over a 2-shard store: enough for the serve
    path's stages and spans, no training."""
    from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    wd = str(tmp_path_factory.mktemp("profiling_serve"))
    cfg = get_config("cdssm_toy", {
        "data.num_pages": 64, "data.trigram_buckets": 512,
        "model.embed_dim": 16, "model.conv_channels": 16,
        "model.out_dim": 16, "train.batch_size": 16,
        "eval.embed_batch_size": 32, "eval.store_shard_size": 32})
    trainer = Trainer(cfg, workdir=wd)
    state = trainer.init_state()
    emb = BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                       trainer.mesh, query_tok=trainer.query_tok)
    store = VectorStore(os.path.join(wd, "store"), dim=cfg.model.out_dim,
                        shard_size=32)
    store.ensure_model_step(int(state.step))
    emb.embed_corpus(trainer.corpus, store)
    return cfg, trainer, emb, store


def _host_intervals(trace_dir):
    """{event name: [(thread line's index, start_ns, end_ns)]} over the
    host planes, on the wall clock (the session's `profile_start_time`
    plus each event's offset)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(path)
    t0 = dict(data.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (i, t0 + ev.start_ns, t0 + ev.end_ns))
    return out


def test_encode_children_and_request_spans_on_the_profilers_clock(
        toy_service, tmp_path):
    """In a recorded CPU trace of requests through the batcher, every
    `serve.encode_launch` and `serve.encode_wait` lies inside a
    `serve.encode` on the serve-batcher thread (the line of its
    `serve.batch_window`), and the exported request span `encode` starts
    within 1 ms of its `serve.encode` annotation: one clock for both."""
    from dnn_page_vectors_tpu.infer.serve import SearchService
    cfg, trainer, emb, store = toy_service
    svc = SearchService(cfg, emb, trainer.corpus, store, preload_hbm_gb=1.0)
    svc.start_batcher()
    try:
        assert svc.search(trainer.corpus.query_text(1), k=3)   # compiles
        svc.tracer.clear()
        with maybe_profile(True, str(tmp_path)):
            for i in range(2, 5):
                assert svc.search(trainer.corpus.query_text(i), k=3)
    finally:
        svc.close()
    ev = _host_intervals(os.path.join(str(tmp_path), "trace"))
    batcher = {ln for ln, _, _ in ev["serve.batch_window"]}
    assert len(batcher) == 1
    encodes = ev["serve.encode"]
    assert len(encodes) == 3
    for child in ("serve.encode_launch", "serve.encode_wait"):
        assert len(ev[child]) == 3
        for ln, s, e in ev[child]:
            assert ln in batcher
            assert any(ln == pl and ps <= s and e <= pe
                       for pl, ps, pe in encodes), child
    starts_us = sorted(e["ts"] for e in svc.tracer.chrome_trace()[
        "traceEvents"] if e["name"] == "encode")
    assert len(starts_us) == 3
    for ts, (_, s, _) in zip(starts_us, sorted(encodes, key=lambda x: x[1])):
        assert abs(ts * 1e3 - s) < 1e6, (ts * 1e3 - s)
