"""Observability subsystem (utils/telemetry.py, utils/tracing.py,
docs/OBSERVABILITY.md): typed registry instruments with bounded memory and
rolling windows, request-scoped tracing through the serving path —
including the micro-batcher's thread hop — the slow-query log, Chrome
trace_event export, windowed SLO gauges, and the obs.* knob/doc drift
check."""
import dataclasses
import json
import os
import re
import threading

import pytest

from dnn_page_vectors_tpu.config import ObsConfig, get_config
from dnn_page_vectors_tpu.utils import faults
from dnn_page_vectors_tpu.utils.logging import MetricsLogger
from dnn_page_vectors_tpu.utils.telemetry import (
    MetricsRegistry, Reservoir, default_registry, reset_default)
from dnn_page_vectors_tpu.utils.tracing import NULL_SPAN, Tracer

pytestmark = pytest.mark.obs

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry instruments
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("x.count")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("x.count") is c          # get-or-create by name
    g = reg.gauge("x.gauge")
    g.set(2.5)
    assert reg.gauge("x.gauge").value == 2.5
    h = reg.histogram("x.hist", window_s=None)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4 and h.mean == 2.5
    assert h.percentile(50) == 2.0              # lower middle, even count
    assert h.percentile(100) == 4.0
    with pytest.raises(TypeError):              # a name is one kind forever
        reg.gauge("x.count")


def test_windowed_counter_rate_rolls_off():
    clock = _FakeClock()
    reg = MetricsRegistry(clock=clock)
    c = reg.counter("qps", window_s=10.0)
    c.inc(20)
    clock.t = 5.0
    c.inc(10)
    assert c.window_count() == 30
    assert c.rate() == pytest.approx(3.0)
    clock.t = 12.0                              # first burst aged out
    assert c.window_count() == 10
    assert c.rate() == pytest.approx(1.0)
    clock.t = 50.0
    assert c.rate() == 0.0
    assert c.value == 30                        # the total never rolls off


def test_windowed_histogram_percentiles_roll_off():
    clock = _FakeClock()
    reg = MetricsRegistry(clock=clock)
    h = reg.histogram("lat", window_s=10.0)
    h.observe(100.0)
    clock.t = 8.0
    h.observe(1.0)
    assert h.window_percentile(99) == 100.0
    clock.t = 15.0                              # the 100ms sample aged out
    assert h.window_percentile(99) == 1.0
    assert h.percentile(99) == 100.0            # since-boot view keeps it


def test_reservoir_is_bounded_with_exact_count_and_mean():
    r = Reservoir(cap=128, seed=0)
    n = 50_000
    for i in range(n):
        r.add(float(i))
    assert r.count == n
    assert len(r._buf) == 128                   # bounded, not 50k
    assert r.sum == pytest.approx(n * (n - 1) / 2)
    # the sampled median of 0..n-1 lands near the true median
    assert 0.2 * n < r.percentile(50) < 0.8 * n


def test_registry_snapshot_is_json_serializable_and_prometheus_exposes():
    reg = MetricsRegistry()
    reg.counter("serve.requests", window_s=10.0).inc(7)
    reg.gauge("serve.degraded").set(0.0)
    reg.histogram("serve.latency_ms").observe(1.5)
    reg.event("view_swap", {"store_generation": 2}, trace_id="t-abc")
    snap = json.loads(json.dumps(reg.snapshot()))     # round-trips
    assert snap["counters"]["serve.requests"]["value"] == 7
    assert "rate_per_s" in snap["counters"]["serve.requests"]
    assert snap["gauges"]["serve.degraded"] == 0.0
    assert snap["histograms"]["serve.latency_ms"]["count"] == 1
    assert snap["events"][0]["event"] == "view_swap"
    assert snap["events"][0]["trace_id"] == "t-abc"
    text = reg.prometheus_text()
    assert "# TYPE serve_requests counter" in text
    assert "serve_requests 7" in text
    assert 'serve_latency_ms{quantile="0.99"}' in text
    assert "serve_latency_ms_count 1" in text


def test_event_ring_is_bounded():
    reg = MetricsRegistry(events=4)
    for i in range(10):
        reg.event("e", {"i": i})
    evs = reg.events("e")
    assert len(evs) == 4 and evs[0]["attrs"]["i"] == 6


def test_fault_counters_mirror_into_default_registry():
    reset_default()
    faults.reset()
    try:
        faults.count("test_mirror_event", 3)
        c = default_registry().counter("fault.test_mirror_event")
        assert c.value == 3
    finally:
        faults.reset()
        reset_default()


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_span_tree_nesting_and_attrs():
    tr = Tracer()
    with tr.trace("root", k=10) as root:
        with tr.span("a"):
            with tr.span("b") as b:
                b.set_attrs(x=1)
        with tr.span("c"):
            pass
    d = tr.last_trace()
    assert d["name"] == "root" and d["attrs"]["k"] == 10
    assert [c["name"] for c in d["children"]] == ["a", "c"]
    assert d["children"][0]["children"][0]["attrs"]["x"] == 1
    assert d["dur_ms"] >= 0.0
    assert root.names() == ["root", "a", "b", "c"]


def test_disabled_tracer_is_a_null_no_op():
    tr = Tracer(enabled=False)
    with tr.trace("root") as root:
        assert root is NULL_SPAN
        with tr.span("a") as sp:
            assert sp is NULL_SPAN
        root.set_attrs(x=1).child("q", 0.1)     # mutators must not raise
    assert tr.traces() == [] and tr.current() is None


def test_span_survives_thread_hop_via_explicit_handoff():
    """The micro-batcher pattern: capture current() on the caller thread,
    re-activate with use() on the worker thread."""
    tr = Tracer()
    done = threading.Event()

    def worker(ctx):
        with tr.use(ctx):
            with tr.span("worker_stage"):
                pass
        ctx.child("queue_wait", 0.002)
        done.set()

    with tr.trace("request") as root:
        t = threading.Thread(target=worker, args=(tr.current(),))
        t.start()
        done.wait(5)
        t.join(5)
    names = tr.last_trace()
    names = [c["name"] for c in names["children"]]
    assert "worker_stage" in names and "queue_wait" in names


def test_slow_query_log_threshold_semantics():
    never = Tracer(slow_ms=-1)                  # negative disables
    with never.trace("r"):
        pass
    assert never.slow_queries() == []
    every = Tracer(slow_ms=0)                   # 0 captures everything
    with every.trace("r"):
        pass
    assert len(every.slow_queries()) == 1
    high = Tracer(slow_ms=60_000)
    with high.trace("r"):
        pass
    assert high.slow_queries() == []


def test_chrome_trace_export_is_valid_trace_event_json():
    tr = Tracer()
    with tr.trace("root"):
        with tr.span("tokenize"):
            pass
        with tr.span("topk"):
            pass
    out = json.loads(json.dumps(tr.chrome_trace()))
    evs = out["traceEvents"]
    assert len(evs) == 3
    names = {e["name"] for e in evs}
    assert names == {"root", "tokenize", "topk"}
    for e in evs:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float)) and e["dur"] >= 0
        assert "trace_id" in e["args"]
    root = next(e for e in evs if e["name"] == "root")
    for e in evs:                               # children inside the root
        assert e["ts"] >= root["ts"] - 1e-3
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-3


# ---------------------------------------------------------------------------
# MetricsLogger re-base (satellite)
# ---------------------------------------------------------------------------

def test_metrics_logger_context_manager_and_post_close_write(tmp_path):
    path = os.path.join(str(tmp_path), "metrics.jsonl")
    with MetricsLogger(str(tmp_path), echo=False) as log:
        log.write({"a": 1})
    assert log.closed
    log.write({"b": 2})                         # tolerated, not written
    log.close()                                 # idempotent
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 1
    # jsonl shape unchanged: ts + the written keys, nothing else
    assert set(lines[0]) == {"ts", "a"} and lines[0]["a"] == 1


def test_metrics_logger_mirrors_scalars_into_registry(tmp_path):
    reg = MetricsRegistry()
    with MetricsLogger(str(tmp_path), echo=False, registry=reg) as log:
        log.write({"pages_per_sec_per_chip": 123.5, "note": "text",
                   "degraded": False})
    assert reg.gauge("pages_per_sec_per_chip").value == 123.5
    snap = reg.snapshot()
    assert "note" not in snap["gauges"]         # only numeric scalars
    assert "degraded" not in snap["gauges"]     # bools are flags, not gauges


# ---------------------------------------------------------------------------
# obs.* knob / doc drift (satellite)
# ---------------------------------------------------------------------------

def _drift_findings(rule: str):
    """The generalized graftcheck drift rules (docs/ANALYSIS.md) subsume
    the two hand-rolled checks that used to live here; these wrappers
    keep the old test names so history and `-k` habits survive."""
    from dnn_page_vectors_tpu.tools.analyze import analyze
    return analyze(root=_REPO, rules=[rule]).findings


def test_documented_obs_knobs_match_config():
    """Every `obs.*` knob named in docs/OBSERVABILITY.md exists as an
    ObsConfig field, and every field is documented — the knob table and
    the dataclass cannot drift apart silently. (Thin wrapper over the
    `drift-knobs` rule, which now covers EVERY config section.)"""
    findings = _drift_findings("drift-knobs")
    assert not findings, "\n".join(f.human() for f in findings)
    # the wrapped rule really is checking the obs section, not vacuously
    # passing on a renamed dataclass
    assert {f.name for f in dataclasses.fields(ObsConfig)}


def test_emitted_event_names_are_documented():
    """Every lifecycle event name emitted through `registry.event(...)`
    anywhere in the package appears (backticked) in the
    docs/OBSERVABILITY.md event table — a new PR cannot add a silent
    event; conversely every documented name is really emitted somewhere,
    so the table never advertises dead events. (Thin wrapper over the
    `drift-events` rule.)"""
    findings = _drift_findings("drift-events")
    assert not findings, "\n".join(f.human() for f in findings)
    # the scan itself still sees a healthy event population
    doc = open(os.path.join(_REPO, "docs", "OBSERVABILITY.md")).read()
    documented = set(re.findall(r"^\|\s*`([a-z_]+)`", doc, re.M))
    assert len(documented) >= 10, f"event-table drift? {documented}"


def test_obs_config_round_trips_through_overrides():
    cfg = get_config("cdssm_toy", {"obs.slow_ms": "5.5",
                                   "obs.enabled": "false",
                                   "obs.window_s": "3"})
    assert cfg.obs.slow_ms == 5.5
    assert cfg.obs.enabled is False
    assert cfg.obs.window_s == 3.0


# ---------------------------------------------------------------------------
# end to end: the traced serving path on a real toy store
# ---------------------------------------------------------------------------

_OV = {
    "data.num_pages": 300,
    "data.trigram_buckets": 2048,
    "model.embed_dim": 48,
    "model.conv_channels": 96,
    "model.out_dim": 48,
    "train.batch_size": 64,
    "train.steps": 60,
    "train.warmup_steps": 10,
    "train.learning_rate": 2e-3,
    "train.log_every": 1000,
    "eval.embed_batch_size": 100,
    "eval.store_shard_size": 100,   # 3 shards: exercises the device merge
}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One trained model + embedded 3-shard store + IVF index for the
    whole module (training dominates; services stage cheaply per test)."""
    from dnn_page_vectors_tpu.index.ivf import IVFIndex
    from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    from dnn_page_vectors_tpu.train.loop import Trainer
    wd = str(tmp_path_factory.mktemp("telemetry_serve"))
    cfg = get_config("cdssm_toy", _OV)
    trainer = Trainer(cfg, workdir=wd)
    state, _ = trainer.train()
    emb = BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                       trainer.mesh, query_tok=trainer.query_tok)
    store = VectorStore(os.path.join(wd, "store"), dim=cfg.model.out_dim,
                        shard_size=100)
    store.ensure_model_step(int(state.step))
    emb.embed_corpus(trainer.corpus, store)
    IVFIndex.build(store, emb.mesh, seed=0)
    return cfg, trainer, emb, store


def _cfg_with(cfg, obs=None, serve=None):
    out = cfg
    if obs:
        out = out.replace(obs=dataclasses.replace(out.obs, **obs))
    if serve:
        out = out.replace(serve=dataclasses.replace(out.serve, **serve))
    return out


def _svc(served, preload=0.0, obs=None, serve=None):
    from dnn_page_vectors_tpu.infer.serve import SearchService
    cfg, trainer, emb, store = served
    return SearchService(_cfg_with(cfg, obs=obs, serve=serve), emb,
                         trainer.corpus, store, preload_hbm_gb=preload)


def test_traced_search_span_tree_slow_log_and_export(served):
    """THE acceptance pin: a traced search() through the micro-batcher on
    the HBM-resident toy store produces a span tree covering
    queue_wait -> tokenize -> encode -> topk -> merge -> format, the trace
    lands in the slow-query log at obs.slow_ms=0, and the recent-trace
    ring exports as valid Chrome trace_event JSON."""
    _, trainer, _, _ = served
    svc = _svc(served, preload=4.0, obs={"slow_ms": 0.0})
    assert svc.preloaded
    svc.start_batcher()
    try:
        res = svc.search(trainer.corpus.query_text(7), k=5)
    finally:
        svc.close()
    assert res and all("page_id" in r for r in res)
    roots = [t for t in svc.tracer.traces() if t["name"] == "search"]
    assert roots, [t["name"] for t in svc.tracer.traces()]

    def _names(d):
        out = [d["name"]]
        for c in d["children"]:
            out.extend(_names(c))
        return set(out)

    want = {"search", "queue_wait", "tokenize", "encode", "topk", "merge",
            "format"}
    assert want <= _names(roots[-1]), _names(roots[-1])
    # slow_ms=0 captures every request, full tree included
    slow = svc.tracer.slow_queries()
    assert slow and want <= _names(slow[-1])
    # export: valid trace_event JSON, one complete event per span
    chrome = json.loads(json.dumps(svc.tracer.chrome_trace()))
    evs = chrome["traceEvents"]
    assert {e["name"] for e in evs} >= want
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 0 and "trace_id" in e["args"]


def test_ann_topk_span_carries_index_attributes(served):
    """With an active IVF index the request's topk span reports the ANN
    cost triple — lists_scanned / gather_bytes / rows_reranked — and the
    registry counters move with it."""
    _, trainer, _, _ = served
    svc = _svc(served, serve={"index": "ivf"})
    assert svc._index is not None
    svc.search_many([trainer.corpus.query_text(3)], k=5)
    trace = svc.tracer.last_trace()
    assert trace["name"] == "search_many"

    def _find(d, name):
        if d["name"] == name:
            return d
        for c in d["children"]:
            hit = _find(c, name)
            if hit:
                return hit
        return None

    topk = _find(trace, "topk")
    assert topk is not None
    assert topk["attrs"]["lists_scanned"] > 0
    assert topk["attrs"]["gather_bytes"] > 0
    assert topk["attrs"]["rows_reranked"] > 0
    assert svc.ann_fallbacks == 0
    assert svc.ann_lists_scanned == topk["attrs"]["lists_scanned"]
    assert svc.registry.counter("serve.ann_gather_bytes").value > 0


def test_windowed_slo_gauges_move_across_bursts(served):
    """Two serve bursts: the windowed qps/p99 gauges change between them
    (the live SLO view tracks traffic), while the since-boot metrics keys
    that dashboards and `cli loadtest` read stay present and the snapshot
    stays json-serializable."""
    _, trainer, _, _ = served
    svc = _svc(served)
    queries = [trainer.corpus.query_text(i) for i in range(6)]
    svc.search_many(queries, k=5)
    m1 = svc.metrics()
    assert m1["serve_window_qps"] > 0
    svc.search_many(queries, k=5)
    svc.search_many(queries, k=5)
    m2 = svc.metrics()
    assert m2["serve_window_qps"] > m1["serve_window_qps"]
    assert m2["serve_window_p99_ms"] > 0
    assert m2["serve_window_s"] == svc.cfg.obs.window_s
    # the pre-registry metrics surface is intact
    for key in ("serve_cache_hits", "serve_cache_misses",
                "serve_cache_hit_rate", "store_generation", "refreshes"):
        assert key in m2
    assert any(k.startswith("serve_stage_") and k.endswith("_s")
               for k in m2)
    assert any(k.startswith("serve_stage_") and k.endswith("_n")
               for k in m2)
    # exposition endpoints: JSON snapshot round-trips, Prometheus text
    # exposes the same instruments
    snap = json.loads(json.dumps(svc.metrics_snapshot()))
    assert snap["counters"]["serve.requests"]["value"] == 18
    assert "serve_requests 18" in svc.prometheus_text()


def test_cache_hit_annotation_on_request_trace(served):
    _, trainer, _, _ = served
    svc = _svc(served)
    q = trainer.corpus.query_text(11)
    svc.search_many([q], k=5)
    first = svc.tracer.last_trace()
    assert first["attrs"]["cache_misses"] == 1
    assert any(c["name"] == "encode" for c in first["children"])
    svc.search_many([q], k=5)                   # repeat: embedding cached
    second = svc.tracer.last_trace()
    assert second["attrs"]["cache_hits"] == 1
    assert second["attrs"]["cache_misses"] == 0
    assert not any(c["name"] == "encode" for c in second["children"])
    assert svc.cache_hits == 1 and svc.cache_misses == 1


def test_refresh_emits_view_swap_event(served):
    svc = _svc(served)
    info = svc.refresh()
    evs = svc.registry.events("view_swap")
    assert len(evs) == 1
    assert evs[0]["attrs"]["store_generation"] == info["store_generation"]
    assert svc.registry.gauge("serve.store_generation").value == \
        info["store_generation"]
    assert svc.refreshes == 1


def test_disabled_tracing_serves_identically(served):
    _, trainer, _, _ = served
    on = _svc(served)
    off = _svc(served, obs={"enabled": False})
    q = trainer.corpus.query_text(42)
    want = on.search_many([q], k=5)[0]
    got = off.search_many([q], k=5)[0]
    assert [r["page_id"] for r in got] == [r["page_id"] for r in want]
    assert off.tracer.traces() == [] and off.tracer.slow_queries() == []
    assert off.metrics()["serve_window_qps"] > 0   # metrics still live


# ---------------------------------------------------------------------------
# the dispatcher thread's spans (PipelineProfiler stages, serve.* events)
# ---------------------------------------------------------------------------

_CHILD_STAGES = ("tokenize", "encode", "topk", "merge", "format")


def _search_through_batcher(svc, trainer, n=24, clients=8):
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(clients) as pool:
        return list(pool.map(
            lambda i: svc.search(trainer.corpus.query_text(i), k=5),
            range(n)))


def test_dispatcher_thread_spans_split_its_time(served):
    """batcher_idle / batch_window / dispatch are stages of the service's
    profiler, timed on the serve-batcher thread alone: one `dispatch` per
    batch that passed the door and parent of the stages under it, so the
    three never sum past the wall clock and the children never past
    `dispatch`; a batch shed whole at the door adds none."""
    import time
    from concurrent.futures import Future

    from dnn_page_vectors_tpu.infer.transport import DeadlineExceeded
    _, trainer, _, _ = served
    svc = _svc(served, preload=4.0, serve={"batch_window_ms": 5.0})
    t0 = time.perf_counter()
    svc.start_batcher()
    b = svc._batcher
    try:
        res = _search_through_batcher(svc, trainer)
        n_dispatch = svc.profiler.counts()["dispatch"]
        shed: Future = Future()
        b._dispatch([("q", (5, None, None), shed, time.perf_counter(), None,
                      svc._clock() - 1.0)])
        with pytest.raises(DeadlineExceeded):
            shed.result(timeout=5)
        assert svc.profiler.counts()["dispatch"] == n_dispatch
    finally:
        svc.close()                       # joins the thread: spans closed
    wall = time.perf_counter() - t0
    assert all(res)
    sec, n = svc.profiler.stages(), svc.profiler.counts()
    assert n["dispatch"] == len(b.batch_sizes) > 0
    assert sum(b.batch_sizes) == n["queue_wait"] == len(res)
    assert sec["batcher_idle"] > 0 and n["batch_window"] == n["dispatch"]
    assert sum(sec[c] for c in _CHILD_STAGES) <= sec["dispatch"]
    assert (sec["batcher_idle"] + sec["batch_window"] + sec["dispatch"]
            <= wall)


def test_stage_sums_accumulate_with_every_tracer_off(served):
    """No jax profiler session and obs.enabled=false: the annotations are
    inert, the request tracer yields NULL_SPAN, and a search through the
    batcher still answers with every stage sum accumulated."""
    _, trainer, _, _ = served
    svc = _svc(served, preload=4.0, obs={"enabled": False})
    svc.start_batcher()
    try:
        res = _search_through_batcher(svc, trainer, n=6, clients=3)
    finally:
        svc.close()
    assert all(res) and svc.tracer.traces() == []
    sec = svc.profiler.stages()
    for name in _CHILD_STAGES + ("dispatch", "batch_window", "batcher_idle",
                                 "queue_wait"):
        assert sec.get(name, 0.0) > 0.0, (name, sec)


def test_fold_of_the_carry_lies_under_the_scans_own_scope(served):
    """The cross-shard merge is the scan's own last step since the running
    top-k rides through the launches: in the program the service launches,
    lowered on the shapes it launches it with, the carry's unpacking, the
    `top_k` over [carry | this shard's candidates] and the pack of the
    winners are all named `sharded_topk.local_topk/...`, and the program
    is still `jit__lambda`."""
    import jax
    import numpy as np

    from dnn_page_vectors_tpu.ops.topk import sharded_topk_fn
    svc = _svc(served, preload=4.0)
    try:
        view, B, k = svc._view, svc.query_batch, 10
        shard = view.shards[0]
        text = sharded_topk_fn(svc.embedder.mesh, k).lower(
            jax.ShapeDtypeStruct((B, view.store.dim), np.float32),
            shard.pages, shard.span,
            jax.ShapeDtypeStruct((B, 2 * k), np.int32)
        ).as_text(debug_info=True)
    finally:
        svc.close()
    assert "@jit__lambda" in text
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))

    def scope_of(pattern):
        (line,) = [ln for ln in text.splitlines() if re.search(pattern, ln)]
        return locs[re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)]

    n_data = svc.embedder.mesh.shape["data"]
    wide = f"{B}x{k + n_data * k}"
    fold = "sharded_topk.local_topk/"
    assert scope_of(rf"chlo\.top_k.*tensor<{wide}xf32>").endswith(
        fold + "top_k")
    for pattern in (                         # unpack the carry, pack the out
            rf"bitcast_convert .*tensor<{B}x{k}xi32>\) -> tensor<{B}x{k}xf32>",
            rf"bitcast_convert .*tensor<{B}x{k}xf32>\) -> tensor<{B}x{k}xi32>"):
        assert scope_of(pattern).endswith(fold + "bitcast_convert_type")


def test_bucket_counters_read_buckets_and_zero(served):
    """`topk.carried_buckets` counts every resident bucket whose answer is
    the carried scan's last output, `topk.tail_buckets` those that went on
    through the degraded tail's host fold: after a served batch on a
    healthy view the first reads the buckets (one `merge` stage each) and
    the second 0; with a shard that failed to stage it is the other way
    round, and the answers are the same."""
    _, trainer, _, _ = served

    def read(svc):
        return [svc.registry.counter("topk." + name).value
                for name in ("carried_buckets", "tail_buckets")]

    svc = _svc(served, preload=4.0)
    try:
        assert read(svc) == [0, 0]
        res = svc.search_many(
            [trainer.corpus.query_text(i)
             for i in range(svc.query_batch + 3)], k=5)
        buckets = svc.profiler.counts()["merge"]
        assert all(res) and buckets == 2
        assert read(svc) == [buckets, 0]
    finally:
        svc.close()
    faults.install(faults.FaultPlan.parse("hbm_stage:io_error:1", seed=0))
    svc = _svc(served, preload=4.0)
    faults.reset()
    try:
        assert svc.degraded and len(svc._view.stream_entries) == 1
        got = svc.search_many([trainer.corpus.query_text(7)], k=5)
        assert read(svc) == [0, 1]
    finally:
        svc.close()
    assert got == res[7:8]


def test_kernel_bucket_counter_reads_every_float16_bucket(served):
    """`topk.kernel_buckets` counts the buckets whose resident shards
    `exact_scan` scanned, once a bucket: a float16 store is staged as pair
    words, so every bucket of a served batch is one. A k wider than the
    kernel's 128 lanes is answered from the same words by the XLA scan,
    the same pages first, and is not counted."""
    import numpy as np

    _, trainer, _, _ = served
    svc = _svc(served, preload=4.0)
    try:
        assert svc._view.shards[0].pages.dtype == np.uint32
        kernel = svc.registry.counter("topk.kernel_buckets")
        assert kernel.value == 0
        res = svc.search_many(
            [trainer.corpus.query_text(i)
             for i in range(2 * svc.query_batch + 1)], k=5)
        assert all(res)
        assert kernel.value == svc.profiler.counts()["merge"] == 3
        (wide,) = svc.search_many([trainer.corpus.query_text(7)], k=200)
        assert len(wide) == 200 and kernel.value == 3
        assert ([r["page_id"] for r in wide[:5]]
                == [r["page_id"] for r in res[7]])
    finally:
        svc.close()


def test_encode_splits_into_launch_and_wait(served):
    """Inside every `encode` the service opens `encode_launch` (the put
    and the launch) and then `encode_wait` (the pull):
    one of each an encode call, their seconds within encode's. A tower
    that takes 0.2 s to launch puts them in `encode_launch`, not in the
    wait."""
    _, trainer, emb, _ = served
    svc = _svc(served, preload=4.0)
    svc.start_batcher()
    try:
        assert all(_search_through_batcher(svc, trainer, n=12, clients=4))
        sec, n = svc.profiler.stages(), svc.profiler.counts()
        assert n["encode"] > 0
        assert n["encode_launch"] == n["encode_wait"] == n["encode"]
        assert sec["encode_launch"] + sec["encode_wait"] <= sec["encode"]
        svc.profiler.reset()
        launch = emb.encode_query_call

        def slow_launch(ids, params=None):
            import time
            time.sleep(0.2)
            return launch(ids, params)

        emb.encode_query_call = slow_launch
        try:
            assert svc.search(trainer.corpus.query_text(901), k=5)
        finally:
            del emb.encode_query_call        # the class's method again
        sec, n = svc.profiler.stages(), svc.profiler.counts()
        assert n["encode"] == 1
        assert sec["encode_launch"] >= 0.2 and sec["encode_wait"] < 0.1
    finally:
        svc.close()


@pytest.mark.parametrize("slow_ms", [0.0, None])
def test_dispatch_span_carries_gc_ms_and_close_unhooks(served, slow_ms):
    """With the slow-query log on, the batcher's shared `dispatch` span
    says how much of it the collector took (`gc_ms`); with it off the
    dispatch reads nothing for it. The service's collector hook is in
    `gc.callbacks` while the batcher runs, counts forced passes as stage
    `gc`, and close() takes it out again."""
    import gc
    _, trainer, _, _ = served
    before = list(gc.callbacks)
    svc = _svc(served, preload=4.0,
               obs=None if slow_ms is None else {"slow_ms": slow_ms})
    svc.start_batcher()
    try:
        assert len(gc.callbacks) == len(before) + 1
        gc.collect()
        assert svc.profiler.counts()["gc_gen2"] >= 1
        assert svc.search(trainer.corpus.query_text(3), k=5)
    finally:
        svc.close()
    assert gc.callbacks == before
    root = [t for t in svc.tracer.traces() if t["name"] == "search"][-1]

    def find(d, name):
        if d["name"] == name:
            return d
        for c in d["children"]:
            hit = find(c, name)
            if hit is not None:
                return hit
        return None

    attrs = find(root, "dispatch")["attrs"]
    assert attrs["batch_size"] == 1
    if slow_ms is None:
        assert "gc_ms" not in attrs
    else:
        assert attrs["gc_ms"] >= 0.0


def test_batch_telemetry_is_bounded_and_counts_since_boot(served):
    """`serve_batches` / `serve_mean_batch` come from a running count and
    sum, not from the list of sizes, which keeps the newest 4,096 alone:
    emptied, the metrics read the same, before and after close()."""
    _, trainer, _, _ = served
    svc = _svc(served, preload=4.0)
    svc.start_batcher()
    b = svc._batcher
    try:
        res = _search_through_batcher(svc, trainer, n=10, clients=5)
        assert all(res) and b.batch_sizes.maxlen == 4096
        m = svc.metrics()
        assert m["serve_batches"] == b.batches == len(b.batch_sizes) > 0
        assert b.batched == sum(b.batch_sizes) == len(res)
        assert m["serve_mean_batch"] == round(len(res) / b.batches, 2)
        b.batch_sizes.clear()
        assert svc.metrics()["serve_batches"] == m["serve_batches"]
    finally:
        svc.close()
    after = svc.metrics()
    assert after["serve_batches"] == m["serve_batches"]
    assert after["serve_mean_batch"] == m["serve_mean_batch"]
