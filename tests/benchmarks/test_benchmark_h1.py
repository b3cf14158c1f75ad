"""The Falcon-H1 serve cell's benchmark files: the configuration file against
the catalog row, the FLOP model against the program's and the issue's
arithmetic, the weights' gains, the job rehearsed on the CPU at toy widths
(one correct line; each planted fault in the timed path reads not correct;
the reference in float8 and with each planted fault in the program's place
fails a limit), and the readers."""
import functools
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_h1, harness, tiny, tiny_h1  # noqa: E402

CELL = "falcon_h1_34b_pp12.serve_page_query"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers"}
EXTENDED = ["queue_wait_p95_ms.serve", "batch_occupancy.serve",
            "encode_ms.serve", "topk_ms.serve", "sharded_topk_roofline",
            "step_mfu.serve", "device_idle_share.serve",
            "gen_late_p95_ms.serve", "dispatcher_busy_share.serve",
            "dispatch_self_ms.serve", "idle_with_work_share.serve",
            "ssd_scan_roofline", "mamba_share.serve"]
NEW = ["attn_share.serve", "mlp_share.serve",
       "flash_attention_roofline.serve", "mlp_matmul_mfu.serve"]


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon_h1_34b_pp12.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def _caches_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "JAX_CACHE", str(tmp_path / "jax"))


def test_config_file_holds_the_published_keys_and_states_the_cut():
    cfg = _config()
    pub, held = cfg["published"], cfg["held"]
    for key, value in pub.items():
        assert cfg[key] == (held[key] if key in REDUCED else value), key
    assert {k for k in pub if cfg[k] != pub[k]} == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = [c for c in manifest["configs"] if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == cfg["source"]
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    for e in (entry, cell):     # one printable line of at most 200 characters
        for key in ("why", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and e[key].isprintable(), key
    assert cell["chips"] == 1 and cell["config"] == cfg["name"]
    # the floors of a cut: at least four whole layers, every row, no width
    assert held["num_hidden_layers"] == 6 and 72 % 6 == 0
    assert held["vocab_size"] == pub["vocab_size"] == 261_120
    for key in ("deployment", "assumed", "departures"):
        assert cfg[key]
    assert cfg["compute_dtype"] == cfg["weights_dtype"] == "bfloat16"
    assert set(cfg["assumed"]["gains"]) == {
        "embedding", "wq", "wk", "wv", "wo", "in_proj", "out_proj", "wi_0",
        "wi_1", "wo_mlp"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct"][0]
        assert row["config"] == pub and row["source_url"] == cfg["source"]


def test_manifest_lists_the_cell_where_the_issue_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in EXTENDED:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "serve_p95_ms"
    assert [m["name"] for m in manifest["per_layer"]][-4:] == NEW
    for name, m in by_name.items():
        if name.startswith(("moe_", "expert_")):
            assert CELL not in m["workloads"], name
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == "falcon_h1_34b_pp12"


def test_preset_resolves_to_what_the_file_states():
    from benchmarks.jobs import serve_h1
    cell = harness.Cell(CELL)
    cfg = serve_h1.program_config(cell, seed=5)
    assert cfg.mesh.num_devices == 1 and cfg.serve.max_batch == 4
    assert cfg.serve.encode_batch == 1 and cfg.serve.query_cache_size == 0
    assert cfg.model.weights_dtype == "bfloat16" and cfg.model.shared_towers
    assert cfg.data.query_len == cfg.data.page_len == 1024
    arch = serve_h1.arch_of(cell)
    assert arch["num_hidden_layers"] == 6 and arch["mamba_n_groups"] == 2
    # a published key that the preset does not carry is named in the exit
    for key, value, named in (
            ("mamba_n_groups", 1, "mamba_n_groups"),
            ("mamba_d_ssm", 10240, "mamba_d_ssm"),
            ("key_multiplier", 1.0, "key_multiplier"),
            ("ssm_multipliers", [1, 1, 1, 1, 1], "ssm_multipliers"),
            ("mamba_norm_before_gate", True, "built")):
        bad = json.loads(json.dumps(cell.config))
        bad["published"][key] = value
        other = harness.Cell(CELL)
        other.config = bad
        with pytest.raises(SystemExit, match=named):
            serve_h1.program_config(other, seed=5)


def test_flops_equal_the_programs_and_the_issues_arithmetic():
    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.utils import flops as prog
    shape = flops_h1.shape_of(_config())
    pcfg = get_config("falcon_h1_34b_pp12")
    mine = flops_h1.encoder_flops_per_example(shape, 1024)
    assert mine == prog.encoder_flops_per_example(pcfg.model, 1024)
    # the issue: 430.1M parameters a layer, 3,922.8M held; 860.2 MFLOP of
    # projections a token and layer; 5.34 TFLOP a query on this chip
    assert flops_h1.parameters_per_layer(shape) / 1e6 == pytest.approx(
        430.1, abs=0.05)
    assert flops_h1.parameters_held(shape) / 1e6 == pytest.approx(
        3922.8, abs=0.5)
    assert flops_h1.projection_flops_per_token(shape) / 1e6 == pytest.approx(
        860.2, abs=0.1)
    assert mine / 1e12 == pytest.approx(5.34, abs=0.005)
    assert flops_h1.serve_flops_per_query(shape, 1 << 20) == \
        mine + 2.0 * (1 << 20) * 1024
    # the scan, one layer, one query of eight chunks: visible pairs once, a
    # score tile a group
    pairs = 8 * 128 * 129 / 2
    assert flops_h1.scan_flops_per_query(shape, 1024) == \
        pairs * (2 * 256 * 2 + 2 * 4096) + 2 * 7 * 2 * 128 * 4096 * 256
    assert flops_h1.scan_flops_per_query(shape, 1024) / 1e9 == \
        pytest.approx(4.4, abs=0.05)
    assert flops_h1.scan_bytes_per_query(shape, 1024) == \
        1024 * (2 * 4096 + 2 * 2 * 2 * 256 + 4 * 32 + 4 * 4096)
    assert flops_h1.flash_flops_per_layer(shape, 1024) == \
        4 * 128 * 20 * 1024 * 1025 / 2
    assert flops_h1.flash_flops_per_layer(shape, 1024) / 1e9 == \
        pytest.approx(5.4, abs=0.05)
    assert flops_h1.flash_bytes_per_layer(shape, 1024) == \
        2 * 1024 * 128 * (2 * 20 + 2 * 4)
    assert flops_h1.mlp_flops_per_token(shape) == 6 * 5120 * 21504


def test_weights_take_the_configurations_gains_and_dtypes():
    import jax
    import jax.numpy as jnp
    from benchmarks import weights_h1, weights_ssm
    from benchmarks.reference import falcon_h1 as ref
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    tree = {"params": {"log_scale": s(), "t": {"block0": {
        "mixer": {"A_log": s(512), "dt_bias": s(512), "D": s(512),
                  "conv_kernel": s(4, 2048), "conv_bias": s(2048),
                  "in_proj": {"kernel": s(256, 9248)},
                  "out_proj": {"kernel": s(64, 256)}},
        "attn": {"wk": {"kernel": s(256, 512)}},
        "ln": {"scale": s(256)}}, "proj": {"kernel": s(256, 64)},
        "tok_embed": {"embedding": s(300, 256)}}}}
    cfg = _config()
    gains = cfg["assumed"]["gains"]
    widths = ref.segment_widths(cfg["published"])
    assert widths == (4096, 4096, 512, 512, 32)
    make = lambda dtype: weights_h1.make_params(
        tree, 2**31 + 7, 20.0, dtype, cfg["assumed"]["float32_leaves"],
        gains, {"in_proj": widths})["params"]["t"]
    p = make(cfg["weights_dtype"])
    std = lambda x: float(jnp.std(x.astype(jnp.float32)))
    mixer = p["block0"]["mixer"]
    w_in = mixer["in_proj"]["kernel"]
    edges = [0, 4096, 8192, 8704, 9216, 9248]
    for gain, lo, hi in zip(gains["in_proj"], edges, edges[1:]):
        assert std(w_in[:, lo:hi]) == pytest.approx(gain / 16, rel=0.05)
    assert std(mixer["out_proj"]["kernel"]) == pytest.approx(
        gains["out_proj"] / 8, rel=0.05)
    assert std(p["block0"]["attn"]["wk"]["kernel"]) == pytest.approx(
        gains["wk"] / 16, rel=0.05)
    assert std(p["tok_embed"]["embedding"]) == pytest.approx(
        gains["embedding"] / 16, rel=0.05)
    assert std(p["proj"]["kernel"]) == pytest.approx(1 / 16, rel=0.05)
    # every other leaf as weights_ssm.py draws it
    other = weights_ssm.make_params(
        tree, 2**31 + 7, 20.0, cfg["weights_dtype"],
        cfg["assumed"]["float32_leaves"])["params"]["t"]["block0"]["mixer"]
    for leaf in ("A_log", "dt_bias", "D", "conv_kernel", "conv_bias"):
        assert bool((mixer[leaf] == other[leaf]).all()), leaf
    # held in bfloat16: every matrix but proj's kernel
    for leaf in (w_in, mixer["out_proj"]["kernel"], mixer["conv_kernel"],
                 p["tok_embed"]["embedding"]):
        assert leaf.dtype == jnp.bfloat16
    for leaf in (p["proj"]["kernel"], mixer["A_log"], mixer["conv_bias"],
                 p["block0"]["ln"]["scale"]):
        assert leaf.dtype == jnp.float32
    # a leaf drawn in blocks of rows is the same draw at either precision
    plain = make("float32")
    assert bool((p["tok_embed"]["embedding"] == plain["tok_embed"][
        "embedding"].astype(jnp.bfloat16)).all())
    assert weights_h1._row_block(261_120, 5120) == 13_056
    assert weights_h1._row_block(300, 256) == 300


# -- the job, rehearsed -------------------------------------------------------

def _run(tmp_path, **kw):
    from benchmarks.jobs import serve_h1
    root = tiny_h1.make_root(str(tmp_path / "root"), CELL, **kw)
    cell = harness.Cell(CELL, root)
    return cell, serve_h1.run(cell, tiny.SEED, 1.5, False,
                              time.perf_counter(), require_chip=False)


def test_h1_rehearsal_is_correct_and_prints_its_line(tmp_path, capfd):
    cell, out = _run(tmp_path)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 30 and out["failed"] == 0
    line = tiny.check_line(cell, out, "serve_p95_ms")
    assert set(line["compared"]) == {"rank_gap", "score_gap", "vector_gap",
                                     "short_answers", "recompiles",
                                     "built_in_window"}
    for name in ("short_answers", "recompiles", "built_in_window"):
        assert line["compared"][name]["limit"] == 0.0
    ctx = out["ctx"]
    assert ctx["job"] == "serve" and ctx["cache_hits"] == 0
    assert ctx["encode_counters"] == {
        "tokens": 30 * 24, "moe_assignments_held": 0, "moe_tiles_used": 0,
        "moe_dropped": 0}
    assert ctx["mamba_layers"] == 3 and ctx["query_tokens"] == 24
    for metric in ("queue_wait_p95_ms.serve", "batch_occupancy.serve",
                   "encode_ms.serve", "topk_ms.serve", "step_mfu.serve",
                   "gen_late_p95_ms.serve", "dispatcher_busy_share.serve",
                   "dispatch_self_ms.serve"):
        assert harness.read_metric(metric, dict(
            ctx, device_kind="TPU v5 lite")) is not None, metric
    # no trace: the device metrics are left out, never reported as 0
    for metric in ("ssd_scan_roofline", "mamba_share.serve",
                   "sharded_topk_roofline", "device_idle_share.serve",
                   "idle_with_work_share.serve", *NEW):
        assert harness.read_metric(metric, ctx) is None, metric
    err = capfd.readouterr().err
    ratios = json.loads(err.split("branch ratios at layer 0: ")[1]
                        .splitlines()[0])
    assert set(ratios) == {"score_std", "mamba_over_residual",
                           "attn_over_residual", "mlp_over_residual"}
    assert all(v > 0 for v in ratios.values())
    assert "served: " in err


def test_h1_rehearsal_with_weights_held_in_bfloat16(tmp_path):
    """The cell's precision at toy widths: the service holds the tree as it
    was given (the job checks), and the gaps are bfloat16's."""
    loose = {"rank_gap": 0.05, "score_gap": 0.05, "vector_gap": 0.2}
    _, out = _run(tmp_path, weights_dtype="bfloat16", limits=loose)
    assert out["correct"], out["compared"]
    assert out["compared"]["vector_gap"]["value"] > 1e-4


def _sizes(monkeypatch, **changed):
    """The tower built with other sizes than the configuration's."""
    from dnn_page_vectors_tpu.models import factory
    real = factory.FalconH1Sizes
    monkeypatch.setattr(factory, "FalconH1Sizes",
                        lambda **kw: real(**dict(kw, **changed)))


def _no_carry(monkeypatch):
    from dnn_page_vectors_tpu.models import granite_hybrid
    monkeypatch.setattr(granite_hybrid, "ssd_scan", functools.partial(
        granite_hybrid.ssd_scan, carry_state=False))


def _one_group(monkeypatch):
    """Both groups read group 0's B and C."""
    import jax.numpy as jnp
    from dnn_page_vectors_tpu.models import granite_hybrid
    real = granite_hybrid.ssd_scan
    first = lambda t: jnp.broadcast_to(t[:, :, :1], t.shape)
    monkeypatch.setattr(
        granite_hybrid, "ssd_scan",
        lambda x, d, a, b, c, chunk: real(x, d, a, first(b), first(c), chunk))


def _no_rotary(monkeypatch):
    _sizes(monkeypatch, rope_theta=0.0)


def _no_key_multiplier(monkeypatch):
    _sizes(monkeypatch, key_multiplier=1.0)


def _ungrouped_norm(monkeypatch):
    from dnn_page_vectors_tpu.models import granite_hybrid
    real = granite_hybrid.RmsNorm
    monkeypatch.setattr(granite_hybrid, "RmsNorm",
                        lambda **kw: real(**dict(kw, groups=1)))


def _no_mup(monkeypatch):
    _sizes(monkeypatch, ssm_multipliers=(1.0,) * 5)


@pytest.mark.parametrize("fault", [
    _no_carry, _one_group, _no_rotary, _no_key_multiplier, _ungrouped_norm,
    _no_mup])
def test_h1_fault_in_the_timed_path_reads_not_correct(tmp_path, monkeypatch,
                                                      fault):
    fault(monkeypatch)
    _, out = _run(tmp_path)
    assert out["correct"] is False
    assert not out["compared"]["vector_gap"]["ok"], out["compared"]


def test_h1_control_and_planted_faults_each_fail_a_limit(tmp_path):
    """The reference in the program's place: in float8, and with each of the
    six planted faults."""
    from benchmarks import compare
    from benchmarks.jobs import serve_h1
    root = tiny_h1.make_root(str(tmp_path / "root"), CELL)
    harness.setup_jax()
    cell = harness.Cell(CELL, root)
    readings = serve_h1.controls(cell, tiny.SEED)
    assert set(readings) == {
        "control_fp8", "fault_no_carry", "fault_one_group", "fault_no_rotary",
        "fault_no_key_multiplier", "fault_ungrouped_norm", "fault_no_mup"}
    for kind, numbers in readings.items():
        judged = compare.judge(numbers, serve_h1.limits_of(cell))
        assert not all(c["ok"] for c in judged.values()), (kind, judged)


# -- the readers --------------------------------------------------------------

def test_readers_of_the_scope_seconds_and_the_token_counter():
    ctx = {"job": "serve", "device_kind": "TPU v5 lite",
           "scope_seconds": {"scopes": {"mamba": 1.0, "mamba.ssd": 0.2,
                                        "attn": 0.4, "attn.flash": 0.1,
                                        "mlp": 2.0},
                             "encode_module_seconds": 4.0,
                             "encode_launches": 40.0},
           "encode_counters": {"tokens": 40960, "moe_assignments_held": 0,
                               "moe_tiles_used": 0, "moe_dropped": 0},
           "query_tokens": 1024, "mamba_layers": 6, "attn_layers": 6,
           "mlp_layers": 6, "ssd_flops_per_query": 4.37e9,
           "ssd_bytes_per_query": 29.5e6, "flash_flops_per_layer": 5.37e9,
           "flash_bytes_per_layer": 12.6e6,
           "mlp_flops_per_token": 6 * 5120 * 21504}
    read = lambda name: harness.read_metric(name, ctx)
    assert read("mamba_share.serve") == 25.0
    assert read("attn_share.serve") == 10.0
    assert read("mlp_share.serve") == 50.0
    # the scan: bytes bound it (29.5 MB / 819 GB/s = 36.0 us > 22.2 us)
    assert read("ssd_scan_roofline") == pytest.approx(
        100 * (29.5e6 / 819e9) * 6 * 40 / 0.2, rel=1e-6)
    # the flash forward: the MXU bounds it (27.3 us > 15.4 us of bytes)
    assert read("flash_attention_roofline.serve") == pytest.approx(
        100 * (5.37e9 / 197e12) * 6 * 40 / 0.1, rel=1e-6)
    assert read("mlp_matmul_mfu.serve") == pytest.approx(
        100 * 6 * 5120 * 21504 * 40960 * 6 / 197e12 / 2.0, rel=1e-6)
    for name in NEW + ["ssd_scan_roofline", "mamba_share.serve"]:
        assert harness.read_metric(name, {"job": "train"}) is None
        assert harness.read_metric(name, {}) is None
    # another tower's serve ctx (no flash or SwiGLU counts) reads nothing
    bare = {k: v for k, v in ctx.items() if not k.startswith(("flash_",
                                                              "mlp_"))}
    assert harness.read_metric("flash_attention_roofline.serve",
                               bare) is None
    assert harness.read_metric("mlp_matmul_mfu.serve", bare) is None


def test_scope_sums_take_the_module_that_launches_the_flash_kernel(
        monkeypatch):
    """A synthetic trace: two launches of the encode (a projection under
    `attn.qkv`, the kernel under `attn.flash`, a product under `mlp.down`)
    and one of the scan program, which launches no flash kernel and is left
    out; an op the compiled text does not name is in no scope."""
    from benchmarks import trace_reduce
    from benchmarks.jobs import serve_h1
    ms = 1e6
    encode = lambda t0: [
        ("%fusion.1 = bf16[1024,2560]{1,0} fusion(...)", t0, 1 * ms),
        ("%flash_fwd.3 = bf16[1,20,1024,128]{3,2,1,0} custom-call(...)",
         t0 + 1 * ms, 2 * ms),
        ("%fusion.9 = bf16[1024,5120]{1,0} fusion(...)", t0 + 3 * ms, 4 * ms),
        ("%copy.77 = bf16[8]{0} copy(...)", t0 + 7 * ms, 1 * ms)]
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit__encode_counted(1)", 0.0, 8 * ms),
                            ("jit__lambda(2)", 9 * ms, 1 * ms),
                            ("jit__encode_counted(1)", 10 * ms, 8 * ms)],
            "XLA Ops": encode(0.0) + [("%fusion.1 = f32[8,20]{1,0} fusion()",
                                       9 * ms, 1 * ms)] + encode(10 * ms)},
        "/host:CPU": {"main": [("bench_window", 0.0, 20 * ms)]}}
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "load", lambda path: planes)
    meta = 'metadata={op_name="jit(_encode_counted)/query_tower/block0/%s"}'
    text = "\n".join([
        "  %fusion.1 = bf16[1024,2560]{1,0} fusion(%p), "
        + meta % "attn/attn/attn.qkv/wq/dot_general",
        "  %flash_fwd.3 = bf16[1,20,1024,128]{3,2,1,0} custom-call(%q), "
        + meta % "attn/attn/attn.flash/pallas_call",
        "  ROOT %fusion.9 = bf16[1024,5120]{1,0} fusion(%h), "
        + meta % "mlp/mlp/mlp.down/wo_mlp/dot_general"])
    got = serve_h1._scope_seconds("unused", text)
    s = got["scopes"]
    assert s["attn"] == pytest.approx(6e-3) and s["attn.qkv"] == \
        pytest.approx(2e-3)
    assert s["attn.flash"] == pytest.approx(4e-3)
    assert s["mlp"] == s["mlp.down"] == pytest.approx(8e-3)
    assert s["mamba"] == s["mlp.gate_up"] == 0.0
    assert got["kernels"]["flash_fwd"] == pytest.approx(4e-3)
    assert got["encode_module_seconds"] == pytest.approx(16e-3)
    assert got["encode_launches"] == 2
