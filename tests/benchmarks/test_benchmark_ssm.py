"""The state-space serve cell's benchmark files: the configuration file
against the catalog row, the FLOP model against the program's and the
issue's arithmetic, the weights' initialisers, the job rehearsed on the CPU
at toy widths (one correct line; each planted fault in the timed path reads
not correct; the reference in float8 and with each planted fault in the
program's place fails a limit), and the new readers."""
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

from benchmarks import flops_granitemoehybrid as ssm_flops  # noqa: E402
from benchmarks import harness, tiny, tiny_ssm  # noqa: E402

CELL = "granite4_h_small_ep2.serve_page_query"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "num_local_experts", "vocab_size"}


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite4_h_small_ep2.json")) as f:
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
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == cfg["source"]
    # one printable line of at most 200 characters, as the driver asks of
    # every `why` and `source` (a longer one is refused before any run)
    for key in ("why", "source"):
        assert 1 <= len(entry[key]) <= 200 and entry[key].isprintable(), key
    # the floors of a cut: one whole period, 8 experts, an eighth of the
    # vocabulary; and no width is touched
    period = pub["layer_types"].index("attention", 6) - 5
    assert held["layer_types"] == pub["layer_types"][:period] \
        and held["num_hidden_layers"] == period == 10
    assert held["num_local_experts"] >= 8
    assert held["vocab_size"] * 8 >= pub["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert cfg[key]
    assert cfg["compute_dtype"] == cfg["weights_dtype"] == "bfloat16"
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small"][0]
        assert row["config"] == pub and row["source_url"] == cfg["source"]


def test_preset_resolves_to_what_the_file_states():
    from benchmarks.jobs import serve_ssm
    cell = harness.Cell(CELL)
    cfg = serve_ssm.program_config(cell, seed=5)
    assert cfg.mesh.num_devices == 1 and cfg.serve.max_batch == 4
    assert cfg.serve.encode_batch == 1
    assert cfg.model.weights_dtype == "bfloat16" and cfg.model.shared_towers
    arch = serve_ssm.arch_of(cell)
    assert len(arch["layer_types"]) == 10 and arch["num_local_experts"] == 72
    bad = json.loads(json.dumps(cell.config))
    bad["published"]["mamba_d_state"] = 64
    cell.config = bad
    with pytest.raises(SystemExit, match="mamba_d_state"):
        serve_ssm.program_config(cell, seed=5)


def test_flops_equal_the_programs_and_the_issues_arithmetic():
    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.utils import flops as prog
    shape = ssm_flops.shape_of(_config())
    pcfg = get_config("granite4_h_small_ep2")
    mine = ssm_flops.encoder_flops_per_example(shape, 1024)
    assert mine == prog.encoder_flops_per_example(pcfg.model, 1024)
    # the issue: 3.33 GFLOP a token, 3.4 TFLOP a query on this chip's share
    assert mine / 1024 / 1e9 == pytest.approx(3.33, rel=2e-2)
    assert ssm_flops.serve_flops_per_query(shape, 1 << 20) == \
        mine + 2.0 * (1 << 20) * 1024
    # the scan, one layer, one query of four chunks: visible pairs once
    pairs = 4 * 256 * 257 / 2
    assert ssm_flops.scan_flops_per_query(shape, 1024) == \
        pairs * (2 * 128 + 2 * 8192) + 2 * 3 * 2 * 256 * 8192 * 128
    assert ssm_flops.scan_bytes_per_query(shape, 1024) == \
        1024 * (2 * 8192 + 4 * 128 + 4 * 128 + 4 * 8192)
    assert ssm_flops.expert_flops_per_assignment(shape) == 6 * 4096 * 768
    assert ssm_flops.expert_kernel_bytes_per_call(shape) == \
        pytest.approx(680e6, rel=1e-2)
    assert ssm_flops.mamba_layers(shape) == 9


def test_weights_follow_the_familys_initialisers_and_the_stated_dtypes():
    import jax
    import jax.numpy as jnp
    from benchmarks import weights, weights_ssm
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    tree = {"params": {"log_scale": s(), "t": {"block0": {
        "mixer": {"A_log": s(512), "dt_bias": s(512), "D": s(512),
                  "conv_kernel": s(4, 2048), "conv_bias": s(2048),
                  "in_proj": {"kernel": s(256, 64)},
                  "out_proj": {"kernel": s(64, 256)}},
        "moe": {"w_gate": s(8, 256, 64), "w_down": s(8, 64, 256),
                "router": {"kernel": s(256, 64)}},
        "ln": {"scale": s(256)}}, "proj": {"kernel": s(256, 64)},
        "tok_embed": {"embedding": s(50, 256)}}}}
    cfg = _config()
    p = weights_ssm.make_params(
        tree, 2**31 + 7, 20.0, cfg["weights_dtype"],
        cfg["assumed"]["float32_leaves"])["params"]["t"]
    f32 = lambda x: x.astype(jnp.float32)
    std = lambda x: float(jnp.std(f32(x)))
    mixer, moe = p["block0"]["mixer"], p["block0"]["moe"]
    assert std(moe["w_gate"]) == pytest.approx(256 ** -0.5, rel=0.05)
    assert std(moe["w_down"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(mixer["conv_kernel"]) == pytest.approx(0.5, rel=0.05)
    decay = jnp.exp(mixer["A_log"])
    assert 1.0 <= float(decay.min()) < 2.0 and 15.0 < float(decay.max()) <= 16
    step = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 <= float(step.min()) < 2e-3 and 5e-2 < float(step.max()) <= 0.1
    assert bool((mixer["D"] == 1.0).all())
    # held in bfloat16: every matrix but the router's and proj's kernels
    for leaf in (moe["w_gate"], mixer["in_proj"]["kernel"],
                 mixer["out_proj"]["kernel"], mixer["conv_kernel"],
                 p["tok_embed"]["embedding"]):
        assert leaf.dtype == jnp.bfloat16
    for leaf in (moe["router"]["kernel"], p["proj"]["kernel"],
                 mixer["A_log"], mixer["conv_bias"], p["block0"]["ln"]["scale"]):
        assert leaf.dtype == jnp.float32
    # a leaf both make is the same draw, rounded where it is held so
    plain = weights.make_params(tree, 2**31 + 7)["params"]["t"]
    assert bool((moe["router"]["kernel"]
                 == plain["block0"]["moe"]["router"]["kernel"]).all())
    assert bool((p["tok_embed"]["embedding"] == plain["tok_embed"][
        "embedding"].astype(jnp.bfloat16)).all())


# -- the job, rehearsed ---------------------------------------------------------

def _run(tmp_path, **kw):
    from benchmarks.jobs import serve_ssm
    root = tiny_ssm.make_root(str(tmp_path / "root"), CELL, **kw)
    cell = harness.Cell(CELL, root)
    return cell, serve_ssm.run(cell, tiny.SEED, 1.5, False,
                               time.perf_counter(), require_chip=False)


def test_ssm_rehearsal_is_correct_and_prints_its_line(tmp_path):
    cell, out = _run(tmp_path)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 30 and out["failed"] == 0
    line = tiny.check_line(cell, out, "serve_p95_ms")
    assert {"rank_gap", "score_gap", "vector_gap", "routing_gap",
            "dropped_assignments", "short_answers", "recompiles",
            "built_in_window"} <= set(line["compared"])
    assert line["compared"]["dropped_assignments"]["limit"] == 0.0
    ctx = out["ctx"]
    assert ctx["job"] == "serve" and ctx["cache_hits"] == 0
    c = ctx["encode_counters"]
    assert c["tokens"] == 30 * 24 and c["moe_dropped"] == 0
    assert c["moe_assignments_held"] <= c["moe_tiles_used"] * 256
    for metric in ("queue_wait_p95_ms.serve", "batch_occupancy.serve",
                   "encode_ms.serve", "topk_ms.serve",
                   "gen_late_p95_ms.serve", "expert_tile_fill.serve"):
        assert harness.read_metric(metric, ctx) is not None
    # no trace: the device metrics are left out, never reported as 0
    for metric in ("ssd_scan_roofline", "expert_matmul_roofline.serve",
                   "mamba_share.serve", "moe_share.serve",
                   "sharded_topk_roofline"):
        assert harness.read_metric(metric, ctx) is None


def test_ssm_rehearsal_with_weights_held_in_bfloat16(tmp_path):
    """The cell's precision at toy widths: the service holds the tree as it
    was given (the job checks), and the gaps are bfloat16's."""
    loose = {"rank_gap": 0.05, "score_gap": 0.05, "vector_gap": 0.2,
             "routing_gap": 0.05}
    _, out = _run(tmp_path, weights_dtype="bfloat16", limits=loose)
    assert out["correct"], out["compared"]
    assert out["compared"]["vector_gap"]["value"] > 1e-4


def _no_carry(monkeypatch):
    """The state not carried across chunk boundaries."""
    from dnn_page_vectors_tpu.models import granite_hybrid
    monkeypatch.setattr(granite_hybrid, "ssd_scan", functools.partial(
        granite_hybrid.ssd_scan, carry_state=False))


def _other_weights(monkeypatch):
    """The routed sum under weights of another normalisation (as a softmax
    over all the logits gives the selected: less than 1 in all)."""
    from dnn_page_vectors_tpu.ops import grouped_matmul as gm
    real = gm.unpermute
    monkeypatch.setattr(gm, "unpermute", lambda rows, weight, plan: real(
        rows, weight * 0.6, plan))


def _no_residual_multiplier(monkeypatch):
    from dnn_page_vectors_tpu.models import factory
    real = factory.GraniteSizes
    monkeypatch.setattr(factory, "GraniteSizes", lambda **kw: real(
        **dict(kw, residual_multiplier=1.0)))


@pytest.mark.parametrize("fault", [_no_carry, _other_weights,
                                   _no_residual_multiplier])
def test_ssm_fault_in_the_timed_path_reads_not_correct(tmp_path, monkeypatch,
                                                       fault):
    fault(monkeypatch)
    _, out = _run(tmp_path)
    assert out["correct"] is False
    assert not out["compared"]["vector_gap"]["ok"], out["compared"]


def test_ssm_control_and_planted_faults_each_fail_a_limit(tmp_path):
    """The reference in the program's place: in float8, without the carried
    state, with a softmax over all the logits, without the multiplier."""
    from benchmarks import compare
    from benchmarks.jobs import serve_ssm
    root = tiny_ssm.make_root(str(tmp_path / "root"), CELL)
    harness.setup_jax()
    cell = harness.Cell(CELL, root)
    readings = serve_ssm.controls(cell, tiny.SEED)
    assert set(readings) == {"control_fp8", "fault_no_carry",
                             "fault_softmax_all",
                             "fault_no_residual_multiplier"}
    for kind, numbers in readings.items():
        judged = compare.judge(numbers, serve_ssm.limits_of(cell))
        assert not all(c["ok"] for c in judged.values()), (kind, judged)


# -- the new readers -------------------------------------------------------------

def test_readers_of_the_scope_seconds_and_the_counters():
    ctx = {"job": "serve", "device_kind": "TPU v5 lite",
           "scope_seconds": {"scopes": {"mamba.ssd": 0.2, "moe.experts": 0.5,
                                        "mamba": 1.0, "moe": 2.0},
                             "encode_module_seconds": 4.0,
                             "encode_launches": 20.0},
           "encode_counters": {"tokens": 40960, "moe_assignments_held": 8e5,
                               "moe_tiles_used": 7200, "moe_dropped": 0},
           "query_tokens": 1024, "mamba_layers": 9, "expert_layers": 10,
           "expert_tile_rows": 256, "ssd_flops_per_query": 5.4e9,
           "ssd_bytes_per_query": 51.4e6,
           "expert_flops_per_assignment": 6 * 4096 * 768,
           "expert_kernel_bytes_per_call": 680e6}
    read = lambda name: harness.read_metric(name, ctx)
    assert read("mamba_share.serve") == 25.0
    assert read("moe_share.serve") == 50.0
    assert read("expert_tile_fill.serve") == pytest.approx(43.4, rel=1e-2)
    # the scan: bytes bound it (51.4 MB / 819 GB/s = 62.8 us > 27.4 us)
    assert read("ssd_scan_roofline") == pytest.approx(
        100 * 62.76e-6 * 9 * 40 / 0.2, rel=1e-2)
    # the experts at one or two queries a call: the kernels' bytes bound it
    assert read("expert_matmul_roofline.serve") == pytest.approx(
        100 * (680e6 / 819e9) * 20 * 10 / 0.5, rel=1e-2)
    for name in ("ssd_scan_roofline", "expert_matmul_roofline.serve",
                 "mamba_share.serve", "moe_share.serve",
                 "expert_tile_fill.serve"):
        assert harness.read_metric(name, {"job": "train"}) is None
