"""The routed-expert cell's benchmark files: the configuration file against
the catalog row, the FLOP model against the program's, the job rehearsed on
the CPU at toy widths (one correct line; the timed path broken underneath
reads not correct; the reference in float8 and with each planted fault in
the program's place fails a limit), and the scope reducer with its readers."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_glm4_moe_lite as moe_flops  # noqa: E402
from benchmarks import harness, tiny, tiny_moe, trace_scopes  # noqa: E402

CELL = "glm47_flash_ep8.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm47_flash_ep8.json")) as f:
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
    # the floors of a cut: four layers after the dense one, 8 experts, an
    # eighth of the vocabulary; and no width is touched
    assert held["num_hidden_layers"] >= pub["first_k_dense_replace"] + 4
    assert held["n_routed_experts"] >= 8
    assert held["vocab_size"] * 8 >= pub["vocab_size"]
    for key in ("deployment", "assumed", "departures", "optimizer"):
        assert cfg[key]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash"][0]
        assert row["config"] == pub and row["source_url"] == cfg["source"]


def test_preset_resolves_to_what_the_file_states():
    from benchmarks.jobs import train_moe
    cell = harness.Cell(CELL)
    cfg = train_moe.program_config(cell, seed=5)
    assert cfg.mesh.num_devices == 1 and cfg.train.batch_size == 32
    assert cfg.model.remat_blocks and cfg.model.shared_towers
    arch = train_moe.arch_of(cell)
    assert arch["num_hidden_layers"] == 5 and arch["n_routed_experts"] == 64
    bad = json.loads(json.dumps(cell.config))
    bad["published"]["q_lora_rank"] = 512
    cell.config = bad
    with pytest.raises(SystemExit, match="q_lora_rank"):
        train_moe.program_config(cell, seed=5)


def test_flops_equal_the_programs_and_the_issues_arithmetic():
    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.utils import flops as prog
    shape = moe_flops.shape_of(_config())
    pcfg = get_config("glm47_flash_ep8")
    mine = moe_flops.train_flops_per_pair(shape, 32)
    assert mine == pytest.approx(prog.train_flops_per_pair(pcfg, 32),
                                 rel=1e-12)
    assert 32 * mine / 1e12 == pytest.approx(51.57, rel=1e-3)
    per_token = moe_flops.encoder_flops_per_example(shape, 1024) / 1024
    assert per_token / 1e9 == pytest.approx(0.51, rel=2e-2)
    assert moe_flops.held_assignments_per_token(shape) == 0.5
    # per step: 33,792 tokens x 4 layers x 0.5 assignments x 3 products
    assert moe_flops.expert_matmul_flops_per_step(shape, 32) == \
        3 * 33792 * 4 * 0.5 * 6 * 2048 * 1536
    pairs = 1024 * 1025 / 2 + 32 * 33 / 2
    assert moe_flops.flash_flops_per_step(shape, 32) == \
        3.5 * 32 * 5 * 2 * pairs * 20 * 512


def test_stacked_kernels_are_scaled_by_their_fan_in():
    import jax
    import jax.numpy as jnp
    from benchmarks import weights, weights_moe
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    tree = {"params": {"log_scale": s(), "t": {
        "moe": {"w_gate": s(8, 256, 64), "w_down": s(8, 64, 256),
                "select_bias": s(64), "router": {"kernel": s(256, 64)}},
        "ln": {"scale": s(256)}, "tok_embed": {"embedding": s(50, 256)}}}}
    p = weights_moe.make_params(tree, 2**31 + 7)["params"]["t"]
    std = lambda x: float(jnp.std(x))
    assert std(p["moe"]["w_gate"]) == pytest.approx(256 ** -0.5, rel=0.05)
    assert std(p["moe"]["w_down"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(p["moe"]["router"]["kernel"]) == pytest.approx(256 ** -0.5,
                                                              rel=0.05)
    assert std(p["moe"]["select_bias"]) == pytest.approx(0.02, rel=0.3)
    # every other leaf is what weights.py makes of it
    plain = weights.make_params(tree, 2**31 + 7)["params"]["t"]
    for path in (("ln", "scale"), ("tok_embed", "embedding"),
                 ("moe", "router", "kernel"), ("moe", "select_bias")):
        a, b = p, plain
        for k in path:
            a, b = a[k], b[k]
        assert bool((a == b).all()), path


# -- the job, rehearsed ---------------------------------------------------------

def _run(tmp_path):
    from benchmarks.jobs import train_moe
    root = tiny_moe.make_root(str(tmp_path / "root"), CELL)
    cell = harness.Cell(CELL, root)
    return cell, train_moe.run(cell, tiny.SEED, 1.0, False,
                               time.perf_counter(), require_chip=False)


def test_moe_rehearsal_is_correct_and_prints_its_line(tmp_path):
    cell, out = _run(tmp_path)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    line = tiny.check_line(cell, out, "train_pages_per_s")
    assert {"loss1", "loss2", "loss3", "grad_norm", "change_norm",
            "routing_gap", "dropped_assignments", "built_in_window"} <= \
        set(line["compared"])
    assert line["compared"]["dropped_assignments"]["limit"] == 0.0
    ctx = out["ctx"]
    assert ctx["job"] == "train"
    for metric in ("input_wait_share.train", "step_ms.train"):
        assert harness.read_metric(metric, ctx) is not None
    ratio = harness.read_metric("expert_load_max_over_mean.train", ctx)
    assert 1.0 <= ratio <= 4.0          # 4 held experts: at most all on one
    # no trace: the device metrics are left out, never reported as 0
    for metric in ("expert_matmul_roofline", "flash_attention_roofline",
                   "moe_share.train", "mla_share.train",
                   "step_device_ms.train"):
        assert harness.read_metric(metric, ctx) is None


def _no_scaling(monkeypatch):
    """The routed sum without routed_scaling_factor."""
    from dnn_page_vectors_tpu.ops import grouped_matmul as gm
    real = gm.unpermute
    monkeypatch.setattr(gm, "unpermute", lambda rows, weight, plan: real(
        rows, weight / tiny_moe.SCALING, plan))


def _bidirectional(monkeypatch):
    """Bidirectional attention in place of causal."""
    from dnn_page_vectors_tpu.ops import flash_attention as fa
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: real(
        *a, **dict(kw, causal=False)))


@pytest.mark.parametrize("fault", [_no_scaling, _bidirectional])
def test_moe_fault_in_the_timed_path_reads_not_correct(tmp_path, monkeypatch,
                                                       fault):
    fault(monkeypatch)
    _, out = _run(tmp_path)
    assert out["correct"] is False
    assert {k for k, c in out["compared"].items() if not c["ok"]}, \
        out["compared"]


def test_moe_control_and_planted_faults_each_fail_a_limit(tmp_path):
    """The reference in the program's place: in float8, with half the batch,
    without the scaling factor, and bidirectional."""
    from benchmarks import compare
    from benchmarks.jobs import train_moe
    root = tiny_moe.make_root(str(tmp_path / "root"), CELL)
    readings = train_moe.controls(harness.Cell(CELL, root), tiny.SEED)
    assert set(readings) == {"control_fp8", "fault_half_batch",
                             "fault_no_scaling", "fault_bidirectional"}
    for kind, numbers in readings.items():
        judged = compare.judge(numbers, tiny_moe.LIMITS)
        assert not all(c["ok"] for c in judged.values()), (kind, judged)


# -- the scope reducer and its readers -------------------------------------------

HLO = """
HloModule jit_train_step
  %fusion.1 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(train_step)/jvp(TwoTower)/query_tower/layers/block1_mix/mla/attn/wq_a/dot_general" source_file="x.py"}
  %flash_fwd.2 = bf16[8,8]{1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(TwoTower)/query_tower/layers/block1_mix/mla/attn/mla.flash/flash_fwd/pallas_call" source_file="x.py"}
  %fusion.3 = bf16[8,8]{1,0} fusion(%flash_fwd.2), kind=kLoop, calls=%g, metadata={op_name="jit(train_step)/transpose(jvp(TwoTower))/query_tower/layers/block1_ffn/moe/moe/moe.experts/mul"}
  ROOT %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%h, metadata={op_name="jit(train_step)/optimizer/add"}
  %copy.5 = f32[8]{0} copy(%p)
"""


def _planes():
    # an event is named by the whole instruction, operands included
    ev = lambda name, start, dur: (
        f"%{name} = f32[8]{{0}} fusion(%flash_fwd.2, %flash_dq.7)",
        float(start), float(dur))
    return {"/device:TPU:0": {"XLA Ops": [
        ev("fusion.1", 0, 1e9), ev("flash_fwd.2", 1e9, 2e9),
        ev("fusion.3", 3e9, 3e9), ev("fusion.4", 6e9, 1e9),
        ev("copy.5", 7e9, 1e9), ev("fusion.99", 8e9, 2e9)]}}


def test_scope_seconds_groups_the_window_by_scope_and_kernel():
    names = trace_scopes.op_names(HLO)
    assert names["fusion.4"] == "jit(train_step)/optimizer/add"
    assert names["flash_fwd.2"].endswith("flash_fwd/pallas_call")
    assert names["copy.5"] == ""
    assert trace_scopes.is_kernel("flash_fwd.2", "flash_fwd")
    assert not trace_scopes.is_kernel("flash_fwd_x.2", "flash_fwd")
    out = trace_scopes.scope_seconds(
        _planes(), None, names,
        ["mla", "mla.flash", "moe", "moe.experts", "optimizer", "loss"],
        ["flash_fwd", "flash_dq"])
    assert out["scopes"] == {"mla": 3.0, "mla.flash": 2.0, "moe": 3.0,
                             "moe.experts": 3.0, "optimizer": 1.0,
                             "loss": 0.0}
    assert out["kernels"] == {"flash_fwd": 2.0, "flash_dq": 0.0}
    assert out["matched"] == pytest.approx(0.8)       # fusion.99 is unknown
    half = trace_scopes.scope_seconds(_planes(), (0.0, 4e9), names,
                                      ["moe"], [])
    assert half["scopes"] == {"moe": 1.0}
    assert trace_scopes.scope_seconds({}, None, names, ["moe"], []) == {}
    assert not trace_scopes.in_scope("jit(f)/moe.experts/mul", "moe")


def test_the_new_readers_read_a_traced_context():
    ctx = {"job": "train", "steps": 4, "device_kind": "TPU v5 lite",
           "expert_flops_per_step": 197e12 * 0.05,
           "flash_flops_per_step": 197e12 * 0.03,
           "scope_seconds": {
               "scopes": {"moe.experts": 1.0, "moe": 2.0, "mla": 3.0},
               "kernels": {"flash_fwd": 0.1, "flash_dq": 0.1,
                           "flash_dkv": 0.2}},
           "trace_modules": {"step": "jit_train_step"},
           "reduced": {"modules": {"jit_train_step(123)": {
               "seconds": 8.0, "launches": 4}}},
           "assignments_held": [[[10, 30], [20, 20]], [[25, 15], [40, 0]]]}
    read = lambda name: harness.read_metric(name, ctx)
    assert read("expert_matmul_roofline") == pytest.approx(20.0)
    assert read("flash_attention_roofline") == pytest.approx(30.0)
    assert read("moe_share.train") == pytest.approx(25.0)
    assert read("mla_share.train") == pytest.approx(37.5)
    assert read("expert_load_max_over_mean.train") == pytest.approx(
        (1.5 + 1.0 + 1.25 + 2.0) / 4)
    # a program without the scopes (the parent): nothing to read, no raise
    bare = dict(ctx, scope_seconds=None, assignments_held=None)
    for name in ("expert_matmul_roofline", "flash_attention_roofline",
                 "moe_share.train", "mla_share.train",
                 "expert_load_max_over_mean.train"):
        assert harness.read_metric(name, bare) is None
