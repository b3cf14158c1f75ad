"""The `qwen3_next` cell's benchmark files: the configuration file against
the catalog row, the FLOP model against the program's, the weights, the job
rehearsed on the CPU at toy widths (one correct line; the timed path broken
underneath reads not correct; the reference in float8 and with each planted
fault in the program's place fails a limit), the two new readers, and the
manifest by name."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_qwen3_next as qflops  # noqa: E402
from benchmarks import harness, tiny, tiny_qwen3_next  # noqa: E402

CELL = "qwen3_next_80b_ep16.train"
CONFIG = "qwen3_next_80b_ep16"
# a JSON-lines catalog of published configurations, checked when given
CATALOG = os.environ.get("MODEL_CATALOG", "")
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
NEW = ["gdn_share.train", "gated_delta_roofline.train"]
EXTENDED = ["input_wait_share.train", "step_ms.train", "step_mfu.train",
            "step_device_ms.train", "device_idle_share.train",
            "expert_matmul_roofline", "flash_attention_roofline",
            "moe_share.train", "expert_load_max_over_mean.train"]


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
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
    # the floors of a cut: a whole period, 8 experts, an eighth of the
    # vocabulary; and no width is touched
    assert held["num_hidden_layers"] % pub["full_attention_interval"] == 0
    assert held["num_experts"] >= 8
    assert held["vocab_size"] * 8 >= pub["vocab_size"]
    for key in ("deployment", "assumed", "departures", "optimizer"):
        assert cfg[key]
    assert "16 chips share each layer" in cfg["deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct"][0]
        assert row["config"] == pub and row["source_url"] == cfg["source"]


def test_manifest_lists_the_cell_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert configs[CONFIG]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cells[CELL]["config"] == CONFIG and cells[CELL]["chips"] == 1
    assert cells[CELL]["traffic"] == "train_ids_b16_p2048"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["train_pages_per_s"]["workloads"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in EXTENDED:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "train_pages_per_s"
        assert by_name[name]["source"] == "device_trace"
    assert by_name["gated_delta_roofline.train"]["layer"] == \
        by_name["expert_matmul_roofline"]["layer"]
    assert CELL not in by_name["mla_share.train"]["workloads"]


def test_flops_equal_the_programs_and_the_parameters_the_cut_counts():
    import jax
    import jax.numpy as jnp
    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.models.factory import build_two_tower
    from dnn_page_vectors_tpu.utils import flops as prog
    shape = qflops.shape_of(_config())
    pcfg = get_config(CONFIG)
    mine = qflops.train_flops_per_pair(shape, 16)
    assert mine == pytest.approx(prog.train_flops_per_pair(pcfg, 16),
                                 rel=1e-12)
    # per step: 33,792 tokens x 4 layers x 0.625 assignments x 3 products
    assert qflops.held_assignments_per_token(shape) == 0.625
    assert qflops.expert_matmul_flops_per_step(shape, 16) == \
        3 * 33792 * 4 * 0.625 * 6 * 2048 * 512
    pairs = 2048 * 2049 / 2 + 64 * 65 / 2
    assert qflops.flash_flops_per_step(shape, 16) == \
        3.5 * 16 * 1 * 4 * pairs * 16 * 256
    # the rule, a token and value head at chunk 64: 3 Q K + 2 Q V, ten
    # 64 x 64 tiles of T, 3 K V, 2 FLOPs each
    assert qflops.gated_delta_flops_per_token(shape, 2048) == \
        32 * 2 * (3 * 64 * 128 + 2 * 64 * 128 + 10 * 64 * 64 + 3 * 128 * 128)
    model = build_two_tower(pcfg, pcfg.data.vocab_size)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((2, 64), jnp.int32),
                          jnp.zeros((2, 64), jnp.int32))
    count = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count == 590_967_873       # 590.97M parameters, with log_scale


def test_weights_start_the_norms_where_published_and_the_decays_slow():
    import jax
    import jax.numpy as jnp
    from benchmarks import weights_qwen3_next as wq
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    tree = {"params": {"log_scale": s(), "t": {
        "ln": {"centred_scale": s(64)}, "norm": {"scale": s(8)},
        "A_log": s(32), "dt_bias": s(32), "conv_kernel": s(4, 256),
        "w_gate": s(8, 256, 64), "proj": {"kernel": s(256, 64),
                                         "bias": s(64)}}}}
    p = wq.make_params(tree, 2**31 + 7)["params"]["t"]
    assert float(jnp.abs(p["ln"]["centred_scale"]).max()) == 0.0
    assert float(jnp.abs(p["norm"]["scale"] - 1).max()) == 0.0
    # time steps log-uniform in [1e-3, 1e-1], not the published dt_bias 1
    step = jax.nn.softplus(p["dt_bias"])
    assert float(step.min()) >= 1e-3 * 0.999
    assert float(step.max()) <= 1e-1 * 1.001
    a = jnp.exp(p["A_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0
    assert float(jnp.std(p["conv_kernel"])) == pytest.approx(0.5, rel=0.1)
    assert float(jnp.std(p["w_gate"])) == pytest.approx(256 ** -0.5,
                                                        rel=0.05)


# -- the job, rehearsed ---------------------------------------------------------

def _run(tmp_path):
    from benchmarks.jobs import train_qwen3_next
    root = tiny_qwen3_next.make_root(str(tmp_path / "root"), CELL)
    cell = harness.Cell(CELL, root)
    return cell, train_qwen3_next.run(cell, tiny.SEED, 1.0, False,
                                      time.perf_counter(),
                                      require_chip=False)


def test_rehearsal_is_correct_and_prints_its_line(tmp_path):
    cell, out = _run(tmp_path)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    line = tiny.check_line(cell, out, "train_pages_per_s")
    assert {"loss1", "loss2", "loss3", "grad_norm", "change_norm",
            "routing_gap", "dropped_assignments", "built_in_window"} <= \
        set(line["compared"])
    ctx = out["ctx"]
    # every window step counts both sides' positions in all three layers
    assert ctx["gdn_tokens"] == out["attempted"] * 8 * (8 + 136) * 3
    assert ctx["gdn_state_norm_max"] > 0
    for metric in ("input_wait_share.train", "step_ms.train",
                   "expert_load_max_over_mean.train"):
        assert harness.read_metric(metric, ctx) is not None
    # no trace: the device metrics are left out, never reported as 0
    for metric in NEW + ["expert_matmul_roofline",
                         "flash_attention_roofline", "moe_share.train",
                         "step_device_ms.train"]:
        assert harness.read_metric(metric, ctx) is None


def test_state_not_carried_in_the_timed_path_reads_not_correct(
        tmp_path, monkeypatch):
    from dnn_page_vectors_tpu.models import qwen3_next
    real = qwen3_next.gated_delta
    monkeypatch.setattr(qwen3_next, "gated_delta", lambda *a: real(
        *a, carry_state=False))
    _, out = _run(tmp_path)
    assert out["correct"] is False
    assert {k for k, c in out["compared"].items() if not c["ok"]}


def test_control_and_planted_faults_each_fail_a_limit(tmp_path):
    """The reference in the program's place: in float8, and with each of
    the model's planted faults."""
    from benchmarks import compare
    from benchmarks.jobs import train_qwen3_next
    from benchmarks.reference import qwen3_next as ref
    root = tiny_qwen3_next.make_root(str(tmp_path / "root"), CELL)
    readings = train_qwen3_next.controls(harness.Cell(CELL, root), tiny.SEED)
    assert set(readings) == {"control_fp8"} | {f"fault_{f}"
                                               for f in ref.FAULTS}
    for kind, numbers in readings.items():
        judged = compare.judge(numbers, tiny_qwen3_next.LIMITS)
        assert not all(c["ok"] for c in judged.values()), (kind, judged)


# -- the readers ----------------------------------------------------------------

def test_the_new_readers_read_a_traced_context():
    ctx = {"job": "train", "steps": 4, "device_kind": "TPU v5 lite",
           "gdn_tokens": 1000.0,
           "gated_delta_flops_per_token": 197e12 * 1e-3 / 1000,
           "gated_delta_bytes_per_token": 819e9 * 2e-3 / 1000,
           "scope_seconds": {"scopes": {"gdn": 4.0, "gdn.delta": 0.01}},
           "trace_modules": {"step": "jit_train_step"},
           "reduced": {"modules": {"jit_train_step(9)": {
               "seconds": 8.0, "launches": 4}}}}
    read = lambda name: harness.read_metric(name, ctx)
    assert read("gdn_share.train") == pytest.approx(50.0)
    # the bytes bound it: 2 ms of least time in 10 ms
    assert read("gated_delta_roofline.train") == pytest.approx(20.0)
    # a program without the scopes or the counter (the parent): nothing to
    # read, no raise
    for bare in (dict(ctx, scope_seconds=None), {}):
        for name in NEW:
            assert harness.read_metric(name, bare) is None
    assert harness.read_metric("gated_delta_roofline.train",
                               dict(ctx, gdn_tokens=None)) is None


def test_control_flow_is_left_out_of_the_scope_sums():
    """The trace lists a `while` or `conditional` as one event over its
    body, whose operations are events of their own: the job takes the
    control flow out of the instruction-to-scope map, so a scan's body in
    `gdn.delta` (or a `cond` in `moe`) is counted once."""
    from benchmarks import trace_scopes
    from benchmarks.jobs import train_qwen3_next
    text = (
        '  %while.8 = (s32[], bf16[4,8]{1,0}) while((s32[], bf16[4,8]{1,0}) '
        '%t), condition=%c, body=%b, metadata={op_name="jit(train_step)/'
        'query_tower/layers/block0_mix/gdn/gdn.delta/while"}\n'
        '  %cond.9 = bf16[4,8]{1,0} conditional(pred[] %p, bf16[4,8]{1,0} %x,'
        ' bf16[4,8]{1,0} %x), true_computation=%u, false_computation=%v, '
        'metadata={op_name="jit(train_step)/query_tower/moe/moe/cond"}\n'
        '  %fusion.10 = f32[128,128]{1,0} fusion(%while.8), kind=kLoop, '
        'calls=%f, metadata={op_name="jit(train_step)/query_tower/layers/'
        'block0_mix/gdn/gdn.delta/while/body/dot_general"}\n')
    assert train_qwen3_next.control_flow(text) == {"while.8", "cond.9"}
    names = trace_scopes.op_names(text)
    ev = lambda name, start, dur: (f"%{name} = f32[8]{{0}} fusion(%p)",
                                   float(start), float(dur))
    planes = {"/device:TPU:0": {"XLA Ops": [
        ev("while.8", 0, 4e9), ev("fusion.10", 1e9, 2e9),
        ev("cond.9", 5e9, 1e9)]}}
    both = trace_scopes.scope_seconds(planes, None, names,
                                      ["gdn.delta", "moe"], [])
    assert both["scopes"] == {"gdn.delta": 6.0, "moe": 1.0}
    for name in train_qwen3_next.control_flow(text):
        names[name] = ""
    once = trace_scopes.scope_seconds(planes, None, names,
                                      ["gdn.delta", "moe"], [])
    assert once["scopes"] == {"gdn.delta": 2.0, "moe": 0.0}
