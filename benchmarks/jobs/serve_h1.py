"""The serve job of the `falcon_h1` tower: `jobs/serve_ssm.py`'s protocol by
import (the store once per checkout, every shape warm, the open-loop window,
every answer waited for, the sampled queries encoded once more through the
service's own compiled encode, the plain reference once the service is freed;
the accepted serve readers read its `ctx`), with what this tower changes:

* the preset is checked against every published key of `falcon_h1` (groups
  and the mixer's inner width among them);
* weights come from `weights_h1` (the configuration's gains; held in bfloat16
  where it says so) and the reference is `reference/falcon_h1.py`;
* the tower has no routed layer: no `routing_gap`, no `dropped_assignments`;
  of the `encode.*` counters only `tokens` counts;
* the traced run's scope sums are over this tower's scopes, and its encode
  program is the module of the trace that launches the flash kernel;
* the four ratios the weights' gains are chosen by (layer 0 of one seeded
  query, by the reference) go to stderr.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
import types

import numpy as np

from .. import compare, harness, trace_reduce, trace_scopes
from .. import flops as base_flops
from .. import flops_h1, weights_h1
from ..reference import falcon_h1 as ref_model
from ..reference import serve_ref, towers
from ..traffic import generator
from . import serve, serve_ssm
from .serve_ssm import (QueryTokenizer, _vector_gap, answer_gaps, query_ids,
                        sample_of)
from .train import shape_tree

SCOPES = ["mamba", "mamba.in_proj", "mamba.conv", "mamba.ssd",
          "mamba.gate_norm", "mamba.out_proj", "attn", "attn.qkv",
          "attn.rope", "attn.flash", "attn.out", "mlp", "mlp.gate_up",
          "mlp.down"]
KERNELS = ["flash_fwd"]

# published key -> the program's ModelConfig field that must equal it
_MODEL_KEYS = {
    "hidden_size": "model_dim", "intermediate_size": "mlp_dim",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_key_value_heads", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "mamba_n_heads": "mamba_n_heads", "mamba_d_head": "mamba_d_head",
    "mamba_d_ssm": "mamba_d_ssm", "mamba_d_state": "mamba_d_state",
    "mamba_n_groups": "mamba_n_groups", "mamba_d_conv": "mamba_d_conv",
    "mamba_chunk_size": "mamba_chunk_size", "mamba_expand": "mamba_expand",
    "embedding_multiplier": "embedding_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_multipliers": "ssm_multipliers",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "attention_in_multiplier": "attention_in_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "key_multiplier": "key_multiplier", "mlp_multipliers": "mlp_multipliers"}
# published switches the program builds one value of
_BUILT = {"mamba_conv_bias": True, "mamba_rms_norm": True,
          "mamba_norm_before_gate": False, "mamba_proj_bias": False,
          "attention_bias": False, "mlp_bias": False,
          "projectors_bias": False, "rope_scaling": None,
          "hidden_act": "silu"}


def program_config(cell, seed: int):
    """The program's Config for this cell: its preset, the config file's
    overrides. What the preset resolves to is checked against every number
    the configuration file states."""
    from dnn_page_vectors_tpu.config import get_config
    prog = cell.config["program"]
    ov = dict(prog["overrides"])
    ov.update(cell.traffic.get("overrides", {}))
    ov.update(cell.workload.get("overrides", {}))
    ov["train.seed"] = seed & 0x7FFFFFFF
    cfg = get_config(prog["preset"], ov)
    pub, held, a = (cell.config[k] for k in ("published", "held", "assumed"))
    m = cfg.model
    plain = lambda v: list(v) if isinstance(v, (tuple, list)) else v
    got = {k: plain(getattr(m, f)) for k, f in _MODEL_KEYS.items()}
    want = {k: plain(pub[k]) for k in _MODEL_KEYS}
    got.update(layers=m.num_layers, vocab=cfg.data.vocab_size,
               out_dim=m.out_dim, page_len=cfg.data.page_len,
               query_len=cfg.data.query_len, dtype=m.dtype,
               weights=m.weights_dtype, dropout=m.dropout,
               shared=m.shared_towers, encoder=m.encoder,
               attention=m.attention, encode_batch=cfg.serve.encode_batch,
               query_tokens=cfg.data.query_len,
               query_cache=cfg.serve.query_cache_size,
               inner=m.mamba_n_heads * m.mamba_d_head, built=_BUILT)
    want.update(layers=held["num_hidden_layers"], vocab=held["vocab_size"],
                out_dim=a["out_dim"], page_len=a["page_len"],
                query_len=a["query_len"], dtype=cell.config["compute_dtype"],
                weights=cell.config["weights_dtype"], dropout=a["dropout"],
                shared=True, encoder=pub["model_type"],
                attention=a["attention"], encode_batch=a["encode_batch"],
                query_tokens=cell.traffic["query_tokens"], query_cache=0,
                inner=pub["mamba_d_ssm"],
                built={k: pub[k] for k in _BUILT})
    if got != want:
        diff = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
        raise SystemExit("the preset resolves to other sizes than the "
                         f"configuration file states (got, stated): {diff}")
    return cfg


def arch_of(cell) -> dict:
    """The reference's sizes: the published keys, with the layers as the
    configuration file's `held` gives them."""
    return dict(cell.config["published"],
                num_hidden_layers=cell.config["held"]["num_hidden_layers"])


def make_params(cell, tree, seed: int):
    a = cell.config["assumed"]
    return weights_h1.make_params(
        tree, seed, a["temperature_init"], cell.config["weights_dtype"],
        a["float32_leaves"], a["gains"],
        {"in_proj": ref_model.segment_widths(cell.config["published"])})


class Served(serve_ssm.Served):
    """`serve_ssm.Served` around this tower: its construction, `drive`,
    `counters`, `program_texts` and `close` as they are, with this module's
    `program_config` and `make_params` where the parent's names them."""

    __init__ = types.FunctionType(
        serve_ssm.Served.__init__.__code__,
        dict(vars(serve_ssm), program_config=program_config,
             make_params=make_params), "__init__")

    def encode_again(self, ids: np.ndarray) -> np.ndarray:
        """[n, L] ids through the service's own compiled encode, a call's
        width at a time (n is a whole number of calls; no program is
        built): unit vectors [n, D]."""
        B = self.cfg.serve.encode_batch
        return np.concatenate([
            np.asarray(self.embedder.encode_query_call(ids[s:s + B])[0],
                       np.float32) for s in range(0, len(ids), B)])


def reference_vectors(cell, tree, seed: int, ids: np.ndarray,
                      ratios: bool = False, **how):
    """Unit vectors [n, D] of the plain reference on weights made anew from
    the seed; with `ratios`, beside them what the gains are chosen by
    (`reference/falcon_h1.py:branch_ratios`, on the first of the rows)."""
    arch = arch_of(cell)
    ref = ref_model.ServeReference(
        arch, cell.workload["reference_block_rows"], **how)
    tower = make_params(cell, tree, seed)["params"]["query_tower"]
    vectors = np.asarray(ref.vectors(tower, ids))
    if ratios:
        return vectors, ref_model.branch_ratios(tower, ids[:1], arch)
    return vectors


def limits_of(cell) -> dict:
    """The cell's limits with what is held at 0: what `run` judges its
    numbers by, and `study_h1.py` the controls'."""
    return dict(cell.workload["limits"], short_answers=0.0, recompiles=0.0,
                built_in_window=0.0)


def _scope_seconds(trace_dir: str, text: str) -> dict:
    """The window's device-op time by scope and by kernel over the encode
    program (`text`: its compiled text, which names every instruction's
    scope), and that program's own device seconds. The encode is every XLA
    module of the trace that launches the flash kernel (the scan and the
    merge do not)."""
    planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    window = harness._span_window(planes)
    names = trace_scopes.op_names(text)
    total = {"scopes": dict.fromkeys(SCOPES, 0.0),
             "kernels": dict.fromkeys(KERNELS, 0.0)}
    encode_s = launches = 0.0
    devs = trace_reduce._device_planes(planes)
    for d in devs:
        ops = sorted(planes[d].get(trace_reduce.OPS_LINE, []),
                     key=lambda e: e[1])
        starts = [e[1] for e in ops]
        for name, start, dur in planes[d].get(trace_reduce.MODULES_LINE, []):
            a, b = (max(start, window[0]), min(start + dur, window[1])) \
                if window else (start, start + dur)
            if b <= a:
                continue
            mine = ops[np.searchsorted(starts, start, "left"):
                       np.searchsorted(starts, start + dur, "left")]
            if not any(trace_scopes.is_kernel(
                    trace_scopes._instruction(n), "flash_fwd")
                    for n, _, _ in mine):
                continue
            got = trace_scopes.scope_seconds(
                {d: {trace_reduce.OPS_LINE: mine}}, window, names, SCOPES,
                KERNELS)
            for group in ("scopes", "kernels"):
                for key, sec in got.get(group, {}).items():
                    total[group][key] += sec / len(devs)
            encode_s += (b - a) / 1e9 / len(devs)
            launches += 1 / len(devs)
    for group in ("scopes", "kernels"):
        for name, sec in sorted(total[group].items()):
            print(f"trace {group[:-1]} {name}: {sec:.6f} s", file=sys.stderr)
    print(f"trace encode program: {launches:.0f} launches, {encode_s:.4f} s",
          file=sys.stderr)
    total["encode_module_seconds"] = encode_s
    total["encode_launches"] = launches
    return total


# -- the run ----------------------------------------------------------------

def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True) -> dict:
    harness.setup_jax()
    if require_chip:
        harness.require_chips(cell.chips)
    t, a = cell.traffic, cell.config["assumed"]
    with harness.scratch_dir("bench_serve_h1_") as scratch:
        plan = generator.schedule(
            t, seed, harness.window_seconds(seconds, trace))
        served = Served(cell, seed, scratch, int(plan["query"].max()) + 1)
        try:
            setup_s = time.perf_counter() - t_start
            stats = served.drive(plan, seconds, trace)
            device = harness.device_info(cell.chips)
            harness.note_memory("after the window")
            sample = sample_of(cell, seed, stats["answers"])
            ids = query_ids(cell, seed, [int(plan["query"][i])
                                         for i in sample])
            built0 = harness.COMPILES["programs"]
            again = served.encode_again(ids) if sample else None
            rebuilt = harness.COMPILES["programs"] - built0
            scope_seconds = None
            if trace:
                (text,) = served.program_texts().values()
                scope_seconds = _scope_seconds(
                    os.path.join(scratch, "trace"), text)
            tree, shard_rows = served.tree, served.shard_rows
        finally:
            served.close()
        del served
        gc.collect()
        harness.note_time("window, answers, the sample again, freeing")
        answers = stats["answers"]
        if sample:
            q_ref, ratios = reference_vectors(cell, tree, seed, ids,
                                              ratios=True)
            print("branch ratios at layer 0: " + json.dumps(ratios),
                  file=sys.stderr)
        harness.note_time("reference vectors")
        k = int(t["k"])
        numbers = {"short_answers": float(sum(
            len(answers[i]) != k
            or len({h["page_id"] for h in answers[i]}) != k
            for i in sample))}
        good = [j for j, i in enumerate(sample) if len(answers[i]) == k]
        if good:
            numbers.update(answer_gaps(
                cell, shard_rows, q_ref[np.asarray(good)],
                np.asarray([[h["page_id"] for h in answers[sample[j]]]
                            for j in good], np.int64),
                np.asarray([[h["score"] for h in answers[sample[j]]]
                            for j in good], np.float32)))
            numbers["vector_gap"] = _vector_gap(again, q_ref)
        else:
            numbers.update(rank_gap=float("inf"), score_gap=float("inf"),
                           vector_gap=float("inf"))
        harness.note_time("reference scores of every row")
    ctx, n, failed = stats["ctx"], stats["n"], stats["failed"]
    lat_ms = stats["latency_ms"]
    # one line for whoever reads a run's stderr: the front's numbers
    print("served: " + json.dumps({
        "latency_ms": {q: harness.percentile(lat_ms, q)
                       for q in (50, 90, 95, 99, 100)},
        "over_1s": int((lat_ms > 1000).sum()),
        "slowest_due_s": [round(float(plan["due_s"][i]), 2)
                          for i in np.argsort(-lat_ms)[:5]],
        "stage_seconds": stats["stage_seconds"],
        "stage_counts": stats["stage_counts"],
        **{k: v for k, v in ctx.items() if np.isscalar(v) or
           isinstance(v, dict)}}, default=float), file=sys.stderr)
    numbers["recompiles"] = float(ctx["recompiles"])
    numbers["built_in_window"] = float(stats["programs_built"] + rebuilt)
    harness.note_compiles("at the end")
    compared = compare.judge(numbers, limits_of(cell))
    shape = flops_h1.shape_of(cell.config)
    L = a["query_len"]
    return {
        "correct": bool(all(c["ok"] for c in compared.values())
                        and failed == 0),
        "attempted": n, "failed": failed,
        "end_to_end": {"serve_p95_ms": harness.percentile(lat_ms, 95),
                       "setup_s": setup_s},
        "compared": compared, "device": device, "reduced": stats["reduced"],
        "ctx": dict(ctx, job="serve", window_s=stats["window_s"],
                    requests=n, answered=n - failed, chips=cell.chips,
                    latency_p50_ms=harness.percentile(lat_ms, 50),
                    stage_seconds=stats["stage_seconds"],
                    stage_counts=stats["stage_counts"],
                    reduced=stats["reduced"], device_kind=device["kind"],
                    flops_per_query=flops_h1.serve_flops_per_query(
                        shape, int(t["store_rows"])),
                    scan_bytes_per_launch=base_flops.scan_bytes_per_dispatch(
                        shard_rows, a["out_dim"]),
                    trace_modules=cell.workload.get("trace_modules", {}),
                    scope_seconds=scope_seconds, query_tokens=L,
                    mamba_layers=shape["layers"],
                    attn_layers=shape["layers"], mlp_layers=shape["layers"],
                    ssd_flops_per_query=flops_h1.scan_flops_per_query(
                        shape, L),
                    ssd_bytes_per_query=flops_h1.scan_bytes_per_query(
                        shape, L),
                    flash_flops_per_layer=flops_h1.flash_flops_per_layer(
                        shape, L),
                    flash_bytes_per_layer=flops_h1.flash_bytes_per_layer(
                        shape, L),
                    mlp_flops_per_token=flops_h1.mlp_flops_per_token(shape)),
    }


def controls(cell, seed: int, kinds=None, queries: int = 8) -> dict:
    """{kind: compared numbers} of the reference put in the program's place:
    in float8 (the control), and with each planted fault of this model. No
    program state is built; `queries` queries are drawn from the seed."""
    from dnn_page_vectors_tpu.train.loop import Trainer
    every = {"control_fp8": {"quant": towers.to_fp8},
             "fault_no_carry": {"carry_state": False},
             "fault_one_group": {"one_group": True},
             "fault_no_rotary": {"rotary": False},
             "fault_no_key_multiplier": {"key_multiplier": False},
             "fault_ungrouped_norm": {"grouped_norm": False},
             "fault_no_mup": {"mup": False}}
    a, t = cell.config["assumed"], cell.traffic
    k = int(t["k"])
    with harness.scratch_dir("study_h1_") as scratch:
        cfg = program_config(cell, seed)
        tok = QueryTokenizer(cell.config["held"]["vocab_size"],
                             a["query_len"], seed, 0)
        tree = shape_tree(Trainer(cfg, corpus=serve._Pages(8),
                                  tokenizers=(tok, tok), workdir=scratch))
    rng = np.random.default_rng(seed & 0xFFFFFFFF)
    ids = query_ids(cell, seed, rng.choice(1 << 20, size=queries,
                                           replace=False))
    q_ref = reference_vectors(cell, tree, seed, ids)
    none = np.full((len(ids), k), -1, np.int64)
    out = {}
    for kind in kinds or every:
        q_low = reference_vectors(cell, tree, seed, ids, **every[kind])
        low_s, low_i, _ = serve_ref.exact_topk(
            q_low, int(t["store_seed"]), int(t["store_rows"]),
            cfg.eval.store_shard_size, a["out_dim"], k, none)
        numbers = answer_gaps(cell, cfg.eval.store_shard_size, q_ref, low_i,
                              low_s)
        numbers["vector_gap"] = _vector_gap(q_low, q_ref)
        out[kind] = numbers
    return out
