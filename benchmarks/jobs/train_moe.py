"""The train job of a tower with routed experts (`glm4_moe_lite`): the same
protocol as `jobs/train.py` (three checked steps on the window's own call and
feed, three warm steps, the window, then the plain reference on the same
weights and rows), through the same `Trainer.compiled_step` and
`Trainer.batches()`, with what the expert layer adds:

* ids are drawn over the HELD slice of the vocabulary;
* weights come from `weights_moe` (stacked expert kernels by their fan-in);
* the reference is `reference/glm4_moe_lite.py`, one shared tower;
* the step's counters (`moe/assignments_held`, `moe/dropped`) are kept on
  the device during the window and read once it has closed; the checked
  steps' counts are compared with the reference's own routing;
* a traced run groups the window's device-op time by name scope
  (`trace_scopes.py`) while the trace is still on disk, for the readers of
  the kernels' and the layers' shares.
"""
from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

from .. import compare, corpus, harness, trace_reduce, trace_scopes
from .. import flops_glm4_moe_lite as moe_flops
from .. import weights_moe
from ..reference import glm4_moe_lite as ref_model
from ..reference import towers, train_ref
from .train import (CHECKED_STEPS, WARM_STEPS, _adam_mu, make_state,
                    shape_tree)

SCOPES = ["mla", "mla.flash", "moe", "moe.router", "moe.dispatch",
          "moe.experts", "moe.shared", "moe.combine", "loss", "optimizer"]
KERNELS = ["flash_fwd", "flash_dq", "flash_dkv", "moe_gmm", "moe_tgmm"]

# published key -> the program's ModelConfig field that must equal it
_MODEL_KEYS = {
    "hidden_size": "model_dim", "intermediate_size": "mlp_dim",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_attention_heads": "num_heads", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "n_routed_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "routed_scaling_factor": "routed_scaling_factor",
    "first_k_dense_replace": "first_k_dense_replace",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps"}


class Feed:
    """Hashed ids over the held rows of the vocabulary, for the program
    (corpus + tokenizers) and for the reference (`reference_ids`)."""

    def __init__(self, cell, seed: int):
        t, a = cell.traffic, cell.config["assumed"]
        if t["feed"] != "hash_ids":
            raise ValueError(f"unknown feed {t['feed']!r}")
        self.vocab_size = cell.config["held"]["vocab_size"]
        self.q_len, self.p_len = a["query_len"], a["page_len"]
        self.seed = seed
        self.corpus = corpus.IdCorpus(t["corpus_pages"])
        self.tokenizers = (
            corpus.HashTokenizer(self.vocab_size, self.q_len, seed, 0),
            corpus.HashTokenizer(self.vocab_size, self.p_len, seed, 1))

    def reference_ids(self, page_ids) -> tuple:
        return (corpus.hash_ids(self.seed, 0, page_ids, self.q_len,
                                self.vocab_size),
                corpus.hash_ids(self.seed, 1, page_ids, self.p_len,
                                self.vocab_size))


def program_config(cell, seed: int):
    """The program's Config for this cell: its preset, the config file's
    overrides, the traffic's batch. What the preset resolves to is checked
    against every number the configuration file states."""
    from dnn_page_vectors_tpu.config import get_config
    prog = cell.config["program"]
    ov = dict(prog["overrides"])
    ov.update(cell.traffic.get("overrides", {}))
    ov.update(cell.workload.get("overrides", {}))
    ov["train.seed"] = seed & 0x7FFFFFFF
    cfg = get_config(prog["preset"], ov)
    pub, held, a = (cell.config[k] for k in ("published", "held", "assumed"))
    m = cfg.model
    got = {k: getattr(m, f) for k, f in _MODEL_KEYS.items()}
    want = {k: pub[k] for k in _MODEL_KEYS}
    got.update(layers=m.num_layers, experts_held=m.experts_held,
               vocab=cfg.data.vocab_size, out_dim=m.out_dim,
               page_len=cfg.data.page_len, query_len=cfg.data.query_len,
               dtype=m.dtype, dropout=m.dropout, shared=m.shared_towers,
               encoder=m.encoder, attention=m.attention)
    want.update(layers=held["num_hidden_layers"],
                experts_held=held["n_routed_experts"],
                vocab=held["vocab_size"], out_dim=a["out_dim"],
                page_len=a["page_len"], query_len=a["query_len"],
                dtype=cell.config["compute_dtype"], dropout=a["dropout"],
                shared=True, encoder=pub["model_type"],
                attention=a["attention"])
    if got != want:
        diff = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
        raise SystemExit("the preset resolves to other sizes than the "
                         f"configuration file states (got, stated): {diff}")
    return cfg


def arch_of(cell) -> dict:
    """The reference's sizes: the published keys, with the depth and the
    experts held as the configuration file's `held` gives them."""
    arch = dict(cell.config["published"])
    arch["num_hidden_layers"] = cell.config["held"]["num_hidden_layers"]
    arch["experts_held_start"] = cell.config["held"]["experts_held_start"]
    return arch


def _wrap_step(step):
    """A seam for the tests, which break the timed path underneath here."""
    return step


def _change_norms(params, tree, seed: int, temperature: float) -> dict:
    p0 = weights_moe.make_params(tree, seed, temperature)
    return train_ref.leaf_norms(params, minus=p0)


def _routing_gap(got: dict, want: dict) -> float:
    """Assignments per held expert of the checked steps, one side against
    the other, as a share of all of them."""
    a, b = (np.asarray(x["held"], float) for x in (got, want))
    return float(np.abs(a - b).sum() / max(b.sum(), 1.0))


def _scope_seconds(win, step_text: str) -> dict:
    """The window's device-op time by scope and by kernel, read while the
    trace is still in the scratch directory."""
    planes = trace_reduce.load(trace_reduce.find_xplane(win._dir))
    out = trace_scopes.scope_seconds(
        planes, harness._span_window(planes),
        trace_scopes.op_names(step_text), SCOPES, KERNELS)
    for group in ("scopes", "kernels"):
        for name, sec in sorted(out.get(group, {}).items()):
            print(f"trace {group[:-1]} {name}: {sec:.6f} s",
                  file=sys.stderr)
    print(f"trace ops matched to the step's text: "
          f"{100 * out.get('matched', 0):.1f}%", file=sys.stderr)
    return out


# -- the run ----------------------------------------------------------------

def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True) -> dict:
    import jax
    harness.setup_jax()
    if require_chip:
        harness.require_chips(cell.chips)
    from dnn_page_vectors_tpu.train.loop import Trainer
    from dnn_page_vectors_tpu.utils.profiling import PipelineProfiler

    with harness.scratch_dir("bench_train_moe_") as scratch:
        harness.note_time("imports")
        cfg = program_config(cell, seed)
        feed = Feed(cell, seed)
        batch_size = cfg.train.batch_size
        temperature = cell.config["assumed"]["temperature_init"]
        trainer = Trainer(cfg, corpus=feed.corpus, tokenizers=feed.tokenizers,
                          workdir=os.path.join(scratch, "work"))
        tree = shape_tree(trainer)
        state = make_state(trainer,
                           weights_moe.make_params(tree, seed, temperature))
        harness.note_time("trainer, weights and state")
        compiled = trainer.compiled_step(state)
        step = _wrap_step(compiled)
        rng = trainer.base_rng()
        prof = PipelineProfiler()
        batches = trainer.batches(start_step=0, profiler=prof)

        # the first three steps, through the window's own call and feed
        prog = {"loss": [], "rows": [], "held": []}
        b1 = cell.config["optimizer"]["b1"]
        dropped = 0.0
        for i in range(CHECKED_STEPS):
            batch = next(batches)
            prog["rows"].append(np.asarray(batch["page_id"]))
            state, metrics = step(state, batch, rng)
            prog["loss"].append(float(metrics["loss"]))
            prog["held"].append(np.asarray(metrics["moe/assignments_held"]))
            dropped += float(metrics["moe/dropped"])
            if i == 0:
                harness.note_time("first step")
                prog["grad"] = {k: v / (1.0 - b1) for k, v in
                                train_ref.leaf_norms(
                                    _adam_mu(state.opt_state)).items()}
        harness.note_time("steps two and three")
        prog["change"] = _change_norms(state.params, tree, seed, temperature)
        for _ in range(WARM_STEPS):
            state, metrics = step(state, next(batches), rng)
        jax.block_until_ready(state)
        prof.reset()
        harness.note_time("change norms and warm steps")
        harness.note_compiles("before the window")

        # the window
        steps, gaps, inflight, counters = 0, [], [], []
        setup_s = time.perf_counter() - t_start
        with harness.Window(seconds, trace, scratch) as win:
            last = win.t0
            while time.perf_counter() < win.deadline:
                state, metrics = step(state, next(batches), rng)
                steps += 1
                inflight.append(metrics["loss"])
                counters.append((metrics["moe/assignments_held"],
                                 metrics["moe/dropped"]))
                if len(inflight) > 2:        # at most two steps run ahead
                    jax.block_until_ready(inflight.pop(0))
                now = time.perf_counter()
                gaps.append(now - last)
                last = now
            jax.block_until_ready(state)
            window_s = win.close()
        last_loss = float(metrics["loss"])
        held = [np.asarray(h).tolist() for h, _ in counters]
        dropped += float(sum(float(d) for _, d in counters))
        stage_s, stage_n = prof.stages(), prof.counts()
        device = harness.device_info(cell.chips)
        harness.note_memory("after the window")
        steps_done = int(state.step)
        scope_seconds = None
        if trace:
            # the program's text names every instruction's scope; the trace
            # names only the instruction (the persistent cache holds the
            # program, so this builds nothing new)
            text = compiled.lower(state, batch, rng).compile().as_text()
            scope_seconds = _scope_seconds(win, text)
            del text
        batches.close()
        del state, step, compiled, batches, trainer, batch, metrics
        del inflight, counters
        gc.collect()
        harness.note_time("window and freeing the state")
        ref = reference_readings(cell, feed, tree, seed, prog["rows"])

    numbers = compare.train_numbers(prog, ref)
    for key in ("grad", "change"):
        gap, leaf = compare.worst_leaf_gap(prog[key], ref[key])
        print(f"widest {key} gap: {gap:.4g} at {leaf}", file=sys.stderr)
    numbers["routing_gap"] = _routing_gap(prog, ref)
    numbers["dropped_assignments"] = dropped
    numbers["rows_distinct"] = float(
        sum(len(set(r.tolist())) != len(r) for r in prog["rows"]))
    numbers["built_in_window"] = float(win.programs_built)
    harness.note_compiles("at the end")
    limits = dict(cell.workload["limits"], rows_distinct=0.0,
                  built_in_window=0.0, dropped_assignments=0.0)
    compared = compare.judge(numbers, limits)
    counted = steps_done == CHECKED_STEPS + WARM_STEPS + steps
    correct = bool(all(c["ok"] for c in compared.values()) and counted
                   and np.isfinite(last_loss))
    shape = moe_flops.shape_of(cell.config)
    return {
        "correct": correct, "attempted": steps, "failed": 0 if counted
        else abs(steps_done - CHECKED_STEPS - WARM_STEPS - steps),
        "end_to_end": {"train_pages_per_s": steps * batch_size / window_s,
                       "setup_s": setup_s},
        "compared": compared, "device": device, "reduced": win.reduced,
        "ctx": {"job": "train", "window_s": window_s, "steps": steps,
                "batch": batch_size, "chips": cell.chips,
                "step_gaps_s": gaps, "stage_seconds": stage_s,
                "stage_counts": stage_n, "reduced": win.reduced,
                "flops_per_pair": moe_flops.train_flops_per_pair(
                    shape, batch_size),
                "expert_flops_per_step":
                    moe_flops.expert_matmul_flops_per_step(shape, batch_size),
                "flash_flops_per_step":
                    moe_flops.flash_flops_per_step(shape, batch_size),
                "scope_seconds": scope_seconds, "assignments_held": held,
                "device_kind": device["kind"],
                "trace_modules": cell.workload.get("trace_modules", {})},
    }


def reference_readings(cell, feed, tree, seed: int, rows: list,
                       quant=towers.identity, half_batch: bool = False,
                       causal: bool = True, scaling: bool = True) -> dict:
    """Loss of each of the three steps, norms of the first clipped gradient
    and of the parameters' change, and the assignments per held expert, by
    the plain reference. `quant` is the lower-precision control;
    `half_batch`, `causal=False` and `scaling=False` are planted faults."""
    import jax.numpy as jnp
    temperature = cell.config["assumed"]["temperature_init"]
    ref = ref_model.MoeTrainReference(
        arch_of(cell), cell.config["optimizer"],
        cell.workload["reference_block_rows"], quant=quant, causal=causal,
        scaling=scaling)
    params = weights_moe.make_params(tree, seed, temperature)
    mu, nu = ref.init_opt(params)
    out = {"loss": [], "held": []}
    for i, ids in enumerate(rows):
        q_ids, p_ids = (jnp.asarray(x) for x in feed.reference_ids(ids))
        keep = np.arange(len(ids) // 2) if half_batch else None
        loss, grads = ref.loss_and_grads(params, q_ids, p_ids, rows=keep)
        out["loss"].append(float(loss))
        out["held"].append(np.asarray(ref.counts))
        harness.note_time(f"reference step {i + 1}: loss and gradients")
        raw = train_ref.leaf_norms(grads) if i == 0 else None
        params, mu, nu, clip = ref.apply(params, mu, nu, grads, i)
        del grads
        if i == 0:
            out["grad"] = {k: v * float(clip) for k, v in raw.items()}
    out["change"] = _change_norms(params, tree, seed, temperature)
    harness.note_time("reference updates and change norms")
    return out


def controls(cell, seed: int, kinds=None) -> dict:
    """{kind: compared numbers} of the reference put in the program's place:
    in float8 (the control), and with each planted fault. No program state
    is built."""
    from dnn_page_vectors_tpu.train.loop import Trainer
    every = {"control_fp8": {"quant": towers.to_fp8},
             "fault_half_batch": {"half_batch": True},
             "fault_no_scaling": {"scaling": False},
             "fault_bidirectional": {"causal": False}}
    with harness.scratch_dir("study_moe_") as scratch:
        feed = Feed(cell, seed)
        cfg = program_config(cell, seed)
        tree = shape_tree(Trainer(cfg, corpus=feed.corpus,
                                  tokenizers=feed.tokenizers,
                                  workdir=scratch))
        rng = np.random.default_rng(seed & 0xFFFFFFFF)
        rows = [rng.choice(feed.corpus.num_pages, size=cfg.train.batch_size,
                           replace=False) for _ in range(CHECKED_STEPS)]
        ref = reference_readings(cell, feed, tree, seed, rows)
        out = {}
        for kind in kinds or every:
            other = reference_readings(cell, feed, tree, seed, rows,
                                       **every[kind])
            numbers = compare.train_numbers(other, ref)
            numbers["routing_gap"] = _routing_gap(other, ref)
            out[kind] = numbers
    return out
