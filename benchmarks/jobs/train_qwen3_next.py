"""The train job of the `qwen3_next` tower: `jobs/train_moe.py`'s protocol by
import (three checked steps on the window's own call and feed, three warm
steps, the window, the counters read once it has closed, then the plain
reference on the same weights and rows; the accepted train readers read its
`ctx`), with what this tower changes:

* the preset is checked against every published key of `qwen3_next`, and a
  key it does not carry is named in the exit;
* weights come from `weights_qwen3_next` and the reference is
  `reference/qwen3_next.py` (the delta rule token by token);
* the FLOP model is `flops_qwen3_next`;
* the step's `gdn/tokens`, `gdn/state_norm_max` and `moe/worst_case_calls`
  counters are kept on the device for every call and read once the window
  has closed: the window's tokens through the recurrence (for
  `gated_delta_roofline.train`), the largest head state's norm, and, step by
  step beside the window's gaps, the expert-layer calls that took the
  worst-case buffers (the last two to standard error);
* the traced run's scope sums are over this tower's scopes, with the
  program's `while` and `conditional` events left out (their bodies'
  operations are counted once each);
* `controls` puts the reference in the program's place in float8 and with
  each of this model's planted faults.
"""
from __future__ import annotations

import gc  # noqa: F401  (read by the protocol's body)
import os  # noqa: F401
import re
import sys
import time  # noqa: F401
import types

import numpy as np

from .. import compare, corpus, harness  # noqa: F401
from .. import flops_qwen3_next as moe_flops
from .. import trace_reduce, trace_scopes  # noqa: F401
from .. import weights_qwen3_next as weights_moe
from ..reference import qwen3_next as ref_model
from ..reference import towers, train_ref
from . import train_moe
from .train import (CHECKED_STEPS, WARM_STEPS, _adam_mu,  # noqa: F401
                    make_state, shape_tree)
from .train_moe import Feed, _routing_gap  # noqa: F401

SCOPES = ["gdn", "gdn.in_proj", "gdn.conv", "gdn.delta", "gdn.gate_norm",
          "gdn.out_proj", "attn", "attn.qkv", "attn.rope", "attn.flash",
          "attn.out", "moe", "moe.router", "moe.dispatch", "moe.experts",
          "moe.shared", "moe.combine", "loss", "optimizer"]
KERNELS = ["flash_fwd", "flash_dq", "flash_dkv", "moe_gmm", "moe_tgmm"]

# published key -> the program's ModelConfig field that must equal it
_MODEL_KEYS = {
    "hidden_size": "model_dim", "intermediate_size": "mlp_dim",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_key_value_heads", "head_dim": "head_dim",
    "partial_rotary_factor": "partial_rotary_factor",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "full_attention_interval": "full_attention_interval",
    "linear_num_key_heads": "linear_num_key_heads",
    "linear_num_value_heads": "linear_num_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel_dim",
    "moe_intermediate_size": "moe_intermediate_size",
    "shared_expert_intermediate_size": "shared_intermediate_size",
    "num_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok"}
# published switches the program builds one value of
_BUILT = {"decoder_sparse_step": 1, "mlp_only_layers": [],
          "norm_topk_prob": True, "hidden_act": "silu",
          "rope_scaling": None, "use_sliding_window": False,
          "tie_word_embeddings": False}
# published keys the check reads otherwise: the type is the encoder, the
# depth and the vocabulary are held, the positions bound the lengths
_OTHER = ("model_type", "num_hidden_layers", "vocab_size",
          "max_position_embeddings")
# (gdn/tokens, gdn/state_norm_max, moe/worst_case_calls) of every step call
_GDN = []


def program_config(cell, seed: int):
    """The program's Config for this cell: its preset, the config file's
    overrides, the traffic's batch. What the preset resolves to is checked
    against every key of the published config."""
    from dnn_page_vectors_tpu.config import get_config
    prog = cell.config["program"]
    ov = dict(prog["overrides"])
    ov.update(cell.traffic.get("overrides", {}))
    ov.update(cell.workload.get("overrides", {}))
    ov["train.seed"] = seed & 0x7FFFFFFF
    cfg = get_config(prog["preset"], ov)
    pub, held, a = (cell.config[k] for k in ("published", "held", "assumed"))
    unread = set(pub) - set(_MODEL_KEYS) - set(_BUILT) - set(_OTHER)
    if unread:
        raise SystemExit("the configuration file states keys the program "
                         f"does not carry: {sorted(unread)}")
    m = cfg.model
    got = {k: getattr(m, f) for k, f in _MODEL_KEYS.items()}
    want = {k: pub[k] for k in _MODEL_KEYS}
    got.update(layers=m.num_layers, experts_held=m.experts_held,
               vocab=cfg.data.vocab_size, out_dim=m.out_dim,
               page_len=cfg.data.page_len, query_len=cfg.data.query_len,
               dtype=m.dtype, dropout=m.dropout, shared=m.shared_towers,
               encoder=m.encoder, attention=m.attention,
               built=_BUILT,
               positions=max(cfg.data.page_len, cfg.data.query_len)
               <= pub["max_position_embeddings"])
    want.update(layers=held["num_hidden_layers"],
                experts_held=held["num_experts"],
                vocab=held["vocab_size"], out_dim=a["out_dim"],
                page_len=a["page_len"], query_len=a["query_len"],
                dtype=cell.config["compute_dtype"], dropout=a["dropout"],
                shared=True, encoder=pub["model_type"],
                attention=a["attention"],
                built={k: pub[k] for k in _BUILT}, positions=True)
    if got != want:
        diff = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
        raise SystemExit("the preset resolves to other sizes than the "
                         f"configuration file states (got, stated): {diff}")
    return cfg


def arch_of(cell) -> dict:
    """The reference's sizes: the published keys, with the depth and the
    experts held as the configuration file's `held` gives them, and the
    program's chunk (which only a planted fault reads)."""
    held = cell.config["held"]
    return dict(cell.config["published"],
                num_hidden_layers=held["num_hidden_layers"],
                experts_held_start=held["experts_held_start"],
                chunk=cell.config["assumed"]["chunk"])


def _wrap_step(step):
    """Keeps every call's linear-attention counters and worst-case count
    (device arrays, not waited for); a seam for the tests, which break the
    timed path underneath here."""
    def counted(state, batch, rng):
        state, metrics = step(state, batch, rng)
        _GDN.append((metrics["gdn/tokens"], metrics["gdn/state_norm_max"],
                     metrics["moe/worst_case_calls"]))
        return state, metrics
    return counted


def reference_readings(cell, feed, tree, seed: int, rows: list,
                       quant=towers.identity, faults=()) -> dict:
    """Loss of each of the three steps, norms of the first clipped gradient
    and of the parameters' change, and the assignments per held expert, by
    the plain reference. `quant` is the lower-precision control, `faults`
    the planted faults (reference/qwen3_next.py:FAULTS)."""
    import jax.numpy as jnp
    temperature = cell.config["assumed"]["temperature_init"]
    ref = ref_model.Qwen3NextTrainReference(
        arch_of(cell), cell.config["optimizer"],
        cell.workload["reference_block_rows"], quant=quant, faults=faults)
    params = weights_moe.make_params(tree, seed, temperature)
    mu, nu = ref.init_opt(params)
    out = {"loss": [], "held": []}
    for i, ids in enumerate(rows):
        q_ids, p_ids = (jnp.asarray(x) for x in feed.reference_ids(ids))
        loss, grads = ref.loss_and_grads(params, q_ids, p_ids)
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


def control_flow(step_text: str) -> set:
    """The `while` and `conditional` instructions of a compiled program's
    text. The trace lists each as one event over its body, whose operations
    are events of their own: summed by scope, a body would count twice."""
    found = set()
    for line in step_text.splitlines():
        head = line.split("metadata=")[0]
        m = trace_scopes._INSTR.match(head)
        if m and re.search(r"\s(while|conditional)\(", head):
            found.add(m.group(1))
    return found


def _scope_seconds(win, step_text: str) -> dict:
    """`train_moe._scope_seconds` with the program's control flow left out
    of the sums (its bodies' operations are counted): the step of this tower
    holds a `while` in `gdn.delta` (the scan over chunks) besides the
    expert layer's `cond`."""
    planes = trace_reduce.load(trace_reduce.find_xplane(win._dir))
    names = trace_scopes.op_names(step_text)
    for name in control_flow(step_text):
        names[name] = ""
    out = trace_scopes.scope_seconds(
        planes, harness._span_window(planes), names, SCOPES, KERNELS)
    for group in ("scopes", "kernels"):
        for name, sec in sorted(out.get(group, {}).items()):
            print(f"trace {group[:-1]} {name}: {sec:.6f} s",
                  file=sys.stderr)
    print(f"trace ops matched to the step's text: "
          f"{100 * out.get('matched', 0):.1f}%", file=sys.stderr)
    return out


# `train_moe`'s protocol, reading this module's names: the preset check,
# the weights, the FLOP model, the seam, the scopes and the reference
_protocol = types.FunctionType(train_moe.run.__code__, globals(),
                               "_protocol")
_change_norms = types.FunctionType(train_moe._change_norms.__code__,
                                   globals(), "_change_norms")


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True) -> dict:
    del _GDN[:]
    out = _protocol(cell, seed, seconds, trace, t_start, require_chip)
    steps = out["attempted"]
    window = _GDN[len(_GDN) - steps:] if steps else []
    tokens = float(sum(np.asarray(t, np.float64).sum() for t, _, _ in window))
    worst = max((float(np.max(s)) for _, s, _ in _GDN), default=0.0)
    fallback = [int(w) for _, _, w in window]
    before = sum(int(w) for _, _, w in _GDN[:len(_GDN) - steps])
    del _GDN[:]
    print(f"gdn: {tokens:.0f} tokens through the recurrence in the window, "
          f"largest head state norm {worst:.4g}", file=sys.stderr)
    gaps = out["ctx"]["step_gaps_s"]
    print(f"window steps: worst-case expert calls {fallback} (before the "
          f"window {before}); gaps (s) "
          f"{[round(g, 4) for g in gaps]}", file=sys.stderr)
    shape = moe_flops.shape_of(cell.config)
    out["ctx"].update(
        gdn_tokens=tokens, gdn_state_norm_max=worst,
        gated_delta_flops_per_token=moe_flops.gated_delta_flops_per_token_fb(
            shape),
        gated_delta_bytes_per_token=moe_flops.gated_delta_bytes_per_token(
            shape))
    return out


def controls(cell, seed: int, kinds=None) -> dict:
    """{kind: compared numbers} of the reference put in the program's place:
    in float8 (the control), and with each planted fault. No program state
    is built."""
    from dnn_page_vectors_tpu.train.loop import Trainer
    every = {"control_fp8": {"quant": towers.to_fp8}}
    every.update({f"fault_{f}": {"faults": (f,)} for f in ref_model.FAULTS})
    with harness.scratch_dir("study_qwen3_next_") as scratch:
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
