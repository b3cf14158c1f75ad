"""The serve job of the `granitemoehybrid` tower: `jobs/serve.py`'s protocol
(the store once per checkout, every shape warm, the open-loop window, every
answer waited for, the plain reference once the service is freed; the
accepted serve readers read its `ctx`), with what this tower changes:

* a query is a whole page: its text is its id, its 1,024 token ids a hash of
  (seed, side, id, position) over the HELD slice of the vocabulary;
* weights come from `weights_ssm` (held in bfloat16 where the configuration
  says so) and the reference is `reference/granitemoehybrid.py`;
* beside `rank_gap` and `score_gap` (a random store row sees 1/32 of a
  vector's error) the sampled queries are encoded once more after the window,
  through the service's own compiled encode (no program is built), and
  compared with the reference's vectors (`vector_gap`, largest L2 distance
  between unit vectors); the assignments per held expert that those same
  calls counted are compared with the reference's routing (`routing_gap`);
* the service's `encode.*` counters are read around the window;
* a traced run groups the window's device-op time by name scope
  (`trace_scopes.py`), encode program by encode program, while the trace is
  still on disk.
"""
from __future__ import annotations

import concurrent.futures
import gc
import json
import os
import sys
import time
import zlib

import numpy as np

from .. import compare, corpus, harness, trace_reduce, trace_scopes
from .. import flops as base_flops
from .. import flops_granitemoehybrid as ssm_flops
from .. import weights_ssm
from ..reference import granitemoehybrid as ref_model
from ..reference import serve_ref, towers
from ..traffic import generator
from . import serve
from .train import shape_tree

SCOPES = ["mamba", "mamba.in_proj", "mamba.conv", "mamba.ssd",
          "mamba.gate_norm", "mamba.out_proj", "attn", "attn.flash", "moe",
          "moe.router", "moe.dispatch", "moe.experts", "moe.shared",
          "moe.combine"]
KERNELS = ["flash_fwd", "moe_gmm"]
COUNTERS = ("tokens", "moe_assignments_held", "moe_tiles_used",
            "moe_dropped")

# published key -> the program's ModelConfig field that must equal it
_MODEL_KEYS = {
    "hidden_size": "model_dim", "intermediate_size": "mlp_dim",
    "shared_intermediate_size": "shared_intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_key_value_heads",
    "attention_multiplier": "attention_multiplier",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "mamba_n_heads": "mamba_n_heads", "mamba_d_head": "mamba_d_head",
    "mamba_d_state": "mamba_d_state", "mamba_expand": "mamba_expand",
    "mamba_d_conv": "mamba_d_conv", "mamba_chunk_size": "mamba_chunk_size",
    "num_local_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "rms_norm_eps": "rms_norm_eps"}


class QueryTokenizer(corpus.HashTokenizer):
    """`HashTokenizer` that also takes a text that is no number (the
    service's own warm-up sends "warmup"): it hashes the text."""

    def encode_batch(self, texts) -> np.ndarray:
        ids = [int(t) if t.isdigit() else (1 << 40) + zlib.crc32(t.encode())
               for t in texts]
        return corpus.hash_ids(self.seed, self.side, ids, self.max_tokens,
                               self.vocab_size)


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
    got = {k: getattr(m, f) for k, f in _MODEL_KEYS.items()}
    want = {k: pub[k] for k in _MODEL_KEYS}
    got.update(layer_types=list(m.layer_types), layers=m.num_layers,
               experts_held=m.experts_held, held_start=m.experts_held_start,
               vocab=cfg.data.vocab_size, out_dim=m.out_dim,
               page_len=cfg.data.page_len, query_len=cfg.data.query_len,
               dtype=m.dtype, weights=m.weights_dtype, dropout=m.dropout,
               shared=m.shared_towers, encoder=m.encoder,
               attention=m.attention,
               encode_batch=cfg.serve.encode_batch,
               query_tokens=cfg.data.query_len)
    want.update(layer_types=held["layer_types"],
                layers=held["num_hidden_layers"],
                experts_held=held["num_local_experts"],
                held_start=held["experts_held_start"],
                vocab=held["vocab_size"], out_dim=a["out_dim"],
                page_len=a["page_len"], query_len=a["query_len"],
                dtype=cell.config["compute_dtype"],
                weights=cell.config["weights_dtype"], dropout=a["dropout"],
                shared=True, encoder=pub["model_type"],
                attention=a["attention"], encode_batch=a["encode_batch"],
                query_tokens=cell.traffic["query_tokens"])
    # what the program has no field for, because it builds one value only
    got.update(groups=1, conv_bias=True)
    want.update(groups=pub["mamba_n_groups"], conv_bias=pub["mamba_conv_bias"])
    if got != want:
        diff = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
        raise SystemExit("the preset resolves to other sizes than the "
                         f"configuration file states (got, stated): {diff}")
    return cfg


def arch_of(cell) -> dict:
    """The reference's sizes: the published keys, with the layers and the
    experts held as the configuration file's `held` gives them."""
    arch = dict(cell.config["published"])
    arch["layer_types"] = list(cell.config["held"]["layer_types"])
    arch["experts_held_start"] = cell.config["held"]["experts_held_start"]
    return arch


def make_params(cell, tree, seed: int):
    a = cell.config["assumed"]
    return weights_ssm.make_params(tree, seed, a["temperature_init"],
                                   cell.config["weights_dtype"],
                                   a["float32_leaves"])


def query_ids(cell, seed: int, ids) -> np.ndarray:
    """[n, query_len] token ids of the queries `ids`, as the service's
    tokenizer makes them (side 0)."""
    return corpus.hash_ids(seed, 0, ids, cell.config["assumed"]["query_len"],
                           cell.config["held"]["vocab_size"])


class Served(serve.Served):
    """`serve.Served` around the hybrid tower: `drive` and `close` are the
    parent's."""

    def __init__(self, cell, seed: int, scratch: str, pool_size: int):
        import jax
        from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
        from dnn_page_vectors_tpu.infer.serve import SearchService
        from dnn_page_vectors_tpu.train.loop import Trainer
        t, a = cell.traffic, cell.config["assumed"]
        if t["feed"] != "hash_ids":
            raise ValueError(f"unknown feed {t['feed']!r}")
        self.cell, self.seed, self.scratch = cell, seed, scratch
        self.k, self.rows = int(t["k"]), int(t["store_rows"])
        self.cfg = cfg = program_config(cell, seed)
        self.shard_rows = cfg.eval.store_shard_size
        vocab = cell.config["held"]["vocab_size"]
        q_tok, p_tok = (QueryTokenizer(vocab, n, seed, side) for side, n in
                        enumerate((a["query_len"], a["page_len"])))
        pages = serve._Pages(self.rows)
        trainer = Trainer(cfg, corpus=pages, tokenizers=(q_tok, p_tok),
                          workdir=os.path.join(scratch, "work"))
        self.tree = shape_tree(trainer)
        params = make_params(cell, self.tree, seed)
        self.embedder = embedder = BulkEmbedder(
            cfg, trainer.model, params, p_tok, trainer.mesh, query_tok=q_tok)
        # the configuration states what is held in which precision: the
        # service has to hold the tree as it was given (no cast of its own)
        cast = [weights_ssm.path_str(path) for (path, x), y in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_leaves(embedder.params)) if x.dtype != y.dtype]
        if cast:
            raise SystemExit("the service holds these leaves in another "
                             f"precision than they were given in: {cast}")
        del params
        del trainer
        harness.note_time("imports, model and weights")
        harness.note_memory("weights on the device")
        self.store_seed = int(t["store_seed"])
        store = serve.open_store(
            os.path.join(harness.CACHE_DIR, "store_" + cell.entry["traffic"]),
            self.store_seed, self.rows, a["out_dim"], self.shard_rows,
            t["store_dtype"])
        harness.note_time("store opened (written in a checkout's first run)")
        # the window's queries are 0 .. pool_size-1; warm-up sends others
        self.texts = [str(i) for i in range(pool_size + 96)]
        self.svc = svc = SearchService(
            cfg, embedder, pages, store,
            preload_hbm_gb=cell.workload["preload_hbm_gb"])
        harness.note_time("service built, store staged")
        if svc.degraded or not svc.preloaded:
            raise SystemExit("the store is not HBM-resident or the service "
                             "came up degraded")
        svc.start_batcher()
        self.search = serve._wrap_search(svc.search)
        self.pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=int(t["clients"]), thread_name_prefix="client")
        # warm every shape: the encode, the scan and the merge, then batches
        # through the batcher, on queries no window sends
        svc.warmup(k=self.k)
        for part in (self.texts[-16:], self.texts[-32:-16]):
            list(self.pool.map(lambda q: self.search(q, self.k), part))
        harness.note_time("warm-up")
        harness.note_compiles("before the window")

    def counters(self) -> dict:
        reg = self.svc.registry
        return {n: reg.counter("encode." + n).value for n in COUNTERS}

    def drive(self, plan: dict, seconds: float, trace: bool) -> dict:
        before = self.counters()
        stats = super().drive(plan, seconds, trace)
        after = self.counters()
        stats["ctx"]["encode_counters"] = {n: after[n] - before[n]
                                           for n in COUNTERS}
        return stats

    def encode_again(self, ids: np.ndarray):
        """[n, L] ids through the service's own compiled encode, a call's
        width at a time (n is a whole number of calls: no row of padding,
        which would be routed and counted; no program is built): (unit
        vectors [n, D], assignments per held expert [layers, held] that
        those calls counted)."""
        B = self.cfg.serve.encode_batch
        vecs, held = [], 0
        for s in range(0, len(ids), B):
            v, (_, h) = self.embedder.encode_query_call(ids[s:s + B])
            vecs.append(np.asarray(v, np.float32))
            held = held + np.asarray(h)
        return np.concatenate(vecs), held

    def program_texts(self) -> dict:
        """{width: compiled text} of the encode program as it was run (the
        same arguments' placement, so the persistent cache holds it): the
        text names every instruction's scope, the trace only the
        instruction."""
        emb, L = self.embedder, self.cell.config["assumed"]["query_len"]
        B = self.cfg.serve.encode_batch
        return {B: emb._encode_query.lower(emb.params, emb._put(np.zeros(
            (B, L), np.int32))).compile().as_text()}


def sample_of(cell, seed: int, answers: list) -> list:
    """The requests whose answers are checked: drawn from the seed among
    those that were answered, in whole encode calls (a row of padding is
    routed too, and would be counted)."""
    have = [i for i, ans in enumerate(answers) if ans is not None]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xC4EC])
    take = min(int(cell.traffic["checked_answers"]), len(have))
    take -= take % cell.config["assumed"]["encode_batch"]
    return sorted(rng.choice(have, size=take, replace=False).tolist()) \
        if take else []


def reference_readings(cell, tree, seed: int, ids: np.ndarray, **how):
    """(unit vectors [n, D], assignments per held expert [layers, held]) of
    the plain reference on weights made anew from the seed."""
    ref = ref_model.ServeReference(
        arch_of(cell), cell.workload["reference_block_rows"], **how)
    params = make_params(cell, tree, seed)
    return ref.vectors(params["params"]["query_tower"], ids)


def answer_gaps(cell, shard_rows: int, q_ref, served_ids,
                served_scores) -> dict:
    """`rank_gap` and `score_gap` as `serve.check_answers` defines them,
    of served (row ids, scores) [n, k] against the reference vectors."""
    t, dim = cell.traffic, cell.config["assumed"]["out_dim"]
    best_s, _, ref_of_served = serve_ref.exact_topk(
        q_ref, int(t["store_seed"]), int(t["store_rows"]), shard_rows, dim,
        int(t["k"]), served_ids)
    return {"rank_gap": float(np.max(best_s - ref_of_served)),
            "score_gap": float(np.max(np.abs(served_scores
                                             - ref_of_served)))}


def limits_of(cell) -> dict:
    """The cell's limits with what is held at 0: what `run` judges its
    numbers by, and `study_ssm.py` the controls'."""
    return dict(cell.workload["limits"], short_answers=0.0, recompiles=0.0,
                built_in_window=0.0, dropped_assignments=0.0)


def _routing_gap(got, want) -> float:
    a, b = (np.asarray(x, float) for x in (got, want))
    return float(np.abs(a - b).sum() / max(b.sum(), 1.0))


def _vector_gap(got, want) -> float:
    return float(np.max(np.linalg.norm(
        np.asarray(got, np.float64) - np.asarray(want, np.float64), axis=1)))


def _scope_seconds(trace_dir: str, programs: dict) -> dict:
    """The window's device-op time by scope and by kernel over the encode
    programs, and those programs' own device seconds. `programs` is
    {bucket: compiled text}; every XLA module of the trace is given the
    program whose instruction names cover most of its operations' time, and
    only modules that launch the grouped-product kernel are encodes."""
    planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    window = harness._span_window(planes)
    names = {b: trace_scopes.op_names(text) for b, text in programs.items()}
    total = {"scopes": dict.fromkeys(SCOPES, 0.0),
             "kernels": dict.fromkeys(KERNELS, 0.0)}
    encode_s = launches = 0.0
    devs = trace_reduce._device_planes(planes)
    for d in devs:
        ops = sorted(planes[d].get(trace_reduce.OPS_LINE, []),
                     key=lambda e: e[1])
        starts = [e[1] for e in ops]
        by_module: dict = {}
        for name, start, dur in planes[d].get(trace_reduce.MODULES_LINE, []):
            a, b = (max(start, window[0]), min(start + dur, window[1])) \
                if window else (start, start + dur)
            if b <= a:
                continue
            lo = np.searchsorted(starts, start, "left")
            hi = np.searchsorted(starts, start + dur, "left")
            m = by_module.setdefault(name, {"ops": [], "s": 0.0, "n": 0})
            m["ops"].extend(ops[lo:hi])
            m["s"] += (b - a) / 1e9
            m["n"] += 1
        for name, m in by_module.items():
            instr = [(trace_scopes._instruction(n), dur)
                     for n, _, dur in m["ops"]]
            if not any(trace_scopes.is_kernel(i, "moe_gmm")
                       for i, _ in instr):
                continue
            cover = {b: sum(dur for i, dur in instr if i in nm)
                     for b, nm in names.items()}
            best = max(cover, key=cover.get)
            got = trace_scopes.scope_seconds(
                {d: {trace_reduce.OPS_LINE: m["ops"]}}, window, names[best],
                SCOPES, KERNELS)
            for group in ("scopes", "kernels"):
                for key, sec in got.get(group, {}).items():
                    total[group][key] += sec / len(devs)
            encode_s += m["s"] / len(devs)
            launches += m["n"] / len(devs)
            print(f"trace encode module {name}: bucket {best}, "
                  f"{m['n']} launches, {m['s']:.4f} s, ops matched "
                  f"{100 * got.get('matched', 0):.1f}%", file=sys.stderr)
    for group in ("scopes", "kernels"):
        for name, sec in sorted(total[group].items()):
            print(f"trace {group[:-1]} {name}: {sec:.6f} s", file=sys.stderr)
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
    with harness.scratch_dir("bench_serve_ssm_") as scratch:
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
            again, held = served.encode_again(ids) if sample else (None,
                                                                   None)
            rebuilt = harness.COMPILES["programs"] - built0
            scope_seconds = None
            if trace:
                scope_seconds = _scope_seconds(
                    os.path.join(scratch, "trace"), served.program_texts())
            tree, shard_rows = served.tree, served.shard_rows
        finally:
            served.close()
        del served
        gc.collect()
        harness.note_time("window, answers, the sample again, freeing")
        answers = stats["answers"]
        if sample:
            q_ref, ref_held = reference_readings(cell, tree, seed, ids)
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
            numbers["routing_gap"] = _routing_gap(held, ref_held)
        else:
            numbers.update(rank_gap=float("inf"), score_gap=float("inf"),
                           vector_gap=float("inf"),
                           routing_gap=float("inf"))
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
    numbers["dropped_assignments"] = float(
        ctx["encode_counters"]["moe_dropped"])
    numbers["recompiles"] = float(ctx["recompiles"])
    numbers["built_in_window"] = float(stats["programs_built"] + rebuilt)
    harness.note_compiles("at the end")
    compared = compare.judge(numbers, limits_of(cell))
    shape = ssm_flops.shape_of(cell.config)
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
                    flops_per_query=ssm_flops.serve_flops_per_query(
                        shape, int(t["store_rows"])),
                    scan_bytes_per_launch=base_flops.scan_bytes_per_dispatch(
                        shard_rows, a["out_dim"]),
                    trace_modules=cell.workload.get("trace_modules", {}),
                    scope_seconds=scope_seconds,
                    query_tokens=a["query_len"],
                    mamba_layers=ssm_flops.mamba_layers(shape),
                    expert_layers=len(shape["layer_types"]),
                    expert_tile_rows=256,
                    ssd_flops_per_query=ssm_flops.scan_flops_per_query(
                        shape, a["query_len"]),
                    ssd_bytes_per_query=ssm_flops.scan_bytes_per_query(
                        shape, a["query_len"]),
                    expert_flops_per_assignment=
                    ssm_flops.expert_flops_per_assignment(shape),
                    expert_kernel_bytes_per_call=
                    ssm_flops.expert_kernel_bytes_per_call(shape)),
    }


def controls(cell, seed: int, kinds=None, queries: int = 8) -> dict:
    """{kind: compared numbers} of the reference put in the program's place:
    in float8 (the control), and with each planted fault of this model. No
    program state is built; `queries` queries are drawn from the seed."""
    from dnn_page_vectors_tpu.train.loop import Trainer
    every = {"control_fp8": {"quant": towers.to_fp8},
             "fault_no_carry": {"carry_state": False},
             "fault_softmax_all": {"softmax_all": True},
             "fault_no_residual_multiplier": {"residual": False}}
    a, t = cell.config["assumed"], cell.traffic
    k = int(t["k"])
    with harness.scratch_dir("study_ssm_") as scratch:
        cfg = program_config(cell, seed)
        tok = QueryTokenizer(cell.config["held"]["vocab_size"],
                             a["query_len"], seed, 0)
        tree = shape_tree(Trainer(cfg, corpus=serve._Pages(8),
                                  tokenizers=(tok, tok), workdir=scratch))
    rng = np.random.default_rng(seed & 0xFFFFFFFF)
    ids = query_ids(cell, seed, rng.choice(1 << 20, size=queries,
                                           replace=False))
    q_ref, ref_held = reference_readings(cell, tree, seed, ids)
    none = np.full((len(ids), k), -1, np.int64)
    out = {}
    for kind in kinds or every:
        q_low, low_held = reference_readings(cell, tree, seed, ids,
                                             **every[kind])
        low_s, low_i, _ = serve_ref.exact_topk(
            q_low, int(t["store_seed"]), int(t["store_rows"]),
            cfg.eval.store_shard_size, a["out_dim"], k, none)
        numbers = answer_gaps(cell, cfg.eval.store_shard_size, q_ref, low_i,
                              low_s)
        numbers["vector_gap"] = _vector_gap(q_low, q_ref)
        numbers["routing_gap"] = _routing_gap(low_held, ref_held)
        out[kind] = numbers
    return out
