"""The train job: `Trainer.compiled_step` driven through `Trainer.batches()`
(TrainBatcher -> prefetch_to_device), at the cell's batch, on weights and
inputs the benchmark makes from the seed.

Set-up builds ONE object (the compiled step with its state), drives it
through its first three steps on the window's own call and feed while
reading what `correct` compares, and hands that same object to the window.
Once the window has closed and the state is freed, the plain reference
follows those three steps from the same weights and rows.
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

from .. import compare, corpus, flops, harness, vocab, weights
from ..reference import towers, train_ref

CHECKED_STEPS = 3
WARM_STEPS = 3          # after the checked steps, before the window


# -- the feed ---------------------------------------------------------------

class Feed:
    """A cell's corpus and tokenizers for the program, and the same rows'
    token ids for the reference."""

    def __init__(self, cell, seed: int, scratch: str):
        t, a = cell.traffic, cell.config["assumed"]
        self.kind = t["feed"]
        self.vocab_size = cell.config["published"]["vocab_size"]
        self.q_len, self.p_len = a["query_len"], a["page_len"]
        self.seed = seed
        if self.kind == "hash_ids":
            self.corpus = corpus.IdCorpus(t["corpus_pages"])
            self.tokenizers = (
                corpus.HashTokenizer(self.vocab_size, self.q_len, seed, 0),
                corpus.HashTokenizer(self.vocab_size, self.p_len, seed, 1))
        elif self.kind == "jsonl_text":
            from dnn_page_vectors_tpu.data.jsonl import JsonlCorpus
            from dnn_page_vectors_tpu.data.subword import SubwordTokenizer
            self.path = os.path.join(scratch, "corpus.jsonl")
            corpus.write_synth_jsonl(
                self.path, t["corpus_pages"], seed=seed & 0x7FFFFFFF,
                page_len=t["text_page_words"],
                query_len=t["text_query_words"])
            self.corpus = JsonlCorpus(self.path)
            self.vocab = vocab.load_or_build(harness.CACHE_DIR, cell.config)
            self.tokenizers = tuple(
                SubwordTokenizer(self.vocab, style="wordpiece", max_tokens=n)
                for n in (self.q_len, self.p_len))
        else:
            raise ValueError(f"unknown feed {self.kind!r}")

    def reference_ids(self, page_ids) -> tuple:
        """(query ids, page ids) of those rows, made without the program."""
        if self.kind == "hash_ids":
            return (corpus.hash_ids(self.seed, 0, page_ids, self.q_len,
                                    self.vocab_size),
                    corpus.hash_ids(self.seed, 1, page_ids, self.p_len,
                                    self.vocab_size))
        recs = corpus.read_records(self.path, page_ids)
        return (vocab.encode(self.vocab, [recs[int(i)]["query"]
                                          for i in page_ids], self.q_len),
                vocab.encode(self.vocab, [recs[int(i)]["page"]
                                          for i in page_ids], self.p_len))


# -- the program's side -----------------------------------------------------

def program_config(cell, seed: int):
    """The program's Config for this cell: its preset, the config file's
    overrides, the traffic's batch. The published widths are checked against
    what the preset resolves to."""
    from dnn_page_vectors_tpu.config import get_config
    prog = cell.config["program"]
    ov = dict(prog["overrides"])
    ov.update(cell.traffic.get("overrides", {}))
    ov.update(cell.workload.get("overrides", {}))
    ov["train.seed"] = seed & 0x7FFFFFFF
    cfg = get_config(prog["preset"], ov)
    shape, m = flops.shape_of(cell.config), cfg.model
    got = {"d": m.model_dim, "ff": m.mlp_dim, "layers": m.num_layers,
           "out_dim": m.out_dim, "variant": m.encoder,
           "page_len": cfg.data.page_len, "query_len": cfg.data.query_len}
    if got != shape or m.dtype != cell.config["compute_dtype"] \
            or m.dropout != cell.config["assumed"]["dropout"] \
            or cfg.data.vocab_size != cell.config["published"]["vocab_size"]:
        raise SystemExit(f"the preset resolves to {got}, the configuration "
                         f"file states {shape}")
    return cfg


def arch_of(cell) -> dict:
    pub = cell.config["published"]
    shape = flops.shape_of(cell.config)
    heads = pub.get("num_heads", pub.get("num_attention_heads"))
    return {"variant": shape["variant"], "layers": shape["layers"],
            "heads": heads}


def shape_tree(trainer):
    import jax
    import jax.numpy as jnp
    d = trainer.cfg.data
    q = jnp.zeros((2, d.query_len), jnp.int32)
    p = jnp.zeros((2, d.page_len), jnp.int32)
    return jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0), q, p)


def tree_without_a_run(cell, seed: int, corpus_, tokenizers, workdir: str):
    """The parameters' shape tree of a cell, for the controls, which put the
    reference in the program's place and so build no state."""
    from dnn_page_vectors_tpu.train.loop import Trainer
    return shape_tree(Trainer(program_config(cell, seed), corpus=corpus_,
                              tokenizers=tokenizers, workdir=workdir))


def make_state(trainer, params):
    """The program's TrainState around benchmark-made parameters, placed as
    `Trainer.init_state` places its own."""
    import jax
    import jax.numpy as jnp
    from dnn_page_vectors_tpu.parallel.sharding import (
        put_global, replicated, shard_params)
    from dnn_page_vectors_tpu.train.loop import TrainState
    params = shard_params(params, trainer.mesh)
    mesh_devs = frozenset(trainer.mesh.devices.flat)

    def on_mesh(leaf):
        sh = getattr(leaf, "sharding", None)
        if sh is not None and frozenset(sh.device_set) == mesh_devs \
                and not isinstance(sh, jax.sharding.SingleDeviceSharding):
            return leaf
        return put_global(leaf, replicated(trainer.mesh))

    opt_state = jax.tree_util.tree_map(on_mesh, trainer.tx.init(params))
    step = put_global(jnp.zeros((), jnp.int32), replicated(trainer.mesh))
    return TrainState(params=params, opt_state=opt_state, step=step)


def _adam_mu(opt_state):
    """The first-moment tree inside the program's optimizer state."""
    import jax
    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError("no single Adam state in the optimizer state")
    return found[0].mu


def _wrap_step(step):
    """A seam for the tests, which break the timed path underneath here."""
    return step


def _change_norms(params, tree, seed: int, temperature: float) -> dict:
    """{leaf: ||params - initial||}, the initial values regenerated from the
    seed leaf by leaf rather than kept."""
    p0 = weights.make_params(tree, seed, temperature)
    return train_ref.leaf_norms(params, minus=p0)


# -- the run ----------------------------------------------------------------

def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True) -> dict:
    import jax
    harness.setup_jax()
    if require_chip:
        harness.require_chips(cell.chips)
    from dnn_page_vectors_tpu.train.loop import Trainer
    from dnn_page_vectors_tpu.utils.profiling import PipelineProfiler

    with harness.scratch_dir("bench_train_") as scratch:
        harness.note_time("imports")
        cfg = program_config(cell, seed)
        feed = Feed(cell, seed, scratch)
        harness.note_time("feed")
        batch_size = cfg.train.batch_size
        temperature = cell.config["assumed"]["temperature_init"]
        trainer = Trainer(cfg, corpus=feed.corpus, tokenizers=feed.tokenizers,
                          workdir=os.path.join(scratch, "work"))
        tree = shape_tree(trainer)
        state = make_state(trainer,
                           weights.make_params(tree, seed, temperature))
        harness.note_time("trainer, weights and state")
        step = _wrap_step(trainer.compiled_step(state))
        rng = trainer.base_rng()
        prof = PipelineProfiler()
        batches = trainer.batches(start_step=0, profiler=prof)

        # the first three steps, through the window's own call and feed
        prog = {"loss": [], "rows": []}
        b1 = cell.config["optimizer"]["b1"]
        for i in range(CHECKED_STEPS):
            batch = next(batches)
            prog["rows"].append(np.asarray(batch["page_id"]))
            state, metrics = step(state, batch, rng)
            prog["loss"].append(float(metrics["loss"]))
            if i == 0:
                harness.note_time("first step")
                prog["grad"] = {k: v / (1.0 - b1) for k, v in
                                train_ref.leaf_norms(
                                    _adam_mu(state.opt_state)).items()}
                harness.note_time("gradient norms")
        harness.note_time("steps two and three")
        prog["change"] = _change_norms(state.params, tree, seed, temperature)
        harness.note_time("change norms")
        for _ in range(WARM_STEPS):
            state, metrics = step(state, next(batches), rng)
        jax.block_until_ready(state)
        prof.reset()
        harness.note_time("warm steps")
        harness.note_compiles("before the window")

        # the window
        steps, gaps, inflight = 0, [], []
        setup_s = time.perf_counter() - t_start
        with harness.Window(seconds, trace, scratch) as win:
            last = win.t0
            while time.perf_counter() < win.deadline:
                state, metrics = step(state, next(batches), rng)
                steps += 1
                inflight.append(metrics["loss"])
                if len(inflight) > 2:        # at most two steps run ahead
                    jax.block_until_ready(inflight.pop(0))
                now = time.perf_counter()
                gaps.append(now - last)
                last = now
            jax.block_until_ready(state)
            window_s = win.close()
        last_loss = float(metrics["loss"])
        stage_s, stage_n = prof.stages(), prof.counts()
        device = harness.device_info(cell.chips)
        harness.note_memory("after the window")
        steps_done = int(state.step)
        batches.close()
        del state, step, batches, trainer, batch, metrics, inflight
        gc.collect()

        harness.note_time("window and freeing the state")
        # the plain reference, on the same weights and rows
        ref = reference_readings(cell, feed, tree, seed, prog["rows"])

    numbers = compare.train_numbers(prog, ref)
    numbers["rows_distinct"] = float(
        sum(len(set(r.tolist())) != len(r) for r in prog["rows"]))
    numbers["built_in_window"] = float(win.programs_built)
    harness.note_compiles("at the end")
    limits = dict(cell.workload["limits"], rows_distinct=0.0,
                  built_in_window=0.0)
    compared = compare.judge(numbers, limits)
    counted = steps_done == CHECKED_STEPS + WARM_STEPS + steps
    correct = bool(all(c["ok"] for c in compared.values()) and counted
                   and np.isfinite(last_loss))
    shape = flops.shape_of(cell.config)
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
                "flops_per_pair": flops.train_flops_per_pair(shape,
                                                             batch_size),
                "device_kind": device["kind"],
                "trace_modules": cell.workload.get("trace_modules", {})},
    }


def reference_readings(cell, feed, tree, seed: int, rows: list,
                       quant=towers.identity, half_batch: bool = False
                       ) -> dict:
    """Loss of each of the three steps, norms of the first clipped gradient
    and of the parameters' change, by the plain reference."""
    import jax.numpy as jnp
    temperature = cell.config["assumed"]["temperature_init"]
    ref = train_ref.TrainReference(
        arch_of(cell), cell.config["optimizer"],
        cell.workload["reference_block_rows"], quant=quant)
    params = weights.make_params(tree, seed, temperature)
    mu, nu = ref.init_opt(params)
    out = {"loss": []}
    for i, ids in enumerate(rows):
        q_ids, p_ids = (jnp.asarray(x) for x in feed.reference_ids(ids))
        keep = np.arange(len(ids) // 2) if half_batch else None
        loss, grads = ref.loss_and_grads(params, q_ids, p_ids, rows=keep)
        out["loss"].append(float(loss))
        harness.note_time(f"reference step {i + 1}: loss and gradients")
        raw = train_ref.leaf_norms(grads) if i == 0 else None
        params, mu, nu, clip = ref.apply(params, mu, nu, grads, i)
        del grads
        if i == 0:
            out["grad"] = {k: v * float(clip) for k, v in raw.items()}
    out["change"] = _change_norms(params, tree, seed, temperature)
    harness.note_time("reference updates and change norms")
    return out
