"""Contrastive training loop (SURVEY.md §3 #12; call stack §4.1).

The hot loop is ONE jit-compiled `train_step` with donated state:
  encode both towers -> global-batch cosine-contrastive loss -> grad ->
  optax update. Under a >1-device mesh the same step is compiled with the
  batch sharded over 'data' and params sharded by parallel/sharding.py; XLA
  emits the gradient psum / page-vector all-gather over ICI (the reference's
  torch-DDP/NCCL role, BASELINE.json:5). Everything host-side (tokenization,
  logging, checkpointing) stays off the compiled path.
"""
from __future__ import annotations

import os
import sys
import time
from functools import partial
from typing import Any, Dict, Iterator, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from dnn_page_vectors_tpu.config import Config
from dnn_page_vectors_tpu.data.loader import (
    TrainBatcher, build_corpus, build_tokenizer, prefetch_to_device)
from dnn_page_vectors_tpu.data.toy import ToyCorpus
from dnn_page_vectors_tpu.models.factory import build_two_tower
from dnn_page_vectors_tpu.models.losses import cosine_contrastive_loss
from dnn_page_vectors_tpu.parallel.mesh import fit_mesh_to_devices, make_mesh
from dnn_page_vectors_tpu.parallel.sharding import (
    batch_sharding, param_shardings, put_global, replicated, shard_params,
    stacked_batch_sharding)
from dnn_page_vectors_tpu.models.glm_moe import STATS as MOE_STATS
from dnn_page_vectors_tpu.models.qwen3_next import GDN_STATS
from dnn_page_vectors_tpu.train.optimizer import SELECT_BIAS, make_optimizer
from dnn_page_vectors_tpu.utils import faults, telemetry
from dnn_page_vectors_tpu.utils.logging import MetricsLogger
from dnn_page_vectors_tpu.utils.profiling import PipelineProfiler


@flax.struct.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray          # int32 scalar


def _logged(value):
    """A step metric as the log line carries it: a scalar as a float, the
    expert layers' counters as lists."""
    return float(value) if np.ndim(value) == 0 \
        else np.asarray(value).tolist()


def moe_metrics(stats) -> Dict[str, jnp.ndarray]:
    """The routed-expert layers' counters of one step, from what the shared
    tower sowed (one entry per call, query then page: summed):
    `moe/assignments_held` [layers, held], `moe/assignments_absent` [layers],
    `moe/dropped` (scalar; 0 by construction, and counted),
    `moe/worst_case_calls` (scalar: the (row group, layer) calls whose
    routing needed more than the expected-load buffers and took the
    worst-case ones; no assignment is lost either way)."""
    tower = stats["query_tower"]
    return {"moe/assignments_held": sum(tower["held"]),
            "moe/assignments_absent": sum(tower["absent"]),
            "moe/dropped": sum(tower["dropped"]).sum(),
            "moe/worst_case_calls": sum(tower["worst_case"]).sum()}


def gdn_metrics(stats) -> Dict[str, jnp.ndarray]:
    """The linear-attention layers' counters of one step, from what the
    shared tower sowed (one entry per call, query then page):
    `gdn/tokens` [layers] the positions the recurrence ran over (summed),
    `gdn/state_norm_max` [layers] the largest Frobenius norm of a head's
    final state (the larger of the two calls)."""
    tower = stats["query_tower"]
    return {"gdn/tokens": sum(tower["tokens"]),
            "gdn/state_norm_max": jnp.max(
                jnp.stack(tower["state_norm_max"]), axis=0)}


def make_train_step(model, tx, loss_chunk: int = 0, moe_stats: bool = False,
                    gdn_stats: bool = False):
    """Build the (un-jitted) global-batch train step; caller jits with
    shardings + donation.

    `moe_stats`: the towers have routed-expert layers (models/glm_moe.py),
    whose counters join the step's metrics, reduced on the device;
    `gdn_stats` likewise for linear-attention layers
    (models/qwen3_next.py).

    `loss_chunk` > 0 selects the fused/chunked contrastive loss
    (train.loss_chunk, models/losses.py): the [B, B(1+H)] logits never
    materialize — per-chunk log-sum-exp tiles stream against the
    GSPMD-gathered global page pool instead."""

    collections = [MOE_STATS] * moe_stats + [GDN_STATS] * gdn_stats

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray],
                   base_rng: jax.Array) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        rng = jax.random.fold_in(base_rng, state.step)

        def loss_fn(params):
            out = model.apply(
                params, batch["query"], batch["page"],
                batch.get("neg_page"), deterministic=False,
                rngs={"dropout": rng},
                page_seg=batch.get("page_seg"),
                page_pos=batch.get("page_pos"),
                mutable=collections or False)
            (q, p, neg, scale), stats = out if collections else (out, None)
            # Flax names the towers' ops by module path; the loss and the
            # optimizer are no modules, so they get their scopes here
            with jax.named_scope("loss"):
                loss, metrics = cosine_contrastive_loss(q, p, scale, neg,
                                                        chunk=loss_chunk)
            if moe_stats:
                metrics = dict(metrics, **moe_metrics(stats[MOE_STATS]))
            if gdn_stats:
                metrics = dict(metrics, **gdn_metrics(stats[GDN_STATS]))
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(params=params, opt_state=opt_state,
                          step=state.step + 1), metrics

    return train_step


class Trainer:
    """Wires config -> data -> model -> mesh -> compiled step (§4.1)."""

    def __init__(self, cfg: Config, corpus: Optional[ToyCorpus] = None,
                 hard_negative_lookup=None, workdir: Optional[str] = None,
                 tokenizers: Optional[Tuple[Any, Any]] = None):
        """`tokenizers=(query_tok, page_tok)` bypasses build_tokenizer —
        anything with .vocab_size and .encode_batch works. A caller uses it
        to drive true-vocab-size embedding tables with synthetic ids
        (training a 250k SentencePiece is data prep, not step cost)."""
        self.cfg = cfg
        self.workdir = workdir or cfg.workdir
        os.makedirs(self.workdir, exist_ok=True)
        self.corpus = corpus if corpus is not None else build_corpus(cfg)
        self.query_tok, self.page_tok = (
            tokenizers if tokenizers is not None
            else build_tokenizer(cfg, self.corpus, cache_dir=self.workdir))
        fitted = fit_mesh_to_devices(cfg.mesh)
        want = (cfg.mesh.data, cfg.mesh.model, cfg.mesh.seq)
        got = (fitted.data, fitted.model, fitted.seq)
        if want != got:
            if cfg.mesh.strict:
                raise RuntimeError(
                    f"mesh.strict: config wants {want} devices but only "
                    f"{len(jax.devices())} are visible")
            print(f"WARNING: mesh {want} shrunk to {got} for "
                  f"{len(jax.devices())} visible device(s); set "
                  "mesh.strict=true to fail instead", file=sys.stderr)
        self.mesh = make_mesh(fitted)
        self.model = build_two_tower(cfg, self.page_tok.vocab_size,
                                     mesh=self.mesh)
        self._moe = getattr(self.model.query_tower, "sows_moe_stats", False)
        self._gdn = getattr(self.model.query_tower, "sows_gdn_stats", False)
        self.tx = make_optimizer(cfg.train,
                                 no_decay=SELECT_BIAS if self._moe else None)
        self.hard_negative_lookup = hard_negative_lookup
        self._compiled = None
        self._compiled_multi = None

    # -- state ------------------------------------------------------------
    def init_state(self, seed: Optional[int] = None) -> TrainState:
        seed = self.cfg.train.seed if seed is None else seed
        rng = jax.random.PRNGKey(seed)
        d = self.cfg.data
        # dummy batch must divide over the 'data' axis (ring attention's
        # shard_map enforces divisibility even at init-trace time)
        b = max(2, self.mesh.shape["data"])
        dummy_q = jnp.zeros((b, d.query_len) + self._tok_extra(), jnp.int32)
        dummy_p = jnp.zeros((b, d.page_len) + self._tok_extra(), jnp.int32)
        params = self.model.init(rng, dummy_q, dummy_p)
        params = shard_params(params, self.mesh)
        # Moments (zeros_like) inherit param shardings, but optax also makes
        # fresh scalars (adam's count) that land committed on device 0; every
        # leaf must live on THIS mesh or jit rejects the mixed device sets.
        mesh_devs = frozenset(self.mesh.devices.flat)
        def _on_mesh(leaf):
            sh = getattr(leaf, "sharding", None)
            if sh is not None and frozenset(sh.device_set) == mesh_devs:
                return leaf
            return put_global(leaf, replicated(self.mesh))
        opt_state = jax.tree_util.tree_map(_on_mesh, self.tx.init(params))
        step = put_global(jnp.zeros((), jnp.int32), replicated(self.mesh))
        return TrainState(params=params, opt_state=opt_state, step=step)

    def _tok_extra(self) -> tuple:
        return ((self.cfg.data.trigrams_per_word,)
                if self.cfg.data.tokenizer == "trigram" else ())

    def base_rng(self) -> jax.Array:
        """Replicated base key for the per-step dropout fold_in, built with
        train.dropout_rng (default rbg — see config.py for the measured
        threefry cost this avoids). Typed keys can't pass through numpy, so
        the multi-process-safe placement goes via key_data/wrap_key_data."""
        key = jax.random.key(self.cfg.train.seed + 1,
                             impl=self.cfg.train.dropout_rng)
        data = put_global(jax.random.key_data(key), replicated(self.mesh))
        return jax.random.wrap_key_data(data, impl=self.cfg.train.dropout_rng)

    # -- compiled step ----------------------------------------------------
    def compiled_step(self, state: TrainState):
        if self._compiled is None:
            step_fn = make_train_step(self.model, self.tx,
                                      loss_chunk=self.cfg.train.loss_chunk,
                                      moe_stats=self._moe,
                                      gdn_stats=self._gdn)
            state_sh = jax.tree_util.tree_map(lambda x: x.sharding, state)
            self._compiled = jax.jit(
                step_fn,
                in_shardings=(state_sh, batch_sharding(self.mesh),
                              replicated(self.mesh)),
                out_shardings=(state_sh, replicated(self.mesh)),
                donate_argnums=(0,),
            )
        return self._compiled

    def _make_batcher(self, start_step: int,
                      profiler: Optional[PipelineProfiler] = None
                      ) -> TrainBatcher:
        pack = max(1, self.cfg.train.pack_pages)
        if pack > 1:
            if self.cfg.model.encoder not in ("bert", "t5"):
                raise ValueError(
                    "train.pack_pages needs a transformer page tower "
                    f"(bert/t5), not {self.cfg.model.encoder!r}: segment "
                    "masks only exist for attention encoders")
            rows = self.cfg.train.batch_size // pack
            if rows % self.mesh.shape["data"]:
                raise ValueError(
                    f"packed row batch {rows} (batch_size/pack_pages) must "
                    f"divide the mesh data axis {self.mesh.shape['data']}")
        return TrainBatcher(
            self.corpus, self.query_tok, self.page_tok,
            batch_size=self.cfg.train.batch_size, seed=self.cfg.train.seed,
            start_step=start_step,
            hard_negative_lookup=self.hard_negative_lookup,
            workers=self.cfg.data.tokenize_workers, profiler=profiler,
            pack=pack)

    def batches(self, start_step: int = 0,
                profiler: Optional[PipelineProfiler] = None) -> Iterator[Any]:
        return prefetch_to_device(
            iter(self._make_batcher(start_step, profiler=profiler)),
            sharding=batch_sharding(self.mesh), profiler=profiler)

    def stacked_batches(self, start_step: int = 0, k: int = 1,
                        profiler: Optional[PipelineProfiler] = None
                        ) -> Iterator[Any]:
        """[K, B, ...] stacks of K consecutive batches for the scan_steps
        fused dispatch; same data order as batches()."""
        batcher = self._make_batcher(start_step, profiler=profiler)

        def _stack(it):
            while True:
                group = [b for _, b in zip(range(k), it)]
                if len(group) < k:
                    return
                yield {key: np.stack([g[key] for g in group])
                       for key in group[0]}

        return prefetch_to_device(_stack(iter(batcher)),
                                  sharding=stacked_batch_sharding(self.mesh),
                                  profiler=profiler)

    def compiled_multi_step(self, state: TrainState):
        """Train-K-steps-in-one-dispatch: lax.scan over a [K, ...] batch
        stack, donated carry; K is the stack's leading dim (jit retraces per
        K, so one cached wrapper serves any stack size). Semantically
        identical to K calls of the single step (same rng folding: the step
        counter advances inside the scan); metrics returned are the LAST
        step's, matching what a per-step loop would log at the boundary."""
        if self._compiled_multi is None:
            step_fn = make_train_step(self.model, self.tx,
                                      loss_chunk=self.cfg.train.loss_chunk,
                                      moe_stats=self._moe,
                                      gdn_stats=self._gdn)

            def multi(state, stacked, base_rng):
                def body(st, batch):
                    return step_fn(st, batch, base_rng)
                state, ms = jax.lax.scan(body, state, stacked)
                return state, jax.tree_util.tree_map(lambda x: x[-1], ms)

            state_sh = jax.tree_util.tree_map(lambda x: x.sharding, state)
            self._compiled_multi = jax.jit(
                multi,
                in_shardings=(state_sh, stacked_batch_sharding(self.mesh),
                              replicated(self.mesh)),
                out_shardings=(state_sh, replicated(self.mesh)),
                donate_argnums=(0,),
            )
        return self._compiled_multi

    # -- driver -----------------------------------------------------------
    # graftcheck: hot
    def train(self, steps: Optional[int] = None,
              state: Optional[TrainState] = None,
              log: Optional[MetricsLogger] = None,
              ckpt_manager=None,
              profiler: Optional[PipelineProfiler] = None
              ) -> Tuple[TrainState, Dict[str, float]]:
        """Runs `steps` more steps. The data stream resumes at state.step, so
        a restored run sees the same batch order as an uninterrupted one.
        With ckpt_manager, saves (async) every cfg.train.checkpoint_every
        steps — the crash-recovery half of SURVEY.md §5.3.

        Pipeline observability: per-stage wall times (produce_wait / read /
        tokenize / h2d / compute dispatch) accumulate in `profiler` (one is
        created when omitted) and land in every logged metrics line as
        stage_*_s keys — a host-bound run shows up as produce_wait
        dominating, not as an unexplained low pages/sec."""
        cfg = self.cfg
        steps = cfg.train.steps if steps is None else steps
        state = self.init_state() if state is None else state
        scan_k = max(1, cfg.train.scan_steps)
        if scan_k > 1:
            # Fused-dispatch alignment is validated up front, BEFORE any
            # step runs and regardless of whether a ckpt_manager is passed
            # (ADVICE r3: a run launched without a manager used to hit the
            # checkpoint_every error only when it later resumed with one).
            # Deliberately NOT in __init__: inference commands construct a
            # Trainer for its model/tokenizers and must not fail on
            # train-only settings.
            for name, every in (("log_every", cfg.train.log_every),
                                ("checkpoint_every",
                                 cfg.train.checkpoint_every)):
                if every % scan_k:
                    raise ValueError(
                        f"train.{name}={every} must be a multiple of "
                        f"train.scan_steps={scan_k}: host-side events can "
                        "only fire at fused-dispatch boundaries")
            if steps % scan_k:
                raise ValueError(
                    f"steps={steps} must be a multiple of "
                    f"train.scan_steps={scan_k}")
            step_fn = self.compiled_multi_step(state)
        else:
            step_fn = self.compiled_step(state)
        base_rng = self.base_rng()
        # default logger mirrors every numeric scalar into the process
        # registry (docs/OBSERVABILITY.md) — jsonl shape unchanged
        log = log or MetricsLogger(self.workdir,
                                   registry=telemetry.default_registry())
        pages_per_step = cfg.train.batch_size
        n_dev = self.mesh.devices.size
        # MFU next to pages/sec/chip so every logged rate is interpretable
        # against hardware peak (analytic counts: utils/flops.py)
        from dnn_page_vectors_tpu.utils.flops import (
            device_peak_flops, train_flops_per_pair)
        peak = device_peak_flops(self.mesh.devices.flat[0])
        flops_pair = train_flops_per_pair(cfg, cfg.train.batch_size)
        # graftcheck: off=host-sync -- one-time sync before the loop
        start_step = int(state.step)
        prof = (PipelineProfiler(prefix="train.") if profiler is None
                else profiler)
        # train-loop throughput as registry instruments (docs/
        # OBSERVABILITY.md): a windowed steps counter gives live steps/sec
        # mid-run; the gauges mirror the numbers the metrics line reports
        _reg = telemetry.default_registry()
        _m_steps = _reg.counter("train.steps",
                                window_s=telemetry.DEFAULT_WINDOW_S)
        it = (self.stacked_batches(start_step=start_step, k=scan_k,
                                   profiler=prof)
              if scan_k > 1 else self.batches(start_step=start_step,
                                              profiler=prof))
        last: Dict[str, float] = {}
        # the logged rate covers the interval since the previous log line
        # (t_mark, i_mark); the first step holds the compile, so the clock
        # restarts behind its barrier and no logged rate includes it
        t_mark, i_mark = time.perf_counter(), 0
        for c in range(steps // scan_k):
            i = (c + 1) * scan_k         # steps completed this call
            # step boundaries in a `--profile` trace (for the operator:
            # xprof/Perfetto group device ops under each step)
            with jax.profiler.StepTraceAnnotation(
                    "train", step_num=start_step + i):
                batch = next(it)
                with prof.stage("compute"):   # dispatch; async past the first
                    state, metrics = step_fn(state, batch, base_rng)
            _m_steps.inc(scan_k)
            at_log = i % cfg.train.log_every == 0 or i == steps
            if c == 0 and not at_log:
                # graftcheck: off=host-sync -- once per call: the barrier
                # that keeps compilation out of every logged rate
                jax.block_until_ready(state.params)
                t_mark, i_mark = time.perf_counter(), i
            if at_log:
                metrics = {k: _logged(v) for k, v in metrics.items()}
                with prof.stage("sync"):
                    # graftcheck: off=host-sync -- log-cadence drain:
                    # fires every log_every steps, not per step
                    jax.block_until_ready(state.params)
                now = time.perf_counter()
                pps_chip = ((i - i_mark) * pages_per_step
                            / (now - t_mark) / n_dev)
                t_mark, i_mark = now, i
                metrics["pages_per_sec_per_chip"] = pps_chip
                _reg.gauge("train.pages_per_sec_per_chip").set(pps_chip)
                if peak:
                    metrics["mfu"] = pps_chip * flops_pair / peak
                    _reg.gauge("train.mfu").set(metrics["mfu"])
                # HBM headroom next to throughput, read from this host's
                # first mesh device (memory_stats() is None on the CPU
                # backend, which has no device memory to report)
                stats = self.mesh.local_devices[0].memory_stats()
                if stats is not None:
                    metrics["hbm_gb_in_use"] = round(
                        stats["bytes_in_use"] / 2**30, 3)
                # graftcheck: off=host-sync -- post-drain host value
                metrics["step"] = int(state.step)
                # per-stage pipeline breakdown next to the rate it explains
                metrics.update(prof.summary())
                # recovery-path activity (injected faults, I/O retries,
                # checkpoint rollbacks) surfaces in the same line — a run
                # that limped through failures must say so in its metrics
                fc = faults.counters()
                if fc:
                    metrics["fault_counters"] = fc
                log.write(metrics)
                last = metrics
            if (ckpt_manager is not None
                    and i % cfg.train.checkpoint_every == 0
                    and i < steps):      # final save is the caller's
                # graftcheck: off=host-sync -- checkpoint-cadence sync
                ckpt_manager.save(int(state.step), state)
        return state, last
