"""Benchmark: contrastive-training + bulk-embed throughput in pages/sec/chip
(the primary metric, BASELINE.json:2), with analytic-FLOPs MFU, run on
whatever accelerator the environment provides (the driver runs this on one
real TPU chip).

Robustness (VERDICT round 1 #1): backend init can be transiently UNAVAILABLE
or hang, which cost round 1 its only perf datapoint. This file is therefore
a thin jax-free wrapper that runs the actual bench in a worker subprocess
with a per-attempt timeout and retries with backoff while the backend is
down. On persistent failure it prints the error as one JSON line on stderr
and exits 1: no record is a record that says so.

Method (worker): flagship two-tower BERT-mini (config 3 geometry),
pre-tokenized batches resident on device (host tokenization is benched by
tests, not the device metric), jit-compiled train step with donated state;
warmup then timed steps; then a forward-only encode_page sweep (the 1B-page
bulk-embed workload, BASELINE.md:16). MFU comes from
dnn_page_vectors_tpu/utils/flops.py analytic counts over the device's peak
bf16 rate.

vs_baseline: BASELINE.json publishes no reference numbers ("published": {},
see BASELINE.md) — the ratio is computed against the most recent
BENCH_r*.json recorded by the driver, or 1.0 when none exists yet.
"""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import time

METRIC = "train_pages_per_sec_per_chip"
UNIT = "pages/sec/chip"
# Budget knobs (seconds); env-overridable so the driver can tighten them.
# The round-5 worker runs SEVEN optional sweeps after the required metrics
# (1M embed-from-text fp16 + int8, mt5, kim_cnn, lstm, long bert, long t5)
# whose cost is dominated by compiles plus the two timed 1M text sweeps (~60 s each); the default
# allows one full pass; the record-early protocol still bounds the damage
# of any overrun to the not-yet-printed optional fields.
ATTEMPT_TIMEOUT = int(os.environ.get("BENCH_ATTEMPT_TIMEOUT_S", "1500"))
TOTAL_BUDGET = int(os.environ.get("BENCH_TOTAL_BUDGET_S", "3200"))


def _previous_bench() -> float | None:
    best = None
    for path in glob.glob(os.path.join(os.path.dirname(__file__) or ".",
                                       "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
            val = rec.get("parsed", rec)["value"] if "parsed" in rec else rec["value"]
            cand = (int(m.group(1)), float(val))
        except Exception:
            continue
        if best is None or cand[0] > best[0]:
            best = cand
    return None if best is None else best[1]


def _previous_bench_record() -> dict | None:
    """Full record of the NEWEST BENCH_r*.json (highest round number) —
    the baseline the regression gate diffs EVERY shared numeric key
    against. `_previous_bench()` above stays the headline-value scan with
    its original candidacy rule (a record only counts if its `value`
    parses), so `vs_baseline` semantics are byte-stable."""
    best = None
    for path in glob.glob(os.path.join(os.path.dirname(__file__) or ".",
                                       "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
            rec = rec["parsed"] if "parsed" in rec else rec
            if not isinstance(rec, dict):
                continue
            cand = (int(m.group(1)), rec)
        except Exception:
            continue
        if best is None or cand[0] > best[0]:
            best = cand
    return None if best is None else best[1]


# Regression gate (docs/SERVING.md "SLO methodology"): keys where a LOWER
# value is better — latency, build/refresh cost, list imbalance, error
# rates — regress by RISING; everything else (throughput, recall, MFU,
# cache hit rate) regresses by dropping. Ratio-vs-previous keys and
# metadata are excluded: they re-derive from the gated keys anyway.
# compact_* contract values scale with the injected tombstone count (a
# protocol constant), not with performance — excluded like the p99 target.
# partitioned_* protocol constants (store geometry, the routing drill's
# fixed shed count) are excluded the same way; the phase's MEASURED keys
# gate with their suffixes: p99 (_ms) and scan bytes (_bytes) regress by
# rising, qps / scaling-efficiency keys by dropping, and "shed" joins the
# lower-is-better tokens so routing-health counts flag like latency.
_GATE_SKIP = {"vs_baseline", "attempts", "slo_p99_target_ms",
              "compact_bytes_reclaimed", "compact_dead_rows_dropped",
              "partitioned_store_rows", "partitioned_shards",
              "partitioned_dim", "partitioned_k", "partitioned_iters",
              "partitioned_shed_drill_sheds",
              "partitioned_shed_drill_degraded_serves",
              # net_serve protocol constants (store geometry, the SLO
              # target, drill worker counts, the detected core count,
              # and the raw-frame A/B reference arm — its size is fixed
              # by the frame layout, not by performance) — the phase's
              # MEASURED keys (net_qps_at_p99_p*, net_wire_bytes_per_query,
              # net_wire_compression_ratio, net_scaling_eff_p*,
              # net_hedge_fire_rate, net_deadline_shed_rate) all gate
              "net_store_rows", "net_shards", "net_dim", "net_k",
              "net_p99_target_ms", "net_workers", "net_cores",
              "net_wire_bytes_per_query_raw",
              # resize drill protocol constants (the hammer's fixed
              # request count and the drill's worker heartbeat) — the
              # MEASURED keys (resize_qps_dip_pct, resize_recovery_
              # seconds lower-is-better; resize_baseline_qps gates
              # higher-is-better) stay gated
              "resize_hammer_n", "resize_heartbeat_s", "net_front_ends",
              # cache_serve protocol constants (store geometry, the
              # workload's distinct-query count) and state gauges
              # (entry count tracks the workload, not performance) —
              # the phase's MEASURED keys (cache_serve_qps_at_p99_on/
              # _off, cache_serve_speedup, cache_hit_rate higher-is-
              # better; cache_serve_us_per_hit lower-is-better) all gate
              "cache_store_rows", "cache_dim", "cache_k",
              "cache_distinct", "cache_entries",
              # migration drill protocol constants (unit count tracks
              # store geometry, the stamp is a counter) — the MEASURED
              # keys (migrate_pages_per_s higher-is-better;
              # migration_sweep_seconds, migration_swap_ms,
              # serve_p99_during_migration_ms lower-is-better) all gate
              "migration_units", "post_migration_model_step",
              # filtered_serve protocol constants (store geometry, the
              # workload's distinct-query count) — the phase's MEASURED
              # keys (filtered_serve_qps_at_p99_*, filtered_recall_*,
              # filtered_ivf_recall_* higher-is-better; filtered_scan_
              # bytes_per_query_* and the s10 bytes ratio lower-is-
              # better via the "_bytes" token) all gate
              "filtered_store_rows", "filtered_dim", "filtered_k",
              "filtered_distinct"}
_LOWER_IS_BETTER = ("_ms", "seconds", "imbalance", "error", "_bytes",
                    "lint_", "shed", "hedge", "_us_per_", "dip")


def _lower_is_better(key: str) -> bool:
    return any(tok in key for tok in _LOWER_IS_BETTER)


def _regression_gate(rec: dict, prev: dict | None,
                     threshold: float = 0.05) -> tuple[dict, dict]:
    """Diff every shared TOP-LEVEL numeric key of `rec` against `prev`.
    Returns (deltas, regressions): deltas maps key -> new/prev ratio for
    every compared key; regressions keeps the direction-aware changes
    worse than `threshold` (>5% drop for higher-is-better keys, >5% rise
    for lower-is-better ones) with prev/new/ratio spelled out."""
    if not prev:
        return {}, {}
    deltas: dict = {}
    regs: dict = {}
    for key, new in rec.items():
        if key in _GATE_SKIP or isinstance(new, bool) \
                or not isinstance(new, (int, float)):
            continue
        old = prev.get(key)
        if isinstance(old, bool) or not isinstance(old, (int, float)) \
                or old == 0:
            continue
        ratio = float(new) / float(old)
        deltas[key] = round(ratio, 4)
        worse = (ratio > 1.0 + threshold if _lower_is_better(key)
                 else ratio < 1.0 - threshold)
        if worse:
            regs[key] = {"prev": old, "new": new, "ratio": round(ratio, 4)}
    return deltas, regs


def _print_delta_table(rec: dict, prev: dict | None) -> None:
    """Human-readable per-key delta table on stderr (the record carries
    the machine-readable `regressions` block)."""
    deltas, regs = _regression_gate(rec, prev)
    if not deltas:
        print("[bench] no prior BENCH_r*.json record to diff against",
              file=sys.stderr)
        return
    print(f"[bench] delta vs newest prior record "
          f"({len(deltas)} shared keys, {len(regs)} regressions):",
          file=sys.stderr)
    for key in sorted(deltas):
        mark = " REGRESSION" if key in regs else ""
        arrow = "\\/" if deltas[key] < 1.0 else ("/\\" if deltas[key] > 1.0
                                                 else "==")
        print(f"[bench]   {key:46s} {prev[key]:>14} -> "
              f"{rec[key]:>14}  x{deltas[key]:<8} {arrow}{mark}",
              file=sys.stderr)


# ---------------------------------------------------------------------------
# Worker: the actual measurement (runs in a subprocess).
# ---------------------------------------------------------------------------

def _stamp(msg: str) -> None:
    # Progress stamps on stderr: if an attempt times out, the wrapper's
    # captured stderr tail says exactly which stage hung (round-2 timeouts
    # were undiagnosable without this).
    print(f"[bench +{time.perf_counter() - _T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()
_PREV_RECORD: dict | None = None      # newest prior record, loaded lazily


def _emit(rec: dict) -> None:
    """Print a (possibly partial) worker record with the regression gate
    applied: `rec["regressions"]` is recomputed on every emit as keys
    accrue, so the LAST printed record — the one the wrapper parses —
    carries the full-key diff against the newest prior BENCH_r*.json."""
    global _PREV_RECORD
    if _PREV_RECORD is None:
        _PREV_RECORD = _previous_bench_record() or {}
    _, regs = _regression_gate(rec, _PREV_RECORD)
    rec["regressions"] = regs
    print(json.dumps(rec), flush=True)


class _SyntheticTok:
    """vocab-true random-id tokenizer (ids never 0 = pad) for perf phases
    where host vocab training is data-prep cost, not step cost (mt5's 250k
    SentencePiece ~115 s, kim_cnn/lstm's 100k word vocab over 1M pages);
    uniform ids make the embedding gather/scatter no cheaper than text."""

    def __init__(self, vocab_size, max_tokens, seed):
        import numpy as np
        self.vocab_size = vocab_size
        self.max_tokens = max_tokens
        self._rng = np.random.default_rng(seed)

    def encode_batch(self, texts):
        import numpy as np
        return self._rng.integers(
            1, self.vocab_size,
            size=(len(texts), self.max_tokens), dtype=np.int32)


def _roofline_keys(prefix: str, cfg, batch: int, pps: float, peak,
                   dev) -> dict:
    """<prefix>roofline_util + the binding wall next to every MFU column
    (docs/MFU.md "roofline methodology"): achieved pairs/sec over the
    analytic min(compute, memory) ceiling — the number that stays
    meaningful for gather-dominated encoders where bf16-peak MFU reads
    as 3% by construction."""
    from dnn_page_vectors_tpu.utils.flops import (
        device_peak_hbm_bps, roofline, train_bytes_per_pair,
        train_flops_per_pair)
    ceil, bound = roofline(train_flops_per_pair(cfg, batch),
                           train_bytes_per_pair(cfg, batch),
                           peak, device_peak_hbm_bps(dev))
    if ceil is None:
        return {}
    return {f"{prefix}roofline_ceiling_pps": round(ceil, 1),
            f"{prefix}roofline_util": round(pps / ceil, 4),
            f"{prefix}roofline_bound": bound}


def run_worker() -> None:
    from dnn_page_vectors_tpu.utils.platform import enable_compile_cache, hard_sync
    enable_compile_cache()
    import jax

    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.train.loop import Trainer
    from dnn_page_vectors_tpu.utils.flops import (
        device_peak_flops, embed_flops_per_page, train_flops_per_pair)

    _stamp("initializing backend")
    devs = jax.devices()
    n_dev = len(devs)
    peak = device_peak_flops(devs[0])
    _stamp(f"backend up: {n_dev}x {getattr(devs[0], 'device_kind', '?')}")

    # Scale knobs: defaults sized for one real TPU chip; the CPU smoke path
    # (tests, debugging) shrinks via env.
    # 1024/chip: embed throughput measured ~25% higher than at 256 (larger
    # dispatches amortize better) and train is flat; real bulk-embed jobs
    # run large batches anyway (eval.embed_batch_size default 512).
    per_chip = int(os.environ.get("BENCH_BATCH_PER_CHIP", "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "80"))
    embed_iters = int(os.environ.get("BENCH_EMBED_ITERS", "60"))
    # Fused steps per dispatch (train.scan_steps). Default 1: dispatch
    # pipelines with device compute, so fusing was measured to buy nothing
    # single-chip in round 4 (not measured on the current code); the knob
    # stays for experiments.
    scan_k = max(1, int(os.environ.get("BENCH_SCAN_STEPS", "1")))
    steps = max(scan_k, steps - steps % scan_k)   # never a 0-step timed loop
    # Report the best of REPS timed repetitions, the standard estimator for
    # "what the hardware can do" under external interference.
    reps = max(1, int(os.environ.get("BENCH_REPS", "3")))
    # optional sweeps (mt5, long bert/t5) are secondary datapoints: cap at
    # best-of-2 so they can't eat the attempt budget (primary keeps `reps`)
    opt_reps = min(reps, 2)
    batch = per_chip * n_dev
    # TRUE config-3 vocab (VERDICT r3 Missing #4): 100k toy pages supply
    # enough unique words to train the full 30,522-piece WordPiece (~13 s,
    # proven by tests/test_vocab_honesty.py), so the real embedding-table
    # gather/scatter-add is inside the measured step. The tokenizer is
    # cached under the workdir, so bench retries skip the training cost.
    cfg = get_config("bert_mini_v5p16", {
        "data.num_pages": max(100_000, batch),
        "data.query_len": 16,
        "data.page_len": 64,
        "train.batch_size": batch,
        "train.steps": steps,
        "train.log_every": 1_000_000,  # keep logging off the timed path
        "mesh.data": n_dev,
    })
    trainer = Trainer(cfg, workdir="/tmp/dnn_page_vectors_tpu_bench")
    _stamp("trainer built (tokenizer trained)")
    state = trainer.init_state()
    _stamp("state initialized")

    if scan_k > 1:
        step_fn = trainer.compiled_multi_step(state)
        it = iter(trainer.stacked_batches(k=scan_k))
    else:
        step_fn = trainer.compiled_step(state)
        it = iter(trainer.batches())
    batches = [next(it) for _ in range(2 if scan_k > 1 else 4)]
    base_rng = trainer.base_rng()
    _stamp(f"batches staged; compiling train step (scan_k={scan_k})")

    for i in range(2):  # warmup + compile
        state, metrics = step_fn(state, batches[i % len(batches)], base_rng)
    hard_sync(metrics)  # NOT block_until_ready: see utils/platform.hard_sync
    _stamp("train step compiled+warm; timing")

    def _best_time(loop, reps: int) -> float:
        """min over `reps` of: run `loop`, hard-sync its return value."""
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            hard_sync(loop())
            best = min(best, time.perf_counter() - t0)
        return best

    timed_steps = steps

    def _train_loop():
        nonlocal state
        for i in range(timed_steps // scan_k):
            state, metrics = step_fn(state, batches[i % len(batches)],
                                     base_rng)
        return metrics

    dt = _best_time(_train_loop, reps)
    train_pps_chip = batch * timed_steps / dt / n_dev
    train_flops = train_flops_per_pair(cfg, batch)
    train_mfu = (train_pps_chip * train_flops / peak) if peak else None
    _stamp(f"train timed: {train_pps_chip:.1f} pages/s/chip")

    # ---- fused-loss A/B (round 11, train.loss_chunk) --------------------
    # The chunked contrastive loss streams query chunks against the
    # GSPMD-gathered page pool instead of materializing [B, B] logits
    # (models/losses.py) — numerically pinned equal, so the A/B here is a
    # PERF datapoint: the fused step must hold the dense step's rate
    # while freeing the logits HBM that caps the in-batch negative pool.
    # Skippable via BENCH_FUSED=0.
    fused_chunk = int(os.environ.get("BENCH_LOSS_CHUNK", "256"))
    if os.environ.get("BENCH_FUSED", "1") != "0" and fused_chunk > 0 \
            and batch % fused_chunk == 0:
        try:
            import dataclasses as _dcf

            fcfg = cfg.replace(train=_dcf.replace(cfg.train,
                                                  loss_chunk=fused_chunk))
            ftrainer = Trainer(fcfg, corpus=trainer.corpus,
                               workdir="/tmp/dnn_page_vectors_tpu_bench")
            fstate = ftrainer.init_state()
            fstep = ftrainer.compiled_step(fstate)
            fit = iter(ftrainer.batches())
            fbatches = [next(fit) for _ in range(2)]
            frng = ftrainer.base_rng()
            for i in range(2):
                fstate, fm = fstep(fstate, fbatches[i % 2], frng)
            hard_sync(fm)
            _stamp(f"fused-loss step compiled (chunk={fused_chunk}); timing")
            fsteps = max(8, timed_steps // 2)

            def _fused_loop():
                nonlocal fstate
                for i in range(fsteps):
                    fstate, fm = fstep(fstate, fbatches[i % 2], frng)
                return fm

            fdt = _best_time(_fused_loop, opt_reps)
            f_pps = batch * fsteps / fdt / n_dev
            rec_fused = {
                "train_fused_loss_pages_per_sec_per_chip": round(f_pps, 2),
                "train_fused_loss_vs_dense": round(f_pps / train_pps_chip,
                                                   4),
                "train_loss_chunk": fused_chunk,
            }
            del fstate, fstep, fbatches
        except Exception as e:   # optional A/B must never cost the round
            rec_fused = {"fused_error": f"{type(e).__name__}: {e}"[:300]}
    else:
        rec_fused = {}
    _stamp("compiling embed")

    # ---- bulk-embed sweep (forward-only encode_page, device-resident) ----
    from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
    embedder = BulkEmbedder(cfg, trainer.model, state.params,
                            trainer.page_tok, trainer.mesh,
                            query_tok=trainer.query_tok)
    if scan_k > 1:
        page_stack = batches[0]["page"]          # [K, B, L] already stacked
        encode = embedder._encode_page_stack
        per_iter = batch * scan_k
        embed_iters = max(1, embed_iters // scan_k)
    else:
        # measure the PRODUCTION embed path: eval.embed_stack batches fused
        # per dispatch, exactly what embed_corpus runs (round 4 default 8)
        import numpy as _np

        from dnn_page_vectors_tpu.parallel.sharding import (
            stacked_batch_sharding)
        E = max(1, cfg.eval.embed_stack)
        # device-resident BEFORE timing: a numpy arg would re-pay the H2D
        # copy every timed iteration and understate the device metric
        page_stack = jax.device_put(
            _np.stack([_np.asarray(batches[i % len(batches)]["page"])
                       for i in range(E)]),
            stacked_batch_sharding(trainer.mesh))
        encode = embedder._encode_page_stack
        per_iter = batch * E
        embed_iters = max(1, embed_iters // E)
    out = encode(embedder.params, page_stack)
    hard_sync(out)

    def _embed_loop():
        for _ in range(embed_iters):
            out = encode(embedder.params, page_stack)
        return out

    dt_e = _best_time(_embed_loop, reps)
    embed_pps_chip = per_iter * embed_iters / dt_e / n_dev
    embed_flops = embed_flops_per_page(cfg)
    embed_mfu = (embed_pps_chip * embed_flops / peak) if peak else None

    prev = _previous_bench()
    vs = train_pps_chip / prev if prev else 1.0
    from dnn_page_vectors_tpu.utils import faults
    rec = {
        "metric": METRIC,
        "value": round(train_pps_chip, 2),
        "unit": UNIT,
        "vs_baseline": round(vs, 4),
        "train_mfu": round(train_mfu, 4) if train_mfu is not None else None,
        "embed_pages_per_sec_per_chip": round(embed_pps_chip, 2),
        "embed_mfu": round(embed_mfu, 4) if embed_mfu is not None else None,
        "train_flops_per_pair": train_flops,
        "embed_flops_per_page": embed_flops,
        "n_devices": n_dev,
        "device_kind": getattr(devs[0], "device_kind", "unknown"),
        "peak_bf16_flops": peak,
        **rec_fused,
        **_roofline_keys("train_", cfg, batch, train_pps_chip, peak,
                         devs[0]),
        # recovery-path activity during the bench (docs/ROBUSTNESS.md):
        # normally {} / False — a non-empty counter set in a bench record
        # means the run survived faults (retries, quarantines, rollbacks)
        # and the numbers were measured on a degraded pipeline
        "fault_counters": faults.counters(),
        "degraded": bool(faults.counters()),
    }
    # graftcheck counts ride the bench record (docs/ANALYSIS.md): every
    # "lint_" key is lower-is-better, so the regression gate flags
    # suppression growth — per family, so a new lock-order/lifecycle/
    # async/proto pragma flags exactly like a latency regression — and
    # analyzer wall time (lint_ms) regresses visibly too (the `cli lint
    # --changed` pre-commit loop depends on it staying fast). AST-only.
    try:
        from dnn_page_vectors_tpu.tools.analyze import RULES
        from dnn_page_vectors_tpu.tools.analyze import analyze as _lint
        _t_lint = time.time()
        _lint_report = _lint()
        rec["lint_ms"] = round((time.time() - _t_lint) * 1000.0, 1)
        rec["lint_findings"] = len(_lint_report.findings)
        rec["lint_suppressions"] = len(_lint_report.suppressed)
        rec["lint_baselined"] = len(_lint_report.baselined)
        _fam_of = {name: r.family for name, r in RULES.items()}
        for fam in sorted({r.family for r in RULES.values()}):
            fkey = fam.replace("-", "_")
            rec[f"lint_{fkey}_findings"] = sum(
                1 for f in _lint_report.findings
                if _fam_of.get(f.rule) == fam)
            rec[f"lint_{fkey}_suppressions"] = sum(
                1 for s in _lint_report.suppressed
                if _fam_of.get(s["rule"]) == fam)
    except Exception as e:   # the analyzer must never cost a bench round
        rec["lint_error"] = f"{type(e).__name__}: {e}"[:300]
    # The REQUIRED metrics are safe from this point: print them before the
    # optional sweeps, and again merged with their fields on success — the
    # wrapper parses the LAST record, and a sweep crash or per-attempt
    # timeout can no longer destroy the measured primary datapoint (the
    # timeout path recovers records from partial stdout).
    _emit(rec)

    on_tpu = getattr(devs[0], "platform", "") == "tpu"

    # ---- serve phase: QPS / latency of the query-serving layer -----------
    # The serving treatment (round 6, docs/SERVING.md): a store embedded
    # from this run's corpus is pre-staged in HBM, then N queries run (a)
    # strictly sequentially through search() — the pre-round-6 behavior,
    # one padded bucket per query — and (b) through the dynamic
    # micro-batcher at BENCH_SERVE_CONCURRENCY threads, where concurrent
    # callers coalesce into shared bucket-filling dispatches and repeat
    # queries hit the embedding cache. serve_qps / serve_p50_ms /
    # serve_p99_ms / serve_cache_hit_rate land in the record; the stage
    # breakdown (queue_wait/tokenize/encode/topk/merge/format) says where
    # serving time goes. Skippable via BENCH_SERVE=0; skipped off-TPU.
    if os.environ.get("BENCH_SERVE", "1") != "0" and on_tpu:
        try:
            import concurrent.futures
            import shutil

            from dnn_page_vectors_tpu.infer.serve import SearchService
            from dnn_page_vectors_tpu.infer.vector_store import VectorStore
            from dnn_page_vectors_tpu.utils.profiling import (
                LatencyStats, PipelineProfiler)

            shard_rows = 16_384
            n_store = int(os.environ.get("BENCH_SERVE_PAGES",
                                         str(4 * shard_rows)))
            conc = int(os.environ.get("BENCH_SERVE_CONCURRENCY", "32"))
            n_q = int(os.environ.get("BENCH_SERVE_QUERIES", "512"))
            distinct = int(os.environ.get("BENCH_SERVE_DISTINCT", "64"))
            sdir = "/tmp/dnn_page_vectors_tpu_bench/serve_store"
            shutil.rmtree(sdir, ignore_errors=True)
            sstore = VectorStore(sdir, dim=cfg.model.out_dim,
                                 shard_size=shard_rows)
            _stamp(f"serve phase: embedding {n_store}-page store "
                   f"({n_store // shard_rows} shards)")
            embedder.embed_corpus(trainer.corpus, sstore, stop=n_store)
            sprof = PipelineProfiler()
            svc = SearchService(cfg, embedder, trainer.corpus, sstore,
                                preload_hbm_gb=4.0, profiler=sprof)
            kq = 10
            svc.warmup(k=kq)
            qtexts = [trainer.corpus.query_text(i) for i in range(distinct)]
            _stamp(f"serve warm ({svc.warm_latency_ms:.1f} ms median); "
                   f"timing {conc} sequential then {n_q}@{conc} batched")
            svc.clear_cache()
            t0 = time.perf_counter()
            for i in range(conc):
                svc.search(qtexts[i % distinct], k=kq)
            seq_qps = conc / (time.perf_counter() - t0)
            svc.clear_cache()
            sprof.reset()
            lat = LatencyStats()
            svc.start_batcher()

            def _one(i):
                with lat.timed():
                    return svc.search(qtexts[i % distinct], k=kq)

            # burst 1 (sequential, above) vs burst 2 (batched): the
            # windowed registry gauges move between the two — proof the
            # live SLO view (docs/OBSERVABILITY.md) tracks traffic, while
            # the wall-clock serve_qps/serve_p99_ms keys stay authoritative
            win_after_seq = svc.metrics()["serve_window_qps"]
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(conc) as ex:
                list(ex.map(_one, range(n_q)))
            dt = time.perf_counter() - t0
            svc.close()
            smet = svc.metrics()
            rec.update({
                "serve_qps": round(n_q / dt, 2),
                "serve_seq_qps": round(seq_qps, 2),
                "serve_speedup_vs_sequential": round(n_q / dt / seq_qps, 2),
                "serve_p50_ms": round(lat.percentile_ms(50), 3),
                "serve_p99_ms": round(lat.percentile_ms(99), 3),
                "serve_cache_hit_rate": smet["serve_cache_hit_rate"],
                "serve_warm_latency_ms": round(svc.warm_latency_ms, 3),
                "serve_concurrency": conc,
                "serve_queries": n_q,
                "serve_distinct_queries": distinct,
                "serve_store_vectors": sstore.num_vectors,
                "serve_mean_batch": smet.get("serve_mean_batch"),
                # the registry's live windowed view (docs/OBSERVABILITY.md)
                # — read from the SAME instruments tests and serve-metrics
                # exposition read, not recomputed here
                "serve_window_s": smet["serve_window_s"],
                "serve_window_qps": smet["serve_window_qps"],
                "serve_window_qps_after_seq_burst": round(win_after_seq, 3),
                "serve_window_p50_ms": smet["serve_window_p50_ms"],
                "serve_window_p99_ms": smet["serve_window_p99_ms"],
                "serve_window_cache_hit_rate":
                    smet["serve_window_cache_hit_rate"],
                "serve_stage_seconds": {
                    key: round(val, 3)
                    for key, val in sorted(sprof.stages().items())},
            })

            # ---- ann sub-phase: IVF index over the same >=50k store ----
            # Build the inverted file (TPU k-means), measure index quality
            # (recall@10 of the exact top-10 at the default nprobe) and
            # ANN serving QPS under the IDENTICAL protocol as serve_qps
            # (same store, queries, concurrency, batcher, cache) — so
            # ann_qps / serve_qps isolates the retrieval algorithm.
            # Skippable via BENCH_ANN=0.
            if os.environ.get("BENCH_ANN", "1") != "0":
                try:
                    import dataclasses as _dc

                    import numpy as _np3

                    from dnn_page_vectors_tpu.evals.recall import (
                        recall_vs_exact)
                    from dnn_page_vectors_tpu.index.ivf import IVFIndex
                    _stamp(f"ann phase: building IVF index over "
                           f"{sstore.num_vectors} vectors")
                    t0 = time.perf_counter()
                    aidx = IVFIndex.build(sstore, embedder.mesh,
                                          nlist=cfg.serve.nlist,
                                          iters=cfg.serve.kmeans_iters,
                                          seed=0)
                    build_s = time.perf_counter() - t0
                    qv = _np3.asarray(
                        embedder.embed_texts(qtexts, tower="query"),
                        _np3.float32)
                    r10 = recall_vs_exact(aidx, sstore, qv, embedder.mesh,
                                          k=10, nprobe=cfg.serve.nprobe)
                    _stamp(f"ann index built ({build_s:.1f}s, nlist="
                           f"{aidx.nlist}); recall@10 vs exact {r10:.3f}; "
                           f"timing {n_q}@{conc} batched")
                    acfg = cfg.replace(serve=_dc.replace(cfg.serve,
                                                         index="ivf"))
                    asvc = SearchService(acfg, embedder, trainer.corpus,
                                         sstore, preload_hbm_gb=0.0)
                    asvc.warmup(k=kq)
                    asvc.clear_cache()
                    asvc.start_batcher()
                    gb0 = asvc.ann_gather_bytes
                    t0 = time.perf_counter()
                    with concurrent.futures.ThreadPoolExecutor(conc) as ex:
                        list(ex.map(
                            lambda i: asvc.search(qtexts[i % distinct],
                                                  k=kq), range(n_q)))
                    adt = time.perf_counter() - t0
                    ann_bytes = asvc.ann_gather_bytes - gb0
                    asvc.close()
                    amet = asvc.metrics()
                    rec.update({
                        "ann_recall_at_10": round(r10, 4),
                        "ann_qps": round(n_q / adt, 2),
                        "ann_build_seconds": round(build_s, 3),
                        "ann_nlist": aidx.nlist,
                        "ann_nprobe": cfg.serve.nprobe,
                        "ann_imbalance": aidx.imbalance,
                        "ann_fallbacks": amet.get("ann_fallbacks", 0),
                        "ann_lists_scanned": amet.get(
                            "ann_lists_scanned", 0),
                        "ann_candidates_reranked": amet.get(
                            "ann_candidates_reranked", 0),
                        # measured candidate-payload traffic (docs/ANN.md):
                        # bytes the posting gather moved over the host
                        # path, per query and per second — the 4x claim
                        # is a measurement, not an assertion
                        "ann_gather_bytes_per_query": round(
                            ann_bytes / max(n_q, 1), 1),
                        "ann_gather_mbytes_per_s": round(
                            ann_bytes / max(adt, 1e-9) / 1e6, 2),
                        "ann_vs_exact_qps": round(
                            (n_q / adt) / max(rec.get("serve_qps") or 1e-9,
                                              1e-9), 3),
                    })

                    # ---- pq sub-phase: OPQ+PQ codes + on-device ADC ----
                    # Same store / queries / concurrency / batcher
                    # protocol as the ann phase, with compressed posting
                    # payloads and the HBM-resident hot posting set: the
                    # qps and bytes/query deltas vs the r05-style ann
                    # numbers above isolate the payload treatment.
                    # Skippable via BENCH_PQ=0.
                    try:
                      if os.environ.get("BENCH_PQ", "1") != "0":
                        from dnn_page_vectors_tpu.index.pq import auto_pq_m
                        _stamp(f"pq phase: OPQ+PQ build (m="
                               f"{auto_pq_m(sstore.dim)}) over "
                               f"{sstore.num_vectors} vectors")
                        t0 = time.perf_counter()
                        pidx = IVFIndex.build(
                            sstore, embedder.mesh, nlist=cfg.serve.nlist,
                            iters=cfg.serve.kmeans_iters, seed=0,
                            pq_m=cfg.serve.pq_m or auto_pq_m(sstore.dim),
                            pq_iters=cfg.serve.pq_iters,
                            opq_iters=cfg.serve.pq_opq_iters)
                        pq_build_s = time.perf_counter() - t0
                        r10p = recall_vs_exact(pidx, sstore, qv,
                                               embedder.mesh, k=10,
                                               nprobe=cfg.serve.nprobe)
                        pcfg = cfg.replace(serve=_dc.replace(
                            cfg.serve, index="ivf", hot_postings_gb=2.0))
                        psvc = SearchService(pcfg, embedder,
                                             trainer.corpus, sstore,
                                             preload_hbm_gb=0.0)
                        psvc.warmup(k=kq)
                        psvc.clear_cache()
                        psvc.start_batcher()
                        gb0 = psvc.ann_gather_bytes
                        t0 = time.perf_counter()
                        with concurrent.futures.ThreadPoolExecutor(
                                conc) as ex:
                            list(ex.map(
                                lambda i: psvc.search(
                                    qtexts[i % distinct], k=kq),
                                range(n_q)))
                        pdt = time.perf_counter() - t0
                        pq_bytes = psvc.ann_gather_bytes - gb0
                        psvc.close()
                        pmet = psvc.metrics()
                        bpq = pq_bytes / max(n_q, 1)
                        rec.update({
                            "ann_pq_recall_at_10": round(r10p, 4),
                            "ann_pq_qps": round(n_q / pdt, 2),
                            "ann_pq_m": pidx.pq_m,
                            "codebook_build_seconds":
                                (pidx.manifest.get("pq") or {}).get(
                                    "train_seconds"),
                            "ann_pq_build_seconds": round(pq_build_s, 3),
                            "ann_pq_gather_bytes_per_query": round(bpq, 1),
                            "ann_pq_gather_mbytes_per_s": round(
                                pq_bytes / max(pdt, 1e-9) / 1e6, 2),
                            "ann_pq_payload_reduction": round(
                                (ann_bytes / max(n_q, 1)) / max(bpq, 1e-9),
                                2),
                            "ann_pq_hot_rows": pmet.get(
                                "ann_index", {}).get("hot_rows", 0),
                            "ann_pq_fallbacks": pmet.get(
                                "ann_fallbacks", 0),
                            "ann_pq_vs_ann_qps": round(
                                (n_q / pdt) / max(n_q / adt, 1e-9), 3),
                        })
                        _stamp(
                            f"pq phase done: recall@10 {r10p:.3f}, "
                            f"{n_q / pdt:.0f} qps "
                            f"({rec['ann_pq_payload_reduction']}x fewer "
                            "payload bytes/query)")
                    except Exception as e:  # keep serve + ann + update data
                        rec["pq_error"] = f"{type(e).__name__}: {e}"[:300]

                    # ---- update sub-phase: live append + hot-swap ----
                    # The live-update treatment (docs/UPDATES.md): append
                    # one shard of new pages to the serve store as a
                    # generation, refresh() a live ANN service (incremental
                    # index update + atomic view swap), and measure the
                    # operator-facing numbers — append throughput, index
                    # update cost (O(new shards)), the swap's downtime
                    # window, and post-append ANN recall on the NEW pages.
                    # Skippable via BENCH_UPDATE=0.
                    if os.environ.get("BENCH_UPDATE", "1") != "0":
                        try:
                            from dnn_page_vectors_tpu.updates import (
                                append_corpus)
                            n_app = int(os.environ.get(
                                "BENCH_UPDATE_PAGES", str(shard_rows)))
                            base_n = sstore.num_vectors
                            _stamp(f"update phase: appending {n_app} pages "
                                   f"to the {base_n}-page serve store")
                            usvc = SearchService(acfg, embedder,
                                                 trainer.corpus, sstore,
                                                 preload_hbm_gb=4.0)
                            usvc.warmup(k=kq)
                            astats = append_corpus(
                                embedder, trainer.corpus, sstore,
                                stop=base_n + n_app, tombstone=[0])
                            t0 = time.perf_counter()
                            rinfo = usvc.refresh()
                            uq = [trainer.corpus.query_text(base_n + i)
                                  for i in range(min(distinct, n_app))]
                            uqv = _np3.asarray(
                                embedder.embed_texts(uq, tower="query"),
                                _np3.float32)
                            r10u = (recall_vs_exact(
                                usvc._index, sstore, uqv, embedder.mesh,
                                k=10, nprobe=cfg.serve.nprobe)
                                if usvc._index is not None else None)
                            usvc.close()
                            iupd = rinfo.get("index_update") or {}
                            rec.update({
                                "append_pages": n_app,
                                "append_docs_per_s":
                                    astats["append_docs_per_s"],
                                "index_update_seconds": iupd.get("seconds"),
                                "index_update_action": iupd.get("action"),
                                "refresh_seconds":
                                    rinfo["refresh_seconds"],
                                "refresh_swap_ms": rinfo["swap_ms"],
                                "post_append_recall_at_10":
                                    (round(r10u, 4) if r10u is not None
                                     else None),
                                "store_generation":
                                    rinfo["store_generation"],
                            })
                            _stamp(
                                f"update phase done: append "
                                f"{astats['append_docs_per_s']:.0f} docs/s, "
                                f"index {iupd.get('action')} in "
                                f"{iupd.get('seconds')}s, swap "
                                f"{rinfo['swap_ms']:.1f} ms")
                        except Exception as e:  # keep serve + ann data
                            rec["update_error"] = \
                                f"{type(e).__name__}: {e}"[:300]

                    # ---- maintenance sub-phase: compaction + bg rebuild
                    # under load (docs/MAINTENANCE.md): tombstone a slice
                    # of the serve store past a lowered compaction
                    # threshold, then run ONE maintenance pass (janitor →
                    # compaction → background index rebuild, every swap
                    # hot-swapped into the live service) while 4 query
                    # threads hammer it — the measured numbers are the
                    # operator-facing ones: compaction throughput, bytes
                    # reclaimed, the bg rebuild's swap window, and serve
                    # p99 WHILE maintenance ran. BENCH_MAINTENANCE=0 skips.
                    if os.environ.get("BENCH_MAINTENANCE", "1") != "0":
                        try:
                            import threading as _threading

                            from dnn_page_vectors_tpu.updates import (
                                append_corpus as _append)
                            _stamp("maintenance phase: tombstone burst + "
                                   "compaction + bg rebuild under load")
                            mcfg = acfg.replace(maintenance=_dc.replace(
                                acfg.maintenance,
                                compact_tombstone_density=0.02))
                            msvc = SearchService(mcfg, embedder,
                                                 trainer.corpus, sstore,
                                                 preload_hbm_gb=4.0)
                            msvc.warmup(k=kq)
                            msvc.start_batcher()
                            maint = msvc.start_maintenance(threads=False)
                            n_dead = max(64,
                                         int(0.03 * sstore.num_vectors))
                            _append(embedder, trainer.corpus, sstore,
                                    tombstone=list(range(1, 1 + n_dead)))
                            msvc.refresh()
                            mlat = LatencyStats()
                            mstop = _threading.Event()

                            def _hammer(wid):
                                i = wid
                                while not mstop.is_set():
                                    with mlat.timed():
                                        msvc.search(qtexts[i % distinct],
                                                    k=kq)
                                    i += 1

                            hthreads = [
                                _threading.Thread(target=_hammer,
                                                  args=(w,), daemon=True)
                                for w in range(4)]
                            for t in hthreads:
                                t.start()
                            mt0 = time.perf_counter()
                            mout = maint.run_once()
                            m_dt = time.perf_counter() - mt0
                            mstop.set()
                            for t in hthreads:
                                t.join()
                            comp = mout.get("compaction") or {}
                            rb = (comp.get("index_rebuild")
                                  or mout.get("rebuild") or {})
                            mmet = msvc.metrics()
                            msvc.close()
                            rec.update({
                                "compact_docs_per_s":
                                    comp.get("compact_docs_per_s"),
                                "compact_bytes_reclaimed":
                                    comp.get("bytes_reclaimed"),
                                "compact_dead_rows_dropped":
                                    comp.get("dead_rows_dropped"),
                                "bg_rebuild_swap_ms": rb.get("swap_ms"),
                                "bg_rebuild_seconds":
                                    rb.get("build_seconds"),
                                "serve_p99_during_compaction_ms": round(
                                    mlat.percentile_ms(99), 3),
                                "maintenance_pass_seconds": round(m_dt, 3),
                                "maintenance_full_rebuilds":
                                    mmet["full_rebuilds"],
                            })
                            _stamp(
                                f"maintenance phase done: compacted "
                                f"{comp.get('rows')} rows "
                                f"({comp.get('bytes_reclaimed')} B "
                                f"reclaimed), bg swap "
                                f"{rb.get('swap_ms')} ms, p99 under "
                                f"maintenance "
                                f"{mlat.percentile_ms(99):.1f} ms")
                        except Exception as e:  # keep serve + ann data
                            rec["maintenance_error"] = \
                                f"{type(e).__name__}: {e}"[:300]

                    # ---- migration sub-phase: rolling re-embed under
                    # load (docs/MAINTENANCE.md "Rolling model
                    # migration"): the migrate pillar sweeps the live
                    # serve store to a new model stamp unit-by-unit —
                    # every flip hot-swapped into the service, queries
                    # running dual-stamp mid-sweep — while 4 query
                    # threads hammer it. Measured: re-embed throughput,
                    # the sweep's wall clock, and serve p99 WHILE the
                    # store flipped stamps. The target params are the
                    # same trained tower (the drill prices the sweep
                    # machinery, not a second training run), so results
                    # stay comparable across rounds. BENCH_MIGRATE=0
                    # skips.
                    if os.environ.get("BENCH_MIGRATE", "1") != "0":
                        try:
                            import threading as _threading
                            _stamp("migration phase: rolling re-embed "
                                   "under query load")
                            # fresh handle: the compaction sub-phase may
                            # have purged files sstore still references
                            gstore = VectorStore(sstore.directory)
                            gsvc = SearchService(acfg, embedder,
                                                 trainer.corpus, gstore,
                                                 preload_hbm_gb=4.0)
                            gsvc.warmup(k=kq)
                            gmaint = gsvc.start_maintenance(threads=False)
                            g_to = int(gstore.model_step) + 1
                            gmaint.request_migration(g_to, trainer.corpus,
                                                     embedder)
                            glat = LatencyStats()
                            gstop = _threading.Event()

                            def _ghammer(wid):
                                i = wid
                                while not gstop.is_set():
                                    with glat.timed():
                                        gsvc.search(qtexts[i % distinct],
                                                    k=kq)
                                    i += 1

                            gthreads = [
                                _threading.Thread(target=_ghammer,
                                                  args=(w,), daemon=True)
                                for w in range(4)]
                            for t in gthreads:
                                t.start()
                            gt0 = time.perf_counter()
                            g_units, g_rows, g_swaps = 0, 0, []
                            while True:
                                gout = gmaint.run_once().get("migrate")
                                if gout is None:
                                    break
                                if gout.get("refresh_swap_ms") is not None:
                                    g_swaps.append(gout["refresh_swap_ms"])
                                if gout.get("action") == "migrating":
                                    g_units += len(gout.get("units") or [])
                                    g_rows += int(gout.get("rows", 0))
                                else:
                                    break
                            g_dt = time.perf_counter() - gt0
                            gstop.set()
                            for t in gthreads:
                                t.join()
                            gsvc.close()
                            rec.update({
                                "migration_units": g_units,
                                "migrate_pages_per_s": round(
                                    g_rows / max(g_dt, 1e-9), 2),
                                "migration_sweep_seconds": round(g_dt, 3),
                                "migration_swap_ms": (round(
                                    max(g_swaps), 3) if g_swaps else None),
                                "serve_p99_during_migration_ms": round(
                                    glat.percentile_ms(99), 3),
                                "post_migration_model_step":
                                    VectorStore(sstore.directory,
                                                verify=False).model_step,
                            })
                            _stamp(
                                f"migration phase done: {g_units} units "
                                f"({g_rows} rows) in {g_dt:.1f}s, p99 "
                                f"under migration "
                                f"{glat.percentile_ms(99):.1f} ms")
                        except Exception as e:  # keep serve + ann data
                            rec["migration_error"] = \
                                f"{type(e).__name__}: {e}"[:300]
                except Exception as e:  # ann failure must keep serve data
                    rec["ann_error"] = f"{type(e).__name__}: {e}"[:300]

            # ---- slo phase: measured "qps @ p99 < X ms" ----------------
            # The production metric the serve_qps keys above proxy
            # (docs/SERVING.md "SLO methodology"): a seeded open-loop
            # Poisson workload over the same store/queries, the loadgen
            # driver binary-searching offered load for the max sustained
            # QPS whose windowed p99 — read from the telemetry registry,
            # not re-derived — stays under the target. Adaptive batching
            # is ON for this phase (it exists for exactly this traffic);
            # every number regression-gates against the prior round via
            # the `regressions` block. Skippable via BENCH_SLO=0.
            if os.environ.get("BENCH_SLO", "1") != "0":
                try:
                    import dataclasses as _dcs

                    from dnn_page_vectors_tpu.loadgen import (
                        find_qps_at_p99, make_workload)
                    slo_p99 = float(os.environ.get("BENCH_SLO_P99_MS",
                                                   "250"))
                    slo_trial = float(os.environ.get("BENCH_SLO_TRIAL_S",
                                                     "6"))
                    slo_cfg = cfg.replace(
                        serve=_dcs.replace(cfg.serve,
                                           batch_window_adaptive=True),
                        obs=_dcs.replace(cfg.obs, window_s=slo_trial))
                    ssvc = SearchService(slo_cfg, embedder, trainer.corpus,
                                         sstore, preload_hbm_gb=4.0)
                    ssvc.warmup(k=kq)
                    ssvc.start_batcher()
                    wl = make_workload("poisson", seed=0, distinct=distinct,
                                       profile=((kq, None, 1.0),))
                    _stamp(f"slo phase: searching qps @ p99<{slo_p99:.0f}ms"
                           f" ({slo_trial:.0f}s trials, poisson)")
                    srep = find_qps_at_p99(
                        ssvc, wl, qtexts, p99_target_ms=slo_p99,
                        start=float(os.environ.get("BENCH_SLO_START_QPS",
                                                   "16")),
                        iters=int(os.environ.get("BENCH_SLO_ITERS", "3")),
                        duration_s=slo_trial, warmup_s=1.0,
                        progress=_stamp, progress_every_s=slo_trial)
                    ssvc.close()
                    rec.update({
                        "slo_qps_at_p99": srep["qps_at_p99"],
                        "slo_p99_target_ms": srep["p99_target_ms"],
                        "slo_shape": srep["shape"],
                        "slo_trials": [
                            {key: t[key] for key in (
                                "offered_qps", "achieved_qps", "p50_ms",
                                "p99_ms", "error_rate", "cache_hit_rate",
                                "met")} for t in srep["trials"]],
                        "slo_recompiles": ssvc.recompiles,
                        "slo_batch_window_ms": round(
                            ssvc.batch_window_ms, 3),
                        "slo_window_adapts": sum(
                            1 for e in srep["events"]
                            if e["event"] == "window_adapt"),
                    })
                    _stamp(f"slo phase done: {srep['qps_at_p99']:.0f} qps @"
                           f" p99<{slo_p99:.0f}ms over "
                           f"{len(srep['trials'])} trials")
                except Exception as e:  # keep serve + ann + update data
                    rec["slo_error"] = f"{type(e).__name__}: {e}"[:300]
        except Exception as e:  # optional phase must never cost the round
            rec["serve_error"] = f"{type(e).__name__}: {e}"[:300]
        _emit(rec)

    # ---- embed-FROM-TEXT phase (VERDICT r4 Missing #1 / next-round #1) ---
    # The device-resident number above deliberately isolates chip compute;
    # THIS phase measures the production job: a 1M-page jsonl corpus on
    # disk -> per-batch reads (JsonlCorpus fast-extract) -> C++ WordPiece
    # tokenize (data.tokenize_threads) -> prefetch/device -> fp16 store,
    # wall-clock end to end, store writes included. Corpus and trained
    # tokenizer are cached on disk so retries/rounds skip the one-time
    # ~45 s setup. Skippable via BENCH_EMBED_TEXT=0; skipped off-TPU.
    if os.environ.get("BENCH_EMBED_TEXT", "1") != "0" and on_tpu:
        try:
            import shutil

            from dnn_page_vectors_tpu.data.synth import write_synth_jsonl
            from dnn_page_vectors_tpu.infer.vector_store import VectorStore

            n_text = int(os.environ.get("BENCH_TEXT_PAGES", "1000000"))
            tdir = "/tmp/dnn_page_vectors_tpu_bench_text"
            os.makedirs(tdir, exist_ok=True)
            jpath = os.path.join(tdir, f"synth_{n_text}.jsonl")
            if not os.path.exists(jpath):
                _stamp(f"generating {n_text}-page jsonl corpus (one-time)")
                write_synth_jsonl(jpath, n_text, seed=7, page_len=48,
                                  query_len=16)
            ecfg = get_config("bert_mini_v5p16", {
                "data.corpus": f"jsonl:{jpath}",
                "data.num_pages": n_text,
                "data.query_len": 16,
                "data.page_len": 64,
                "data.tokenize_threads": int(
                    os.environ.get("BENCH_TOKENIZE_THREADS", "8")),
                # parallel host producer (round 6): N tokenizer workers
                # read+tokenize batch ranges concurrently and the store
                # writeback overlaps device compute — the serial producer
                # held embed-from-text to 57% of the transport ceiling
                # (BENCH_r05) while the device sat idle between batches
                "data.tokenize_workers": int(
                    os.environ.get("BENCH_TOKENIZE_WORKERS", "6")),
                # 32 batches per dispatch (vs the default 8): fewer, bigger
                # D2H pulls where each result materialization is costly;
                # hosts with the chip on local PCIe are insensitive to
                # this knob beyond the default
                "eval.embed_stack": int(
                    os.environ.get("BENCH_EMBED_STACK", "32")),
                "train.batch_size": batch,
                "train.log_every": 1_000_000,
                "mesh.data": n_dev,
            })
            etrainer = Trainer(ecfg, workdir=tdir)  # wordpiece cached here
            _stamp("text-phase trainer built (tokenizer trained/cached)")
            eembedder = BulkEmbedder(
                ecfg, etrainer.model, etrainer.init_state().params,
                etrainer.page_tok, etrainer.mesh,
                query_tok=etrainer.query_tok)
            sdir = os.path.join(tdir, "store")
            from dnn_page_vectors_tpu.utils.profiling import PipelineProfiler
            eprof = PipelineProfiler()

            def _sweep():
                eprof.reset()   # summary reported below = the LAST rep's
                shutil.rmtree(sdir, ignore_errors=True)
                store = VectorStore(sdir, dim=ecfg.model.out_dim,
                                    shard_size=ecfg.eval.store_shard_size)
                eembedder.embed_corpus(etrainer.corpus, store,
                                       profiler=eprof)
                assert store.num_vectors == n_text, store.num_vectors
                # already host-complete (every vector was materialized into
                # the store); give _best_time's hard_sync a device no-op
                import jax.numpy as jnp
                return jnp.zeros(())

            _stamp("warming text-embed (compile + first shard)")
            shutil.rmtree(sdir, ignore_errors=True)
            warm = VectorStore(sdir, dim=ecfg.model.out_dim,
                               shard_size=ecfg.eval.store_shard_size)
            eembedder.embed_corpus(etrainer.corpus, warm,
                                   stop=ecfg.eval.store_shard_size)
            # Raw device->host bandwidth: the embed job's entire output IS
            # D2H traffic (2 B/dim/page after the on-device fp16 cast), so
            # this sets a transport-imposed ceiling on the from-text rate.
            # The ratio of achieved rate to THIS ceiling — not to the compute
            # rate — is the honest pipeline-efficiency number here
            # (docs/SCALING.md "host budget").
            import jax.numpy as _jnp
            import numpy as _np2
            big = _jnp.zeros((32 * 1024 * 1024 // 2,), _jnp.float16) + 1
            _np2.asarray(big)                       # warm the path
            t0 = time.perf_counter()
            _np2.asarray(big * 2)
            d2h_bps = big.nbytes / (time.perf_counter() - t0)
            ceiling = d2h_bps / (ecfg.model.out_dim * 2)
            _stamp(f"D2H {d2h_bps / 1e6:.0f} MB/s -> transport ceiling "
                   f"{ceiling:,.0f} pages/s; timing full 1M sweep")
            tdt = _best_time(_sweep, opt_reps)
            etext_pps = n_text / tdt / n_dev
            # MEASURED drain rate of the job's own packed d2h transfers
            # (bytes and seconds from the PipelineProfiler, round 11) —
            # the probe-based number keeps setting the transport CEILING,
            # but the recorded embed_d2h_mbytes_per_sec is now what the
            # sweep actually achieved, one packed device_get per dispatch
            eprof_s = eprof.stages().get("d2h", 0.0)
            d2h_measured = (eprof.stage_bytes().get("d2h", 0) / eprof_s
                            / 1e6 if eprof_s > 0 else 0.0)
            rec.update({
                "embed_from_text_pages_per_sec_per_chip": round(etext_pps, 2),
                "embed_from_text_pages": n_text,
                "embed_from_text_vs_device": round(
                    etext_pps / embed_pps_chip, 4),
                "embed_d2h_mbytes_per_sec": round(d2h_measured, 1),
                "embed_d2h_probe_mbytes_per_sec": round(d2h_bps / 1e6, 1),
                "embed_from_text_transport_ceiling_pps": round(ceiling, 1),
                "embed_from_text_vs_transport_ceiling": round(
                    min(etext_pps / ceiling, 9.99), 4),
                "embed_tokenize_threads": ecfg.data.tokenize_threads,
                "embed_tokenize_workers": ecfg.data.tokenize_workers,
                # which stage binds (PipelineProfiler; LAST rep's sweep —
                # read/tokenize are cumulative over the worker pool, so
                # compare ratios, and produce_wait against wall clock)
                "embed_stage_seconds": {
                    k: round(v, 2) for k, v in sorted(
                        eprof.stages().items())},
            })
            _emit(rec)

            # int8 store variant: quantization happens ON DEVICE (bulk_embed
            # q8 wire), so the job ships 1 B/dim codes + 2 B/row scales —
            # the config-4 1B-page recipe (docs/SCALING.md), and another
            # ~2x off the transport-bound sandbox number.
            def _sweep_q8():
                shutil.rmtree(sdir, ignore_errors=True)
                store = VectorStore(sdir, dim=ecfg.model.out_dim,
                                    shard_size=ecfg.eval.store_shard_size,
                                    dtype="int8")
                eembedder.embed_corpus(etrainer.corpus, store)
                assert store.num_vectors == n_text, store.num_vectors
                import jax.numpy as jnp
                return jnp.zeros(())

            _stamp("warming int8 text-embed (q8 wire compile)")
            shutil.rmtree(sdir, ignore_errors=True)
            warm8 = VectorStore(sdir, dim=ecfg.model.out_dim,
                                shard_size=ecfg.eval.store_shard_size,
                                dtype="int8")
            eembedder.embed_corpus(etrainer.corpus, warm8,
                                   stop=ecfg.eval.store_shard_size)
            _stamp("int8 text-embed compiled; timing full 1M sweep")
            qdt = _best_time(_sweep_q8, 1)   # secondary datapoint: one rep
            q_pps = n_text / qdt / n_dev
            rec.update({
                "embed_from_text_int8_pages_per_sec_per_chip": round(
                    q_pps, 2),
                "embed_from_text_int8_vs_transport_ceiling": round(
                    min(q_pps / (2 * ceiling), 9.99), 4),  # 1 B/dim wire
            })
        except Exception as e:  # optional phase must never cost the round
            rec["embed_text_error"] = f"{type(e).__name__}: {e}"[:300]
        _emit(rec)

    # ---- mT5-base geometry sweep (config 5: d=768, L=12, seq 128) --------
    # Config 5's first perf datapoint (VERDICT r3 Missing #4) and the
    # cleanest test of whether the stack reaches high MFU when
    # matmul-bound (d=768 vs bert-mini's 256; see docs/MFU.md). The model
    # carries the TRUE 250,112-row mT5 embedding table; batches are
    # synthetic uniform token ids via Trainer's tokenizers hook — training
    # the 250k SentencePiece is ~115 s of host data prep (proven real by
    # tests/test_vocab_honesty.py), not step cost, and uniform ids make
    # the gather/scatter no cheaper than Zipfian text. Skippable via
    # BENCH_MT5=0; skipped off-TPU.
    if os.environ.get("BENCH_MT5", "1") != "0" and on_tpu:
        # one in-phase retry (the minutes-long mt5 compile is the most
        # exposed to a transient backend error): the wrapper only retries the WHOLE worker when
        # the REQUIRED metrics are missing — an optional-phase failure after
        # the primary record printed would otherwise be final
        for _mt5_attempt in range(2):
          try:
            import numpy as np

            _stamp(f"building mt5-base phase (synthetic-id batches, "
                   f"attempt {_mt5_attempt + 1})")
            m_batch = int(os.environ.get("BENCH_MT5_BATCH", "256")) * n_dev
            mcfg = get_config("mt5_multilingual", {
                "data.num_pages": max(2_048, m_batch),
                "train.batch_size": m_batch,
                "train.log_every": 1_000_000,
                "mesh.data": n_dev, "mesh.model": 1,
            })
            mvocab = mcfg.data.vocab_size          # config 5's true 250,112
            toks = (_SyntheticTok(mvocab, mcfg.data.query_len, 1),
                    _SyntheticTok(mvocab, mcfg.data.page_len, 2))
            mstate = mstep = mbatches = None
            try:
                mtrainer = Trainer(
                    mcfg, workdir="/tmp/dnn_page_vectors_tpu_bench_mt5",
                    tokenizers=toks)
                mstate = mtrainer.init_state()
                mstep = mtrainer.compiled_step(mstate)
                mit = iter(mtrainer.batches())
                mbatches = [next(mit) for _ in range(2)]
                mrng = mtrainer.base_rng()
                for i in range(2):
                    mstate, mm = mstep(mstate, mbatches[i % 2], mrng)
                hard_sync(mm)
                _stamp("mt5 step compiled; timing")
                msteps = int(os.environ.get("BENCH_MT5_STEPS", "12"))

                def _mt5_loop():
                    nonlocal mstate
                    for i in range(msteps):
                        mstate, mm = mstep(mstate, mbatches[i % 2], mrng)
                    return mm

                mdt = _best_time(_mt5_loop, opt_reps)
                mpps = m_batch * msteps / mdt / n_dev
                mflops = train_flops_per_pair(mcfg, m_batch)
                rec.update({
                    "mt5_train_pages_per_sec_per_chip": round(mpps, 2),
                    "mt5_train_mfu": (round(mpps * mflops / peak, 4)
                                      if peak else None),
                    "mt5_vocab_size": mvocab,
                    "mt5_model_dim": mcfg.model.model_dim,
                    **_roofline_keys("mt5_", mcfg, m_batch, mpps, peak,
                                     devs[0]),
                })
            finally:
                # free the multi-GB mt5 state even on failure, or the
                # long-context sweep below inherits an OOM-primed chip
                del mstate, mstep, mbatches
          except Exception as e:  # optional sweep must never cost the round
            rec["mt5_error"] = f"{type(e).__name__}: {e}"[:300]
            continue
          rec.pop("mt5_error", None)     # a retry succeeded: drop the error
          break
        _emit(rec)

    # ---- word-family sweep: kim_cnn + lstm at config-2 geometry ----------
    # Configs 1-2's first real-chip datapoints (VERDICT r4 Weak #5): the
    # Kim-CNN and BiLSTM encoders at config-2 per-chip geometry (batch
    # 512/chip, 100k-word vocab — BASELINE.json:8) with synthetic-id
    # batches (the 100k vocab over 1M pages is one-time host prep, not step
    # cost). cdssm is deliberately absent: config 1 is the single-process
    # CPU toy oracle (BASELINE.json:7), timed continuously by the e2e test
    # suite, not a TPU reference workload (docs/MFU.md). Skippable via
    # BENCH_WORD=0; skipped off-TPU.
    if os.environ.get("BENCH_WORD", "1") != "0" and on_tpu:
        for cname, key in (("kim_cnn_v5e8", "kim_cnn"),
                           ("lstm_words", "lstm")):
          # in-phase retry (see the mt5 phase): optional phases never
          # re-run otherwise
          for _w_attempt in range(2):
            try:
                _stamp(f"building {key} phase (synthetic-id batches, "
                       f"attempt {_w_attempt + 1})")
                # 2048/chip (round 11, was 512): the word-family step is
                # ~1 ms of analytic device work at 512 — below the
                # per-dispatch floor, so the old batch measured dispatch
                # latency, not the encoder. The
                # per-model batch sizing puts enough work per step that
                # the MFU/roofline columns describe the model
                # (docs/MFU.md "word-family accounting fix").
                w_batch = int(os.environ.get("BENCH_WORD_BATCH",
                                             "2048")) * n_dev
                wcfg = get_config(cname, {
                    "data.num_pages": max(4_096, w_batch),
                    "train.batch_size": w_batch,
                    "train.log_every": 1_000_000,
                    "mesh.data": n_dev,
                })
                toks = (_SyntheticTok(wcfg.data.vocab_size,
                                      wcfg.data.query_len, 3),
                        _SyntheticTok(wcfg.data.vocab_size,
                                      wcfg.data.page_len, 4))
                wstate = wstep = wbatches = None
                try:
                    wtrainer = Trainer(
                        wcfg,
                        workdir=f"/tmp/dnn_page_vectors_tpu_bench_{key}",
                        tokenizers=toks)
                    wstate = wtrainer.init_state()
                    wstep = wtrainer.compiled_step(wstate)
                    wit = iter(wtrainer.batches())
                    wbatches = [next(wit) for _ in range(2)]
                    wrng = wtrainer.base_rng()
                    for i in range(2):
                        wstate, wm = wstep(wstate, wbatches[i % 2], wrng)
                    hard_sync(wm)
                    _stamp(f"{key} step compiled; timing")
                    wsteps = int(os.environ.get("BENCH_WORD_STEPS", "16"))

                    def _word_loop():
                        nonlocal wstate
                        for i in range(wsteps):
                            wstate, wm = wstep(wstate, wbatches[i % 2], wrng)
                        return wm

                    wdt = _best_time(_word_loop, opt_reps)
                    wpps = w_batch * wsteps / wdt / n_dev
                    wflops = train_flops_per_pair(wcfg, w_batch)
                    rec.update({
                        f"{key}_train_pages_per_sec_per_chip": round(wpps, 2),
                        f"{key}_train_mfu": (round(wpps * wflops / peak, 4)
                                             if peak else None),
                        f"{key}_batch_per_chip": w_batch // n_dev,
                        **_roofline_keys(f"{key}_", wcfg, w_batch, wpps,
                                         peak, devs[0]),
                    })
                finally:
                    del wstate, wstep, wbatches
            except Exception as e:  # optional sweep must never cost the round
                rec[f"{key}_error"] = f"{type(e).__name__}: {e}"[:300]
                continue
            rec.pop(f"{key}_error", None)
            break
        _emit(rec)

    # ---- long-context sweep (bert_long_sp geometry, Pallas flash) --------
    # Single chip can't form a seq ring, so the single-chip long-page path
    # is the flash kernel (fwd + custom-VJP bwd, O(L) HBM); SP is validated
    # by the driver's dryrun_multichip instead. Skippable via BENCH_LONG=0;
    # skipped off-TPU (interpret-mode Pallas at L=1024 is not a benchmark).
    if os.environ.get("BENCH_LONG", "1") == "0" or \
            getattr(devs[0], "platform", "") != "tpu":
        return
    # in-phase retry: see the mt5 phase (transient backend errors)
    for _l_attempt in range(2):
      try:
        _stamp(f"building long-context trainer (L=1024, flash, "
               f"attempt {_l_attempt + 1})")
        lcfg = get_config("bert_long_sp", {
            "data.num_pages": 2_048,
            "data.vocab_size": 8_192,
            "model.attention": "flash",
            "train.batch_size": int(os.environ.get("BENCH_LONG_BATCH", "64")),
            "train.log_every": 1_000_000,
            "mesh.data": n_dev, "mesh.seq": 1,
        })
        ltrainer = Trainer(lcfg, workdir="/tmp/dnn_page_vectors_tpu_bench_long")
        lstate = ltrainer.init_state()
        lstep = ltrainer.compiled_step(lstate)
        lit = iter(ltrainer.batches())
        lbatches = [next(lit) for _ in range(2)]
        lrng = ltrainer.base_rng()
        for i in range(2):
            lstate, lm = lstep(lstate, lbatches[i % 2], lrng)
        hard_sync(lm)
        _stamp("long-context step compiled; timing")
        lsteps = int(os.environ.get("BENCH_LONG_STEPS", "24"))

        def _long_loop():
            nonlocal lstate
            for i in range(lsteps):
                lstate, lm = lstep(lstate, lbatches[i % 2], lrng)
            return lm

        ldt = _best_time(_long_loop, opt_reps)
        lpps = lcfg.train.batch_size * lsteps / ldt / n_dev
        lflops = train_flops_per_pair(lcfg, lcfg.train.batch_size)
        rec.update({
            "long_train_pages_per_sec_per_chip": round(lpps, 2),
            "long_train_mfu": (round(lpps * lflops / peak, 4)
                               if peak else None),
            "long_page_len": lcfg.data.page_len,
            **_roofline_keys("long_", lcfg, lcfg.train.batch_size, lpps,
                             peak, devs[0]),
        })
        del lstate, lstep, lbatches     # free HBM for the t5 variant

        # sequence-packing A/B at long geometry (round 11, BENCH_PACK=0
        # skips): see _long_pack for the protocol + accounting
        if os.environ.get("BENCH_PACK", "1") != "0":
            for _p_attempt in range(2):
                try:
                    _long_pack(rec, n_dev, peak, opt_reps, _best_time,
                               _stamp, devs[0])
                except Exception as e:
                    rec["long_pack_error"] = f"{type(e).__name__}: {e}"[:300]
                    continue
                rec.pop("long_pack_error", None)
                break

        # t5 long-context variant (round 4): the Pallas dbias backward
        # keeps the T5-biased flash path O(L) in training too, so long
        # multilingual pages get their first perf datapoint. Own
        # try/except + error key: a crash here keeps the bert-long numbers
        # above and is distinguishable from a bert-long failure.
        for _t_attempt in range(2):
            try:
                _long_t5(rec, n_dev, peak, lsteps, opt_reps, _best_time,
                         _stamp)
            except Exception as e:
                rec["long_t5_error"] = f"{type(e).__name__}: {e}"[:300]
                continue
            rec.pop("long_t5_error", None)
            break
      except Exception as e:  # optional sweep must never cost the round
        rec["long_error"] = f"{type(e).__name__}: {e}"[:300]
        continue
      rec.pop("long_error", None)
      break
    _emit(rec)


def _long_pack(rec, n_dev, peak, opt_reps, _best_time, _stamp,
               dev) -> None:
    """Sequence-packing A/B at bert_long_sp geometry (train.pack_pages,
    docs/MFU.md "packing accounting").

    The production long-page scenario: the program compiles ONE static
    [B, 1024] row shape, but real long-page corpora are mixed-length —
    short pages ride padded rows and the pad tokens burn full-row
    compute. Protocol: a corpus of ~230-word pages through the SAME
    flash bert-long model, (a) unpacked — each page padded to the 1024
    row, the pre-packing behavior — and (b) packed 4-per-row with the
    segment mask. Accounting: both runs report USEFUL-flops MFU (flops
    of the pages' actual tokens, measured from the batch, NOT the padded
    row), so the pad waste the unpacked run burns is visible instead of
    flattered; long_pack_mfu_gain is the packing win in those terms and
    long_pack_speedup the raw pages/sec ratio. The full-length-page
    long_train_mfu above is untouched (its rows have no pad to pack)."""
    import dataclasses as _dcp

    import numpy as _npp

    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.data.toy import ToyCorpus
    from dnn_page_vectors_tpu.train.loop import Trainer
    from dnn_page_vectors_tpu.utils.flops import encoder_flops_per_example
    from dnn_page_vectors_tpu.utils.platform import hard_sync

    pack = int(os.environ.get("BENCH_PACK_PAGES", "4"))
    batch = int(os.environ.get("BENCH_LONG_BATCH", "64"))
    psteps = int(os.environ.get("BENCH_PACK_STEPS", "24"))
    base = get_config("bert_long_sp", {
        "data.num_pages": 2_048,
        "data.vocab_size": 8_192,
        "model.attention": "flash",
        "train.batch_size": batch,
        "train.log_every": 1_000_000,
        "mesh.data": n_dev, "mesh.seq": 1,
    })
    # ~215-word pages tokenize to ~243 wordpieces on the toy corpus
    # (~1.13 tokens/word measured), so 4 pages fit one 1024-token row
    # with headroom — pack=4 rows carry 4x the pages, no truncation
    corpus = ToyCorpus(num_pages=2_048, seed=0,
                       page_len=int(os.environ.get("BENCH_PACK_WORDS",
                                                   "215")),
                       query_len=32)
    results = {}
    for tag, p in (("nopack", 1), ("pack", pack)):
        cfg = base.replace(train=_dcp.replace(base.train, pack_pages=p))
        _stamp(f"long-pack phase: building {tag} trainer (pack={p})")
        tr = Trainer(cfg, corpus=corpus,
                     workdir="/tmp/dnn_page_vectors_tpu_bench_long_pack")
        state = tr.init_state()
        step = tr.compiled_step(state)
        it = iter(tr.batches())
        batches = [next(it) for _ in range(2)]
        rng = tr.base_rng()
        for i in range(2):
            state, m = step(state, batches[i % 2], rng)
        hard_sync(m)
        _stamp(f"long-pack {tag} compiled; timing")

        def _loop():
            nonlocal state
            for i in range(psteps):
                state, m = step(state, batches[i % 2], rng)
            return m

        pdt = _best_time(_loop, opt_reps)
        pps = batch * psteps / pdt / n_dev
        # useful flops: the pages' ACTUAL tokens (host-side, from batch 0)
        page_tok_count = int((_npp.asarray(batches[0]["page"]) != 0).sum())
        mean_tok = page_tok_count / batch
        useful = 3.0 * (
            encoder_flops_per_example(cfg.model, cfg.data.query_len)
            + encoder_flops_per_example(cfg.model, int(round(mean_tok)))
            + 2.0 * batch * cfg.model.out_dim)
        results[tag] = (pps, (pps * useful / peak) if peak else None,
                        mean_tok)
        del state, step, batches

    (np_pps, np_mfu, np_tok), (pk_pps, pk_mfu, pk_tok) = \
        results["nopack"], results["pack"]
    rec.update({
        "long_pack_pages": pack,
        "long_pack_mean_page_tokens": round(pk_tok, 1),
        "long_nopack_pages_per_sec_per_chip": round(np_pps, 2),
        "long_pack_pages_per_sec_per_chip": round(pk_pps, 2),
        "long_pack_speedup": round(pk_pps / np_pps, 3),
        "long_nopack_train_mfu": (round(np_mfu, 4)
                                  if np_mfu is not None else None),
        "long_pack_train_mfu": (round(pk_mfu, 4)
                                if pk_mfu is not None else None),
        "long_pack_mfu_gain": (round(pk_mfu / np_mfu, 3)
                               if np_mfu and pk_mfu else None),
    })
    _stamp(f"long-pack phase done: {np_pps:.0f} -> {pk_pps:.0f} "
           f"pages/s/chip ({pk_pps / np_pps:.2f}x via pack={pack})")


def _long_t5(rec, n_dev, peak, lsteps, opt_reps, _best_time, _stamp) -> None:
    import os

    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.train.loop import Trainer
    from dnn_page_vectors_tpu.utils.flops import train_flops_per_pair
    from dnn_page_vectors_tpu.utils.platform import hard_sync

    _stamp("building long-context t5 variant (flash + rel bias)")
    tcfg = get_config("bert_long_sp", {
        "data.num_pages": 2_048,
        "data.vocab_size": 8_192,
        "model.encoder": "t5",
        "model.attention": "flash",
        "train.batch_size": int(os.environ.get("BENCH_LONG_BATCH", "64")),
        "train.log_every": 1_000_000,
        "mesh.data": n_dev, "mesh.seq": 1,
    })
    ttrainer = Trainer(tcfg,
                       workdir="/tmp/dnn_page_vectors_tpu_bench_long_t5")
    tstate = ttrainer.init_state()
    tstep = ttrainer.compiled_step(tstate)
    tit = iter(ttrainer.batches())
    tbatches = [next(tit) for _ in range(2)]
    trng = ttrainer.base_rng()
    for i in range(2):
        tstate, tm = tstep(tstate, tbatches[i % 2], trng)
    hard_sync(tm)
    _stamp("long-context t5 step compiled; timing")

    def _long_t5_loop():
        nonlocal tstate
        for i in range(lsteps):
            tstate, tm = tstep(tstate, tbatches[i % 2], trng)
        return tm

    tdt = _best_time(_long_t5_loop, opt_reps)
    tpps = tcfg.train.batch_size * lsteps / tdt / n_dev
    tflops = train_flops_per_pair(tcfg, tcfg.train.batch_size)
    rec.update({
        "long_t5_train_pages_per_sec_per_chip": round(tpps, 2),
        "long_t5_train_mfu": (round(tpps * tflops / peak, 4)
                              if peak else None),
    })


# ---------------------------------------------------------------------------
# Partitioned-serving phase (docs/SCALING.md "Partitioned serving").
#
# HOST-SIMULATED BY DESIGN: the scatter-gather's partition workers stand in
# for P serving hosts, so this phase runs on the CPU backend in its own
# subprocess — it produces real measured numbers even when the TPU is
# unreachable (the device phases stay null-honest), and on a TPU round its
# keys merge into the same record. Scaling is accounted the only honest way
# a one-box simulation of P hosts can be: each partition's local top-k runs
# SEQUENTIALLY and is timed individually, and the simulated per-query
# latency is the critical path max(partition seconds) + the measured merge
# fold (PartitionSet.simulate) — wall-clock thread concurrency on a shared
# core would measure the box, not the topology. Scan bytes per query are
# the critical-path partition's candidate payload, measured by the same
# accounting serving itself reports.
# ---------------------------------------------------------------------------

def run_partitioned_worker() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    import jax
    from jax.sharding import Mesh

    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.infer.serve import SearchService
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore

    dim = int(os.environ.get("BENCH_PART_DIM", "64"))
    shard_rows = int(os.environ.get("BENCH_PART_SHARD_ROWS", "16384"))
    n_shards = int(os.environ.get("BENCH_PART_SHARDS", "8"))
    iters = int(os.environ.get("BENCH_PART_ITERS", "12"))
    kq = 10
    rows = shard_rows * n_shards
    _stamp(f"partitioned phase: building {rows}-row synthetic store "
           f"({n_shards} shards, dim {dim})")
    rng = np.random.default_rng(0)
    sdir = "/tmp/dnn_page_vectors_tpu_bench/part_store"
    import shutil
    shutil.rmtree(sdir, ignore_errors=True)
    store = VectorStore(sdir, dim=dim, shard_size=shard_rows)
    for si in range(n_shards):
        v = rng.standard_normal((shard_rows, dim)).astype(np.float32)
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        store.write_shard(si, np.arange(si * shard_rows,
                                        (si + 1) * shard_rows,
                                        dtype=np.int64), v)
    store = VectorStore(sdir)

    class _MeshOnly:
        """The partitioned phase drives retrieval by pre-computed query
        vectors (SearchService.topk_vectors), so the embedder stub only
        needs the mesh — no model, tokenizer, or checkpoint."""

    emb = _MeshOnly()
    emb.mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    qv = rng.standard_normal((1, dim)).astype(np.float32)
    qv /= np.linalg.norm(qv, axis=1, keepdims=True)

    rec = {"partitioned_store_rows": rows, "partitioned_shards": n_shards,
           "partitioned_dim": dim, "partitioned_k": kq,
           "partitioned_iters": iters}
    # Build EVERY topology first, then INTERLEAVE the timed rounds: the
    # sandbox's shared-tenancy noise comes and goes on a minutes scale,
    # so measuring P=1 and P=4 in different minutes would let one slow
    # window misprice the scaling ratio — round-robin sampling puts every
    # topology under the same noise, and the MEDIAN critical path is the
    # robust per-topology estimator on top.
    combos = [(P, R) for P in (1, 2, 4) for R in (1, 2)]
    services = {}
    for P, R in combos:
        cfg = get_config("cdssm_toy", {
            "model.out_dim": dim, "serve.partitions": P,
            "serve.replicas": R})
        svc = SearchService(cfg, emb, None, store, preload_hbm_gb=4.0)
        pset = svc.partition_set
        extra = None
        if pset is None:
            # P=R=1: the single-view path IS the baseline — simulate
            # through a 1-partition set for identical accounting
            from dnn_page_vectors_tpu.infer.partition import PartitionSet
            extra = pset = PartitionSet(svc, store, partitions=1,
                                        replicas=1)
        pset.simulate(qv, 1, kq)               # warm: compile every shape
        services[(P, R)] = (svc, pset, extra)
    stats = {key: {"crit": [], "merge": [], "scan": 0, "ids": None}
             for key in combos}
    for _ in range(iters):
        for key in combos:
            sim = services[key][1].simulate(qv, 1, kq)
            st = stats[key]
            st["crit"].append(sim["critical_path_seconds"])
            st["merge"].append(sim["merge_seconds"])
            st["scan"] = max(sim["scan_bytes"])
            st["ids"] = sim["ids"]
    qps = {}
    scan = {}
    base_ids = stats[(1, 1)]["ids"]
    for P, R in combos:
        st = stats[(P, R)]
        if not np.array_equal(st["ids"], base_ids):
            rec["partitioned_identity_error"] = f"P={P} R={R}"
        # BEST critical path -> qps (the _best_time estimator the train/
        # embed phases use): shared-tenancy interference only ever ADDS
        # time, so min is the honest "what the topology can do" number;
        # the p99 key next to it reports the observed spread
        qps[(P, R)] = 1.0 / float(np.min(np.asarray(st["crit"])))
        scan[(P, R)] = st["scan"]
        rec[f"partitioned_qps_p{P}_r{R}"] = round(qps[(P, R)], 2)
        rec[f"partitioned_p99_ms_p{P}_r{R}"] = round(
            float(np.percentile(np.asarray(st["crit"]), 99)) * 1000.0, 3)
        rec[f"partitioned_scan_bytes_per_query_p{P}_r{R}"] = st["scan"]
        rec[f"partitioned_merge_ms_p{P}_r{R}"] = round(
            sum(st["merge"]) / len(st["merge"]) * 1000.0, 4)
        _stamp(f"partitioned P={P} R={R}: "
               f"{qps[(P, R)]:.1f} sim qps, "
               f"{st['scan']} scan B/query")
        svc, _, extra = services[(P, R)]
        if extra is not None:
            extra.close()
        svc.close()
    for P in (2, 4):
        rec[f"partitioned_scaling_efficiency_p{P}"] = round(
            qps[(P, 1)] / qps[(1, 1)] / P, 4)
    rec["partitioned_scan_bytes_ratio_p4"] = round(
        scan[(4, 1)] / max(scan[(1, 1)], 1), 4)

    # routing drill (fixed protocol, excluded from the gate): a restaging
    # primary sheds to its replica; a partition with EVERY replica
    # degraded serves degraded locally — results stay non-empty and
    # identical (the availability half of the acceptance criteria)
    cfg = get_config("cdssm_toy", {"model.out_dim": dim,
                                   "serve.partitions": 2,
                                   "serve.replicas": 2})
    svc = SearchService(cfg, emb, None, store, preload_hbm_gb=4.0)
    pset = svc.partition_set
    pset._parts[0][0].set_restaging(True)
    svc.topk_vectors(qv, k=kq)
    pset._parts[0][0].set_restaging(False)
    for rep in pset._parts[0]:
        rep.view.stream_entries = list(rep.view.entries)
        rep.view.shards = None
    _, ids = svc.topk_vectors(qv, k=kq)
    rec["partitioned_shed_drill_sheds"] = svc.replica_shed
    rec["partitioned_shed_drill_degraded_serves"] = \
        svc.partition_degraded_serves
    rec["partitioned_degraded_results_identical"] = bool(
        np.array_equal(ids, base_ids))
    svc.close()
    print(json.dumps(rec), flush=True)


def run_net_worker() -> None:
    """The `net_serve` phase (docs/SERVING.md "Network front end"),
    CPU-honest like the partitioned phase: a synthetic store served by
    the REAL network stack — asyncio front end over loopback, partition
    workers as genuine subprocesses behind the WorkerGateway — measured
    by the loadgen driver's qps@p99 search with the issue path crossing
    the socket. HONEST about cores: the P in {1, 2, 4} topology sweep
    runs only where P worker processes can genuinely parallelize
    (P <= detected cores, `BENCH_NET_CORES` overrides) — the PR-13
    flat-30-qps artifact came from pricing a 4-process fan-out on one
    core — with per-step scaling efficiency next to each measured qps.
    Wire-byte accounting is an explicit A/B: the same fixed request
    stream once with `serve.wire_compress` on (the headline
    `net_wire_bytes_per_query`) and once negotiated down to raw frames,
    with the ratio recorded (`net_wire_compression_ratio`). Drills:
    hedge fire rate (one deliberately slow replica) and deadline-shed
    rate under an over-budget burst."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import shutil

    import numpy as np

    import jax
    from jax.sharding import Mesh

    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.infer.partition_host import (
        MeshEmbedder, WorkerGateway)
    from dnn_page_vectors_tpu.infer.serve import SearchService
    from dnn_page_vectors_tpu.infer.server import serve_in_background
    from dnn_page_vectors_tpu.infer.transport import (
        DeadlineExceeded, SocketSearchClient)
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    from dnn_page_vectors_tpu.loadgen import find_qps_at_p99, make_workload

    dim = int(os.environ.get("BENCH_NET_DIM", "64"))
    shard_rows = int(os.environ.get("BENCH_NET_SHARD_ROWS", "16384"))
    n_shards = int(os.environ.get("BENCH_NET_SHARDS", "8"))
    trial_s = float(os.environ.get("BENCH_NET_TRIAL_S", "1.5"))
    # the p99 target carries headroom for the 1-core sandbox, where P=4
    # worker PROCESSES serialize on one core under the front end — the
    # gate tracks the measured qps, the target is a protocol constant.
    # start_qps stays >= 16: below that, a short trial's rolling window
    # sees too few Poisson arrivals for the driver's open-loop sustain
    # check (achieved >= 0.8x offered) to be statistically meaningful
    p99_ms = float(os.environ.get("BENCH_NET_P99_MS", "200"))
    iters = int(os.environ.get("BENCH_NET_ITERS", "2"))
    start_qps = float(os.environ.get("BENCH_NET_START_QPS", "16"))
    # best-of-REPS qps@p99 searches per topology: the _best_time
    # estimator applied to the driver — shared-tenancy noise on this
    # box can sink ALL of one search's short trials, and best-of keeps
    # one bad minute from mispricing a topology
    reps = max(1, int(os.environ.get("BENCH_NET_REPS", "2")))
    # available cores gate the topology sweep: a P-process fan-out on
    # fewer than P cores measures scheduler overhead, not the fleet —
    # BENCH_NET_CORES overrides detection (containers/cgroup quotas the
    # affinity mask can't see)
    try:
        detected = len(os.sched_getaffinity(0))
    except AttributeError:
        detected = os.cpu_count() or 1
    cores = int(os.environ.get("BENCH_NET_CORES", "0") or 0) or detected
    kq = 10
    rows = shard_rows * n_shards
    wdir = "/tmp/dnn_page_vectors_tpu_bench/net"
    sdir = os.path.join(wdir, "store")
    _stamp(f"net phase: building {rows}-row synthetic store "
           f"({n_shards} shards, dim {dim})")
    rng = np.random.default_rng(0)
    shutil.rmtree(wdir, ignore_errors=True)
    store = VectorStore(sdir, dim=dim, shard_size=shard_rows)
    for si in range(n_shards):
        v = rng.standard_normal((shard_rows, dim)).astype(np.float32)
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        store.write_shard(si, np.arange(si * shard_rows,
                                        (si + 1) * shard_rows,
                                        dtype=np.int64), v)
    store = VectorStore(sdir)
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    distinct = 32
    qvs = rng.standard_normal((distinct, dim)).astype(np.float32)
    qvs /= np.linalg.norm(qvs, axis=1, keepdims=True)
    qnames = [f"q{i}" for i in range(distinct)]
    qvec = {name: qvs[i:i + 1] for i, name in enumerate(qnames)}

    class _VecClient:
        """run_trial-compatible issue shim: query text -> its
        pre-computed vector over the T_VQUERY wire path."""

        def __init__(self, client):
            self._client = client

        def search(self, query, k=None, nprobe=None):
            return self._client.topk_vectors(qvec[query], k=k,
                                             nprobe=nprobe)

    def _spawn_workers(gw, P, R=1, slow_rids=(), slow_ms=0, connect=None):
        procs = []
        for wp in range(P):
            for wr in range(R):
                env = dict(os.environ, JAX_PLATFORMS="cpu")
                if wr in slow_rids:
                    env["DPV_WORKER_SLOW_MS"] = str(slow_ms)
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "dnn_page_vectors_tpu.cli",
                     "partition-worker", "--config", "cdssm_toy",
                     "--workdir", wdir,
                     "--set", f"model.out_dim={dim}",
                     "--connect", connect or f"{gw.host}:{gw.port}",
                     "--partition", str(wp), "--partitions", str(P),
                     "--replica", str(wr)],
                    cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
                    env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
        return procs

    rec = {"net_store_rows": rows, "net_shards": n_shards, "net_dim": dim,
           "net_k": kq, "net_p99_target_ms": p99_ms, "net_cores": cores}
    wl = make_workload("poisson", seed=0, distinct=distinct,
                       profile=((kq, None, 1.0),))
    sweep = [P for P in (1, 2, 4) if P <= cores] or [1]
    if len(sweep) < 3:
        _stamp(f"net: {cores} core(s) — sweeping only P={sweep} (a "
               "P-process fan-out beyond the core count would measure "
               "scheduler overhead, not scaling)")
    qps_by_p = {}
    for P in sweep:
        cfg = get_config("cdssm_toy", {
            "model.out_dim": dim,
            # window == trial duration: each trial's p99 reads its OWN
            # window, not the previous trial's load (the slo-phase
            # discipline)
            "obs.window_s": trial_s,
            "serve.partitions": P, "serve.replicas": 1})
        svc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                            preload_hbm_gb=4.0)
        gw = WorkerGateway(svc, heartbeat_s=0.5)
        svc.attach_gateway(gw)
        procs = _spawn_workers(gw, P)
        up = gw.wait_for_workers(P, timeout_s=60.0)
        srv = serve_in_background(svc)
        client = _VecClient(SocketSearchClient(srv.host, srv.port))
        try:
            client.search(qnames[0], k=kq)     # warm every compiled shape
            _stamp(f"net P={P}: workers_up={up}; searching qps @ "
                   f"p99<{p99_ms:.0f}ms over loopback (best of {reps})")
            best, n_trials = 0.0, 0
            for _ in range(reps):
                rep = find_qps_at_p99(
                    svc, wl, qnames, p99_target_ms=p99_ms,
                    start=start_qps, iters=iters, duration_s=trial_s,
                    warmup_s=0.5, workers=16, client=client)
                best = max(best, rep["qps_at_p99"])
                n_trials += len(rep["trials"])
            rec[f"net_qps_at_p99_p{P}"] = round(best, 2)
            qps_by_p[P] = best
            _stamp(f"net P={P}: {best:.1f} qps @ "
                   f"p99<{p99_ms:.0f}ms ({n_trials} trials)")
        finally:
            client._client.close()
            srv.close()
            for pr in procs:
                pr.terminate()
            for pr in procs:
                try:
                    pr.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pr.kill()
            gw.close()
            svc.close()
    # scaling efficiency: measured qps at P over P x the 1-partition
    # qps — only for topologies that actually ran on enough cores
    if qps_by_p.get(1):
        for P in (2, 4):
            if qps_by_p.get(P):
                rec[f"net_scaling_eff_p{P}"] = round(
                    qps_by_p[P] / (P * qps_by_p[1]), 4)

    # multi-front-end sweep (docs/SCALING.md "Scale-out tier"): N
    # listeners + N gateways over ONE shared worker that registers with
    # all of them, priced as one unit through the driver's seeded
    # balancer. fe1 IS the P=1 single-front-end number measured above
    # (same topology, already best-of-reps); fe2 runs only where a
    # second front end has a core to run on (BENCH_NET_CORES honored —
    # two front ends on one core measure the scheduler, not the tier).
    if rec.get("net_qps_at_p99_p1") is not None:
        rec["net_qps_at_p99_fe1"] = rec["net_qps_at_p99_p1"]
    if cores >= 2 and os.environ.get("BENCH_FE", "1") != "0":
        from dnn_page_vectors_tpu.loadgen import BalancedClient
        fe_n = 2
        cfg = get_config("cdssm_toy", {
            "model.out_dim": dim, "obs.window_s": trial_s,
            "serve.partitions": 1, "serve.replicas": 1})
        fe_svcs, fe_gws, fe_srvs, fe_clients = [], [], [], []
        for _ in range(fe_n):
            fsvc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                                 preload_hbm_gb=4.0)
            fgw = WorkerGateway(fsvc, heartbeat_s=0.5)
            fsvc.attach_gateway(fgw)
            fe_svcs.append(fsvc)
            fe_gws.append(fgw)
        connect = ",".join(f"{g.host}:{g.port}" for g in fe_gws)
        procs = _spawn_workers(fe_gws[0], 1, connect=connect)
        up = all(g.wait_for_workers(1, timeout_s=60.0) for g in fe_gws)
        for fe_i, fsvc in enumerate(fe_svcs):
            srv = serve_in_background(fsvc, front_end=fe_i)
            fe_srvs.append(srv)
            fe_clients.append(SocketSearchClient(srv.host, srv.port))
        bal = BalancedClient([_VecClient(c) for c in fe_clients],
                             policy="round_robin", seed=0)
        try:
            for c in fe_clients:                 # warm EVERY front end
                _VecClient(c).search(qnames[0], k=kq)
            _stamp(f"net FE={fe_n}: workers_up={up}; searching tier "
                   f"qps @ p99<{p99_ms:.0f}ms (best of {reps})")
            best, n_trials = 0.0, 0
            for _ in range(reps):
                rep = find_qps_at_p99(
                    fe_svcs[0], wl, qnames, p99_target_ms=p99_ms,
                    start=start_qps, iters=iters, duration_s=trial_s,
                    warmup_s=0.5, workers=16, client=bal,
                    front_ends=fe_svcs)
                best = max(best, rep["qps_at_p99"])
                n_trials += len(rep["trials"])
            rec[f"net_qps_at_p99_fe{fe_n}"] = round(best, 2)
            rec["net_front_ends"] = fe_n
            _stamp(f"net FE={fe_n}: {best:.1f} qps @ "
                   f"p99<{p99_ms:.0f}ms ({n_trials} trials)")
        finally:
            for c in fe_clients:
                c.close()
            for srv in fe_srvs:
                srv.close()
            for pr in procs:
                pr.terminate()
            for pr in procs:
                try:
                    pr.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pr.kill()
            for g in fe_gws:
                g.close()
            for fsvc in fe_svcs:
                fsvc.close()

    # wire-byte A/B (the compression headline): the SAME fixed request
    # stream over the full stack — client edge + worker RPC hop — once
    # with wire compression negotiated and once forced to raw frames.
    # A fixed count (not a qps search) so both arms move identical
    # traffic and the ratio is load-independent.
    # probe length trades time for steady-state honesty: the first send
    # of each distinct query block is a full PUT, so too few requests
    # over-weigh the intern warm-up against the REF steady state
    probe_p = 2 if cores >= 2 else 1
    probe_n = int(os.environ.get("BENCH_NET_PROBE_N", "400"))
    wire_ab = {}
    for label, compress in (("", True), ("_raw", False)):
        cfg = get_config("cdssm_toy", {
            "model.out_dim": dim, "serve.partitions": probe_p,
            "serve.wire_compress": compress})
        svc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                            preload_hbm_gb=4.0)
        gw = WorkerGateway(svc, heartbeat_s=0.5)
        svc.attach_gateway(gw)
        procs = _spawn_workers(gw, probe_p)
        up = gw.wait_for_workers(probe_p, timeout_s=60.0)
        srv = serve_in_background(svc)
        sclient = SocketSearchClient(srv.host, srv.port,
                                     compress=compress)
        try:
            sclient.topk_vectors(qvs[:1], k=kq)          # warm compiles
            wire0 = svc.wire_bytes
            for i in range(probe_n):
                sclient.topk_vectors(qvs[i % distinct: i % distinct + 1],
                                     k=kq)
            wire_ab[label] = (svc.wire_bytes - wire0) / probe_n
            rec[f"net_wire_bytes_per_query{label}"] = round(
                wire_ab[label], 1)
        finally:
            sclient.close()
            srv.close()
            for pr in procs:
                pr.terminate()
            for pr in procs:
                try:
                    pr.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pr.kill()
            gw.close()
            svc.close()
    if wire_ab.get("") and wire_ab.get("_raw"):
        rec["net_wire_compression_ratio"] = round(
            wire_ab["_raw"] / wire_ab[""], 3)
        _stamp(f"net wire A/B (P={probe_p}, workers_up={up}): "
               f"{wire_ab['_raw']:.0f} raw -> {wire_ab['']:.0f} "
               f"compressed bytes/query "
               f"(x{rec['net_wire_compression_ratio']:.2f})")

    # hedge drill: P=1, R=2 over real loopback sockets (thread workers —
    # their slow_ms is mutable, which the drill needs: the latency
    # history warms on a HEALTHY primary, then the primary turns slow
    # and the fan-out must hedge to the fast sibling at the warmed
    # quantile point)
    import threading as _threading

    from dnn_page_vectors_tpu.infer.partition_host import PartitionWorker
    cfg = get_config("cdssm_toy", {
        "model.out_dim": dim, "serve.partitions": 1, "serve.replicas": 2,
        "serve.hedge_quantile": 0.9})
    svc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                        preload_hbm_gb=4.0)
    gw = WorkerGateway(svc, heartbeat_s=0.5)
    svc.attach_gateway(gw)
    tworkers = []
    for wr in range(2):
        w = PartitionWorker(cfg, sdir, ("127.0.0.1", gw.port), partition=0,
                            partitions=1, replica=wr, mesh=mesh)
        _threading.Thread(target=w.run, daemon=True).start()
        tworkers.append(w)
    gw.wait_for_workers(2, timeout_s=60.0)
    try:
        for i in range(12):                    # warm the latency history
            svc.topk_vectors(qvs[i % distinct: i % distinct + 1], k=kq)
        tworkers[0].slow_ms = 40.0             # the primary goes slow
        h0, n_drill = svc.hedge_fires, 30
        t0 = time.perf_counter()
        for i in range(n_drill):
            svc.topk_vectors(qvs[i % distinct: i % distinct + 1], k=kq)
        drill_ms = (time.perf_counter() - t0) / n_drill * 1000.0
        rec["net_hedge_fire_rate"] = round(
            (svc.hedge_fires - h0) / n_drill, 4)
        rec["net_hedged_latency_ms"] = round(drill_ms, 3)
        _stamp(f"net hedge drill: fire rate "
               f"{rec['net_hedge_fire_rate']:.2f}, "
               f"{drill_ms:.1f} ms/query against a 40 ms-slow primary")
    finally:
        for w in tworkers:
            w.stop()
        gw.close()
        svc.close()

    # deadline-shed drill: a burst of requests whose budget is smaller
    # than the socket->executor hop itself — admission finds them
    # EXPIRED at the door and sheds (T_SHED), never errors
    cfg = get_config("cdssm_toy", {"model.out_dim": dim})
    svc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                        preload_hbm_gb=4.0)
    srv = serve_in_background(svc)
    vclient = SocketSearchClient(srv.host, srv.port)
    try:
        vclient.topk_vectors(qvs[:1], k=kq)    # warm: compile off-drill
        sheds0 = svc.deadline_sheds
        errors = 0
        n_burst, shed_seen = 200, 0
        for i in range(n_burst):
            try:
                vclient.topk_vectors(qvs[i % distinct: i % distinct + 1],
                                     k=kq, deadline_ms=0.05)
            except DeadlineExceeded:
                shed_seen += 1
            except Exception:  # noqa: BLE001 — drill metric, not fatal
                errors += 1
        rec["net_deadline_shed_rate"] = round(
            max(svc.deadline_sheds - sheds0, shed_seen) / n_burst, 4)
        rec["net_deadline_drill_errors"] = errors
        _stamp(f"net deadline drill: shed rate "
               f"{rec['net_deadline_shed_rate']:.2f} at a 0.05 ms budget "
               f"({errors} errors)")
    finally:
        vclient.close()
        srv.close()
        svc.close()

    # resize_serve drill (docs/SCALING.md "Scale-out tier";
    # BENCH_RESIZE=0 skips): elastic membership priced under fire. A
    # second worker JOINS mid-hammer, the gateway re-splits the
    # partition map live (fleet_resplit) and hands off through the
    # generation-gated REFRESH barrier. Headline numbers: the qps dip
    # depth while the handoff runs (resize_qps_dip_pct) and the seconds
    # from join until the whole fleet serves the new split
    # (resize_recovery_seconds; acceptance pin <= 3x the heartbeat).
    # Hard pins: zero errors, zero mixed-split result sets — every
    # answer must stay byte-identical to the pre-attach oracle THROUGH
    # the re-split (a mixed-split merge would break identity and counts
    # as an error).
    if os.environ.get("BENCH_RESIZE", "1") != "0":
        import threading as _rthreading

        from dnn_page_vectors_tpu.infer.partition_host import (
            PartitionWorker as _RWorker)
        hb_s = 0.25
        cfg = get_config("cdssm_toy", {
            "model.out_dim": dim, "serve.partitions": 1,
            "serve.replicas": 1, "serve.elastic": True,
            "serve.heartbeat_s": hb_s})
        svc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                            preload_hbm_gb=4.0)
        # the oracle: in-process answers BEFORE any gateway attaches —
        # both splits must reproduce these exactly
        oracle = [svc.topk_vectors(qvs[i:i + 1], k=kq)
                  for i in range(distinct)]
        gw = WorkerGateway(svc, heartbeat_s=hb_s)
        svc.attach_gateway(gw)
        w0 = _RWorker(cfg, sdir, ("127.0.0.1", gw.port), partition=0,
                      partitions=1, replica=0, mesh=mesh)
        _rthreading.Thread(target=w0.run, daemon=True).start()
        gw.wait_for_workers(1, timeout_s=60.0)
        joiner = None
        errors = 0
        stamps = []
        try:
            svc.topk_vectors(qvs[:1], k=kq)      # warm over the wire
            n_hammer = int(os.environ.get("BENCH_RESIZE_N", "1200"))
            join_at = n_hammer // 3
            resplits0 = len(svc.registry.events("fleet_resplit"))
            t_join = recovery = None
            for i in range(n_hammer):
                if i == join_at:
                    joiner = _RWorker(cfg, sdir, ("127.0.0.1", gw.port),
                                      partition=1, partitions=2,
                                      replica=0, mesh=mesh)
                    _rthreading.Thread(target=joiner.run,
                                       daemon=True).start()
                    t_join = time.perf_counter()
                qi = i % distinct
                try:
                    s, ids2 = svc.topk_vectors(qvs[qi:qi + 1], k=kq)
                    osc, oid = oracle[qi]
                    if not (np.array_equal(s, osc)
                            and np.array_equal(ids2, oid)):
                        errors += 1   # mixed-split bytes land here
                except Exception:  # noqa: BLE001 — drill metric
                    errors += 1
                stamps.append(time.perf_counter())
                if t_join is not None and recovery is None:
                    table = gw.partition_set._view_table
                    if (len(svc.registry.events("fleet_resplit"))
                            > resplits0 and len(table) == 2
                            and len(gw.live_workers()) == 2
                            and gw.stale_workers(
                                table[0][0].generation, split=2) == 0):
                        recovery = time.perf_counter() - t_join
            # qps trajectory from completion stamps: baseline = median
            # pre-join bucket, dip = slowest bucket in the 3 s after
            bucket_s = 0.5
            t0b = stamps[0]
            counts: dict = {}
            for t in stamps:
                b = int((t - t0b) / bucket_s)
                counts[b] = counts.get(b, 0) + 1
            pre = sorted(c / bucket_s for b, c in counts.items()
                         if t0b + (b + 1) * bucket_s <= t_join)
            post = [c / bucket_s for b, c in counts.items()
                    if t_join <= t0b + b * bucket_s <= t_join + 3.0]
            baseline = pre[len(pre) // 2] if pre else 0.0
            dip = min(post) if post else baseline
            rec["resize_baseline_qps"] = round(baseline, 1)
            rec["resize_qps_dip_pct"] = round(
                max(0.0, (baseline - dip) / baseline * 100.0)
                if baseline else 0.0, 2)
            rec["resize_recovery_seconds"] = round(
                recovery if recovery is not None else 999.0, 3)
            rec["resize_errors"] = errors
            rec["resize_hammer_n"] = n_hammer
            rec["resize_heartbeat_s"] = hb_s
            _stamp(f"net resize drill: dip "
                   f"{rec['resize_qps_dip_pct']:.1f}% off a "
                   f"{baseline:.0f} qps baseline, recovery "
                   f"{rec['resize_recovery_seconds']:.3f}s (pin <= "
                   f"{3 * hb_s:.2f}s), {errors} errors")
        finally:
            if joiner is not None:
                joiner.stop()
            w0.stop()
            gw.close()
            svc.close()

    # chaos_serve drill (docs/ROBUSTNESS.md "Availability drills";
    # BENCH_CHAOS=0 skips): the self-healing pin priced on real loopback
    # sockets. Phase 1 — tear the sole worker's connection under a query
    # hammer and time kill -> rejoined + live again
    # (chaos_recovery_seconds; the acceptance pin is <= 3x the heartbeat
    # interval). Phase 2 — a seeded wire-fault schedule (torn frames,
    # dup frames, drops, stalls) fires under the hammer; every answer
    # must stay byte-identical to the in-process oracle
    # (chaos_availability = answered/offered, chaos_errors pinned 0 —
    # a mismatch counts as an error).
    if os.environ.get("BENCH_CHAOS", "1") != "0":
        from dnn_page_vectors_tpu.utils import faults as _faults
        hb_s = 0.25
        cfg = get_config("cdssm_toy", {
            "model.out_dim": dim, "serve.partitions": 1,
            "serve.replicas": 1, "serve.heartbeat_s": hb_s})
        svc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                            preload_hbm_gb=4.0)
        # the never-faulted oracle: in-process answers BEFORE any
        # gateway attaches — the wire must reproduce these exactly
        oracle = [svc.topk_vectors(qvs[i:i + 1], k=kq)
                  for i in range(distinct)]
        gw = WorkerGateway(svc, heartbeat_s=hb_s)
        svc.attach_gateway(gw)
        w = PartitionWorker(cfg, sdir, ("127.0.0.1", gw.port), partition=0,
                            partitions=1, replica=0, mesh=mesh)
        _threading.Thread(target=w.run, daemon=True).start()
        gw.wait_for_workers(1, timeout_s=60.0)
        offered = answered = errors = sheds = 0

        def _hammer_one(qi: int):
            nonlocal offered, answered, errors, sheds
            offered += 1
            try:
                s, ids2 = svc.topk_vectors(qvs[qi:qi + 1], k=kq)
            except DeadlineExceeded:
                sheds += 1
                offered -= 1          # sheds excluded from availability
                return
            except Exception:  # noqa: BLE001 — drill metric, not fatal
                errors += 1
                return
            osc, oid = oracle[qi]
            if np.array_equal(s, osc) and np.array_equal(ids2, oid):
                answered += 1
            else:
                errors += 1           # wrong bytes are worse than none
        try:
            svc.topk_vectors(qvs[:1], k=kq)    # warm over the wire
            rejoined0 = len(svc.registry.events("worker_rejoined"))
            t_kill = time.perf_counter()
            w.kill_connection()
            recovery = None
            qi = 0
            while time.perf_counter() - t_kill < 30.0:
                _hammer_one(qi % distinct)     # fallback serves the gap
                qi += 1
                if (len(svc.registry.events("worker_rejoined")) > rejoined0
                        and gw.worker_alive(0, 0)):
                    recovery = time.perf_counter() - t_kill
                    break
            rec["chaos_recovery_seconds"] = round(
                recovery if recovery is not None else 999.0, 3)
            _faults.install(_faults.FaultPlan.parse(
                "wire_send:frame_trunc:40,wire_recv:frame_delay:30,"
                "wire_send:frame_dup:90,wire_send:conn_drop:140", seed=0))
            n_chaos = int(os.environ.get("BENCH_CHAOS_N", "150"))
            for i in range(n_chaos):
                _hammer_one(i % distinct)
            injected = sum(v for key, v in _faults.counters().items()
                           if key.startswith("injected_"))
            rec["chaos_availability"] = round(
                answered / max(offered, 1), 4)
            rec["chaos_errors"] = errors
            _stamp(f"net chaos drill: recovery "
                   f"{rec['chaos_recovery_seconds']:.3f}s (pin <= "
                   f"{3 * hb_s:.2f}s), availability "
                   f"{rec['chaos_availability']:.4f} over {offered} "
                   f"offered ({injected} faults injected, {errors} "
                   f"errors, {sheds} sheds)")
        finally:
            _faults.reset()
            w.stop()
            gw.close()
            svc.close()
    print(json.dumps(rec), flush=True)


def run_cache_worker() -> None:
    """cache_serve phase: CPU-honest A/B of the generation-keyed result
    cache on the Zipfian head. The SAME synthetic store and the SAME
    Zipf-mix workload are priced twice through the real serving path —
    once with `serve.result_cache` on (a hit short-circuits BEFORE the
    request consumes a micro-batch slot) and once off — reported as
    qps@p99 per arm plus the measured hit rate and the per-hit serve
    cost. The embed hop is stubbed to a deterministic name->vector map:
    the result cache keys on query TEXT, and what this phase prices is
    everything after the key (probe, skipped top-k, format) — the off
    arm still pays the full scan, so the ratio isolates the cache."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import shutil

    import numpy as np

    import jax
    from jax.sharding import Mesh

    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.infer.partition_host import MeshEmbedder
    from dnn_page_vectors_tpu.infer.serve import SearchService
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    from dnn_page_vectors_tpu.loadgen import find_qps_at_p99, make_workload

    dim = int(os.environ.get("BENCH_CACHE_DIM", "64"))
    shard_rows = int(os.environ.get("BENCH_CACHE_SHARD_ROWS", "16384"))
    n_shards = int(os.environ.get("BENCH_CACHE_SHARDS", "4"))
    trial_s = float(os.environ.get("BENCH_CACHE_TRIAL_S", "1.5"))
    p99_ms = float(os.environ.get("BENCH_CACHE_P99_MS", "200"))
    iters = int(os.environ.get("BENCH_CACHE_ITERS", "2"))
    start_qps = float(os.environ.get("BENCH_CACHE_START_QPS", "16"))
    reps = max(1, int(os.environ.get("BENCH_CACHE_REPS", "2")))
    # 32 distinct queries under the workload's Zipfian repeat profile:
    # small enough that the head fits the default cache, large enough
    # that the off arm can't live off the embed LRU alone
    distinct = int(os.environ.get("BENCH_CACHE_DISTINCT", "32"))
    kq = 10
    rows = shard_rows * n_shards
    wdir = "/tmp/dnn_page_vectors_tpu_bench/cache"
    sdir = os.path.join(wdir, "store")
    _stamp(f"cache phase: building {rows}-row synthetic store "
           f"({n_shards} shards, dim {dim})")
    rng = np.random.default_rng(0)
    shutil.rmtree(wdir, ignore_errors=True)
    store = VectorStore(sdir, dim=dim, shard_size=shard_rows)
    for si in range(n_shards):
        v = rng.standard_normal((shard_rows, dim)).astype(np.float32)
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        store.write_shard(si, np.arange(si * shard_rows,
                                        (si + 1) * shard_rows,
                                        dtype=np.int64), v)
    store = VectorStore(sdir)
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    qvs = rng.standard_normal((distinct, dim)).astype(np.float32)
    qvs /= np.linalg.norm(qvs, axis=1, keepdims=True)
    qnames = [f"q{i}" for i in range(distinct)]
    qvec = {name: qvs[i:i + 1] for i, name in enumerate(qnames)}

    def _stub_embed(queries):
        return np.concatenate([qvec[q] for q in queries], axis=0)

    class _StubCorpus:
        def page_text(self, i):
            return f"page {i}"

    rec = {"cache_store_rows": rows, "cache_dim": dim, "cache_k": kq,
           "cache_distinct": distinct}
    wl = make_workload("poisson", seed=0, distinct=distinct,
                       profile=((kq, None, 1.0),))
    qps = {}
    for label, on in (("on", True), ("off", False)):
        cfg = get_config("cdssm_toy", {
            "model.out_dim": dim,
            # window == trial duration: each trial's p99 reads its OWN
            # window (the slo-phase discipline)
            "obs.window_s": trial_s,
            "serve.result_cache": on})
        svc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                            preload_hbm_gb=4.0)
        svc._embed_queries_cached = _stub_embed
        svc.corpus = _StubCorpus()
        try:
            svc.search(qnames[0], k=kq)        # warm every compiled shape
            _stamp(f"cache arm={label}: searching qps @ "
                   f"p99<{p99_ms:.0f}ms (best of {reps})")
            best, n_trials = 0.0, 0
            for _ in range(reps):
                rep = find_qps_at_p99(
                    svc, wl, qnames, p99_target_ms=p99_ms,
                    start=start_qps, iters=iters, duration_s=trial_s,
                    warmup_s=0.5, workers=16)
                best = max(best, rep["qps_at_p99"])
                n_trials += len(rep["trials"])
            qps[label] = best
            rec[f"cache_serve_qps_at_p99_{label}"] = round(best, 2)
            _stamp(f"cache arm={label}: {best:.1f} qps @ "
                   f"p99<{p99_ms:.0f}ms ({n_trials} trials)")
            if on:
                met = svc.metrics().get("result_cache") or {}
                hits = int(met.get("hits") or 0)
                misses = int(met.get("misses") or 0)
                if hits + misses:
                    rec["cache_hit_rate"] = round(
                        hits / (hits + misses), 4)
                rec["cache_entries"] = int(met.get("entries") or 0)
                # per-hit serve cost: one resident key hammered on a
                # quiet service — the probe+copy path alone, no scan
                svc.search(qnames[0], k=kq)
                n_hot = 2000
                t0 = time.perf_counter()
                for _ in range(n_hot):
                    svc.search(qnames[0], k=kq)
                rec["cache_serve_us_per_hit"] = round(
                    (time.perf_counter() - t0) / n_hot * 1e6, 2)
        finally:
            svc.close()
    if qps.get("on") and qps.get("off"):
        rec["cache_serve_speedup"] = round(qps["on"] / qps["off"], 3)
        _stamp(f"cache A/B: x{rec['cache_serve_speedup']:.2f} qps@p99 "
               f"with the result cache on (hit rate "
               f"{rec.get('cache_hit_rate', 0):.2f})")
    print(json.dumps(rec), flush=True)


def run_filtered_worker() -> None:
    """filtered_serve phase: CPU-honest pricing of predicate-filtered
    retrieval (docs/ANN.md "Filtered retrieval"). A synthetic store is
    built with a packed attribute word per row laid out so three
    predicates hit fixed selectivities — `lang==0` keeps 1/2 the rows
    (s50), `site in {0}` keeps 1/10 (s10), `recency>=3` keeps 1/100
    (s1). Each arm plus the unfiltered baseline is priced through the
    real serving path (find_qps_at_p99 over a 100%%-filtered workload
    mix), and the exact filtered scan's per-query byte count is recorded
    per arm: the s10 arm's bytes-vs-unfiltered ratio is the <=0.3x
    acceptance gate. An IVF index over the same store prices the
    predicate-intersected posting path: recall@10 vs the exact
    post-filter oracle at each selectivity (the >=0.95 contract)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import shutil

    import numpy as np

    import jax
    from jax.sharding import Mesh

    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.index import attrs as attrs_mod
    from dnn_page_vectors_tpu.index.ivf import IVFIndex
    from dnn_page_vectors_tpu.infer.partition_host import MeshEmbedder
    from dnn_page_vectors_tpu.infer.serve import SearchService
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    from dnn_page_vectors_tpu.loadgen import find_qps_at_p99, make_workload

    dim = int(os.environ.get("BENCH_FILTERED_DIM", "64"))
    shard_rows = int(os.environ.get("BENCH_FILTERED_SHARD_ROWS", "16384"))
    n_shards = int(os.environ.get("BENCH_FILTERED_SHARDS", "4"))
    trial_s = float(os.environ.get("BENCH_FILTERED_TRIAL_S", "1.5"))
    p99_ms = float(os.environ.get("BENCH_FILTERED_P99_MS", "200"))
    iters = int(os.environ.get("BENCH_FILTERED_ITERS", "2"))
    start_qps = float(os.environ.get("BENCH_FILTERED_START_QPS", "16"))
    reps = max(1, int(os.environ.get("BENCH_FILTERED_REPS", "2")))
    distinct = int(os.environ.get("BENCH_FILTERED_DISTINCT", "32"))
    kq = 10
    rows = shard_rows * n_shards
    wdir = "/tmp/dnn_page_vectors_tpu_bench/filtered"
    sdir = os.path.join(wdir, "store")
    _stamp(f"filtered phase: building {rows}-row attributed store "
           f"({n_shards} shards, dim {dim})")
    rng = np.random.default_rng(0)
    shutil.rmtree(wdir, ignore_errors=True)
    store = VectorStore(sdir, dim=dim, shard_size=shard_rows)
    store.init_attrs()
    all_ids = np.arange(rows, dtype=np.int64)
    # deterministic attribute layout -> pinned selectivities (see docstring)
    words = attrs_mod.pack_words(
        lang=(all_ids % 2).astype(np.uint32),
        site=(all_ids % 10).astype(np.uint32),
        recency=np.where(all_ids % 100 == 0, 3, 0).astype(np.uint32))
    for si in range(n_shards):
        lo, hi = si * shard_rows, (si + 1) * shard_rows
        v = rng.standard_normal((shard_rows, dim)).astype(np.float32)
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        store.write_shard(si, all_ids[lo:hi], v, attrs=words[lo:hi])
    store = VectorStore(sdir)
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    qvs = rng.standard_normal((distinct, dim)).astype(np.float32)
    qvs /= np.linalg.norm(qvs, axis=1, keepdims=True)
    qnames = [f"q{i}" for i in range(distinct)]
    qvec = {name: qvs[i:i + 1] for i, name in enumerate(qnames)}

    def _stub_embed(queries):
        return np.concatenate([qvec[q] for q in queries], axis=0)

    class _StubCorpus:
        def page_text(self, i):
            return f"page {i}"

    rec = {"filtered_store_rows": rows, "filtered_dim": dim,
           "filtered_k": kq, "filtered_distinct": distinct}
    arms = (("unfiltered", None),
            ("s50", "lang==0"),
            ("s10", "site in {0}"),
            ("s1", "recency>=3"))
    cfg = get_config("cdssm_toy", {
        "model.out_dim": dim,
        "obs.window_s": trial_s,
        # the cache would absorb the repeats and price the probe, not
        # the filtered scan — this phase wants the scan
        "serve.result_cache": False})
    svc = SearchService(cfg, MeshEmbedder(mesh), None, store,
                        preload_hbm_gb=4.0)
    svc._embed_queries_cached = _stub_embed
    svc.corpus = _StubCorpus()
    # exact post-filter oracle over the DEQUANTIZED store rows (the
    # store holds fp16; comparing against the fp32 originals would
    # charge quantization error to the filter)
    deq = np.concatenate([store._load_entry(e)[1] for e in store.shards()])
    deq = np.asarray(deq, np.float32)
    scores = qvs @ deq.T
    try:
        svc.search(qnames[0], k=kq)            # warm every compiled shape
        for label, pred_text in arms:
            pred = (attrs_mod.Predicate.parse(pred_text)
                    if pred_text else None)
            # per-query scan bytes on the exact path (n=1 so shared
            # gathers are not amortized across a batch)
            probe = 8
            scan = 0
            for i in range(probe):
                _, ids1, sb = svc._topk_view(svc._view, qvs[i:i + 1], 1,
                                             kq, None, predicate=pred)
                scan += int(sb)
            rec[f"filtered_scan_bytes_per_query_{label}"] = scan // probe
            if pred is not None:
                keep = pred.matches(words)
                hits = 0
                for i in range(probe):
                    sc = scores[i].copy()
                    sc[~keep] = -np.inf
                    oracle = np.argsort(-sc)[:kq]
                    _, ids1, _ = svc._topk_view(svc._view, qvs[i:i + 1],
                                                1, kq, None,
                                                predicate=pred)
                    hits += len(set(int(x) for x in ids1[0] if x >= 0)
                                & set(int(o) for o in oracle))
                rec[f"filtered_recall_{label}"] = round(
                    hits / (probe * kq), 4)
            scen = ((label, pred_text, 1.0),) if pred_text else None
            wl = make_workload("poisson", seed=0, distinct=distinct,
                               profile=((kq, None, 1.0),),
                               filter_scenarios=scen)
            _stamp(f"filtered arm={label}: searching qps @ "
                   f"p99<{p99_ms:.0f}ms (best of {reps})")
            best = 0.0
            for _ in range(reps):
                rep = find_qps_at_p99(
                    svc, wl, qnames, p99_target_ms=p99_ms,
                    start=start_qps, iters=iters, duration_s=trial_s,
                    warmup_s=0.5, workers=16)
                best = max(best, rep["qps_at_p99"])
            rec[f"filtered_serve_qps_at_p99_{label}"] = round(best, 2)
            _stamp(f"filtered arm={label}: {best:.1f} qps, "
                   f"{rec[f'filtered_scan_bytes_per_query_{label}']} "
                   f"scan B/query")
    finally:
        svc.close()
    base = rec.get("filtered_scan_bytes_per_query_unfiltered") or 0
    s10 = rec.get("filtered_scan_bytes_per_query_s10")
    if base and s10 is not None:
        rec["filtered_scan_bytes_ratio_s10"] = round(s10 / base, 4)
        _stamp(f"filtered s10 scan ratio: "
               f"x{rec['filtered_scan_bytes_ratio_s10']:.3f} of the "
               f"unfiltered exact bytes (gate <=0.3)")
    # IVF predicate intersection: recall@10 vs the exact post-filter
    # oracle with the predicate applied BEFORE ADC/payload gather
    _stamp("filtered ivf: building IVF index for the intersected path")
    idx = IVFIndex.build(store, mesh, nlist=64, iters=4, seed=0)
    nprobe = int(os.environ.get("BENCH_FILTERED_NPROBE", "16"))
    for label, pred_text in arms[1:]:
        pred = attrs_mod.Predicate.parse(pred_text)
        keep = pred.matches(words)
        sf, if_, st = idx.search(qvs[:8], kq, nprobe=nprobe,
                                 predicate=pred)
        hits = 0
        for i in range(8):
            sc = scores[i].copy()
            sc[~keep] = -np.inf
            oracle = np.argsort(-sc)[:kq]
            hits += len(set(int(x) for x in if_[i] if x >= 0)
                        & set(int(o) for o in oracle))
        rec[f"filtered_ivf_recall_{label}"] = round(hits / (8 * kq), 4)
    _stamp(f"filtered ivf recall@{kq}: "
           + ", ".join(f"{lab}={rec[f'filtered_ivf_recall_{lab}']:.2f}"
                       for lab, _ in arms[1:]))
    print(json.dumps(rec), flush=True)


def _run_filtered() -> dict:
    """Run the filtered_serve phase in a CPU subprocess and return its
    keys — merged into every record like the cache and net phases, so
    the predicate-pricing numbers re-seed the baseline with no TPU."""
    if os.environ.get("BENCH_FILTERED", "1") == "0":
        return {}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--filtered-worker"],
            capture_output=True, text=True,
            timeout=int(os.environ.get("BENCH_FILTERED_TIMEOUT_S", "600")),
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
            env=env)
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "filtered_store_rows" in rec:
                return rec
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"filtered_error":
                (" | ".join(tail[-3:]) if tail
                 else f"rc={proc.returncode}")[:300]}
    except subprocess.TimeoutExpired:
        return {"filtered_error": "filtered worker timed out"}
    except Exception as e:  # noqa: BLE001 — the phase never costs a round
        return {"filtered_error": f"{type(e).__name__}: {e}"[:300]}


def _run_cache() -> dict:
    """Run the result-cache A/B phase in a CPU subprocess and return its
    keys — merged into every record like the partitioned and net phases,
    so the Zipf-head cache numbers re-seed the baseline with no TPU."""
    if os.environ.get("BENCH_CACHE", "1") == "0":
        return {}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--cache-worker"],
            capture_output=True, text=True,
            timeout=int(os.environ.get("BENCH_CACHE_TIMEOUT_S", "600")),
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
            env=env)
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "cache_store_rows" in rec:
                return rec
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"cache_error":
                (" | ".join(tail[-3:]) if tail
                 else f"rc={proc.returncode}")[:300]}
    except subprocess.TimeoutExpired:
        return {"cache_error": "cache worker timed out"}
    except Exception as e:  # noqa: BLE001 — the phase never costs a round
        return {"cache_error": f"{type(e).__name__}: {e}"[:300]}


def _run_net() -> dict:
    """Run the net_serve phase in a CPU subprocess and return its keys —
    merged into every record (null-honest device phases included), so
    this sandbox produces real over-the-wire numbers with no TPU."""
    if os.environ.get("BENCH_NET", "1") == "0":
        return {}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--net-worker"],
            capture_output=True, text=True,
            timeout=int(os.environ.get("BENCH_NET_TIMEOUT_S", "900")),
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
            env=env)
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "net_store_rows" in rec:
                return rec
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"net_error":
                (" | ".join(tail[-3:]) if tail
                 else f"rc={proc.returncode}")[:300]}
    except subprocess.TimeoutExpired:
        return {"net_error": "net worker timed out"}
    except Exception as e:  # noqa: BLE001 — the phase never costs a round
        return {"net_error": f"{type(e).__name__}: {e}"[:300]}


def _run_partitioned() -> dict:
    """Run the host-simulated partitioned phase in a CPU subprocess and
    return its keys (merged into whatever record the wrapper prints —
    including the backend-unreachable null record, which is the point:
    this sandbox produces real numbers for the partitioned phase)."""
    if os.environ.get("BENCH_PARTITIONED", "1") == "0":
        return {}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--partitioned-worker"],
            capture_output=True, text=True,
            timeout=int(os.environ.get("BENCH_PARTITIONED_TIMEOUT_S",
                                       "600")),
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
            env=env)
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "partitioned_store_rows" in rec:
                return rec
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"partitioned_error":
                (" | ".join(tail[-3:]) if tail
                 else f"rc={proc.returncode}")[:300]}
    except subprocess.TimeoutExpired:
        return {"partitioned_error": "partitioned worker timed out"}
    except Exception as e:  # noqa: BLE001 — the phase never costs a round
        return {"partitioned_error": f"{type(e).__name__}: {e}"[:300]}


# ---------------------------------------------------------------------------
# Wrapper: retry the worker while the backend is down; never leak a traceback
# as the only output.
# ---------------------------------------------------------------------------

def _try_parse_last_json(stdout: str) -> dict | None:
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("metric") == METRIC:
            return rec
    return None


def main() -> None:
    deadline = time.time() + TOTAL_BUDGET
    delay = 10.0
    attempt = 0
    last_err = "no attempts ran"
    while True:
        attempt += 1
        # effective bound: the attempt knob, clipped by the remaining budget
        attempt_s = int(min(ATTEMPT_TIMEOUT, max(60, deadline - time.time())))
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--worker"],
                capture_output=True, text=True,
                timeout=attempt_s,
                cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
            )
            rec = _try_parse_last_json(proc.stdout)
            if rec is not None:
                # a parsed record means the required metrics were measured;
                # a nonzero rc after that can only come from optional work
                if proc.returncode != 0:
                    rec.setdefault("long_error", f"worker rc={proc.returncode}")
                _finalize(rec)
                return
            tail = (proc.stderr or proc.stdout or "").strip().splitlines()
            last_err = " | ".join(tail[-3:]) if tail else f"rc={proc.returncode}"
        except subprocess.TimeoutExpired as e:
            # The required metrics print BEFORE the optional long-context
            # sweep: a record recovered from partial stdout means the hang
            # happened in optional work and the primary datapoint is valid.
            partial = e.stdout or b""
            if isinstance(partial, bytes):
                partial = partial.decode(errors="replace")
            rec = _try_parse_last_json(partial)
            if rec is not None:
                rec.setdefault("long_error",
                               f"timed out after {attempt_s}s")
                _finalize(rec)
                return
            # surface the worker's progress stamps so the hung stage is named
            err = e.stderr or b""
            if isinstance(err, bytes):
                err = err.decode(errors="replace")
            tail = " | ".join(err.strip().splitlines()[-3:])
            last_err = (f"worker attempt {attempt} timed out after "
                        f"{attempt_s}s; stderr tail: {tail}")
        if time.time() + delay >= deadline:
            break
        time.sleep(delay)
        delay = min(delay * 2, 120.0)
    # Persistent failure: no record. A `null` headline with rc 0 read as a
    # measurement six records in a row; the error goes to stderr, rc 1.
    print(json.dumps({"metric": METRIC, "error": last_err[-500:],
                      "attempts": attempt}), file=sys.stderr)
    raise SystemExit(1)


def _finalize(rec: dict) -> None:
    """Merge the host-simulated partitioned phase into the worker record,
    re-run the regression gate over the full key set, and print the final
    record (the one the driver parses)."""
    rec.update(_run_partitioned())
    rec.update(_run_net())
    rec.update(_run_cache())
    rec.update(_run_filtered())
    prev = _previous_bench_record()
    _, regs = _regression_gate(rec, prev)
    rec["regressions"] = regs
    _print_delta_table(rec, prev)
    print(json.dumps(rec))


if __name__ == "__main__":
    if "--worker" in sys.argv:
        run_worker()
    elif "--partitioned-worker" in sys.argv:
        run_partitioned_worker()
    elif "--net-worker" in sys.argv:
        run_net_worker()
    elif "--cache-worker" in sys.argv:
        run_cache_worker()
    elif "--filtered-worker" in sys.argv:
        run_filtered_worker()
    else:
        main()
