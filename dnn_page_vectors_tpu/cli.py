"""CLI: one entry point per workflow (SURVEY.md §3 #25; call stacks §4.1-4.4).

  python -m dnn_page_vectors_tpu.cli train --config cdssm_toy
  python -m dnn_page_vectors_tpu.cli embed --config cdssm_toy
  python -m dnn_page_vectors_tpu.cli eval  --config cdssm_toy
  python -m dnn_page_vectors_tpu.cli mine  --config hardneg_v5p64
  python -m dnn_page_vectors_tpu.cli search --config cdssm_toy --query "..."
  python -m dnn_page_vectors_tpu.cli search --config cdssm_toy --queries q.txt
  python -m dnn_page_vectors_tpu.cli index --config cdssm_toy
  python -m dnn_page_vectors_tpu.cli index --config cdssm_toy --pq
  python -m dnn_page_vectors_tpu.cli search --config cdssm_toy --nprobe 8 ...
  python -m dnn_page_vectors_tpu.cli pipeline --config hardneg_v5p64 --rounds 4
  python -m dnn_page_vectors_tpu.cli append --config cdssm_toy \
      --set data.num_pages=12000 --tombstone 17,42
  python -m dnn_page_vectors_tpu.cli refresh --config cdssm_toy
  python -m dnn_page_vectors_tpu.cli migrate --config cdssm_toy
  python -m dnn_page_vectors_tpu.cli maintain --config cdssm_toy --once
  python -m dnn_page_vectors_tpu.cli trace --config cdssm_toy --query "..."
  python -m dnn_page_vectors_tpu.cli serve-metrics --config cdssm_toy
  python -m dnn_page_vectors_tpu.cli serve-metrics --config cdssm_toy --watch 2
  python -m dnn_page_vectors_tpu.cli loadtest --config cdssm_toy \
      --shape poisson --p99-ms 50 --seed 0
  python -m dnn_page_vectors_tpu.cli loadtest --config cdssm_toy \
      --transport socket --partitions 2
  python -m dnn_page_vectors_tpu.cli partition-worker --config cdssm_toy \
      --connect 127.0.0.1:9410 --partition 0 --partitions 2
  python -m dnn_page_vectors_tpu.cli lint
  python -m dnn_page_vectors_tpu.cli lint --write-baseline

Any config field is overridable with --set section.field=value; every flag
round-trips through the Config dataclasses (SURVEY.md §5.6).
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict

from dnn_page_vectors_tpu.config import CONFIGS, get_config
from dnn_page_vectors_tpu.utils.platform import enable_compile_cache


def _parse_overrides(pairs) -> Dict[str, object]:
    out: Dict[str, object] = {}
    for pair in pairs or []:
        key, _, value = pair.partition("=")
        out[key] = value
    return out


def _prepare_store(store_dir, cfg, model_step):
    """Stale-safe store open with the configured geometry (ADVICE r4; see
    infer/vector_store.py:prepare_store)."""
    from dnn_page_vectors_tpu.infer.vector_store import prepare_store
    return prepare_store(store_dir, cfg.model.out_dim,
                         cfg.eval.store_shard_size, cfg.eval.store_dtype,
                         model_step)


def _open_index(cfg, store):
    """The IVF index for eval/mine when serve.index=ivf, or None (exact
    path) — unavailability warns and falls back rather than failing the
    command (docs/ANN.md)."""
    if cfg.serve.index != "ivf":
        return None
    from dnn_page_vectors_tpu.index.ivf import IndexUnavailable, IVFIndex
    from dnn_page_vectors_tpu.utils import faults as _faults
    try:
        return IVFIndex.open(store)
    except IndexUnavailable as e:
        _faults.warn(f"IVF index unavailable ({e}); using exact retrieval")
        return None


def _trainer(cfg):
    from dnn_page_vectors_tpu.train.loop import Trainer
    lookup = None
    if cfg.train.hard_negatives > 0:
        negs_path = os.path.join(cfg.workdir, "hard_negatives.npy")
        if os.path.exists(negs_path):
            # close the mine -> train loop (config 4): feed mined negatives
            from dnn_page_vectors_tpu.mine.ann import HardNegatives
            lookup = HardNegatives.load(negs_path)
        else:
            import sys
            print(f"WARNING: train.hard_negatives="
                  f"{cfg.train.hard_negatives} but {negs_path} does not "
                  "exist — training with in-batch negatives ONLY; run "
                  "'mine' first (or check --workdir)", file=sys.stderr)
    return Trainer(cfg, hard_negative_lookup=lookup)


def _embedder(cfg, trainer, state):
    from dnn_page_vectors_tpu.infer.bulk_embed import BulkEmbedder
    from dnn_page_vectors_tpu.parallel.multihost import inference_mesh
    # single-process: the trainer's mesh; multi-process: a process-local
    # mesh — embed/eval/mine run per-host independent (parallel/multihost.py)
    mesh = inference_mesh(cfg.mesh, trainer.mesh)
    return BulkEmbedder(cfg, trainer.model, state.params, trainer.page_tok,
                        mesh, query_tok=trainer.query_tok)


def _restore_or_init(cfg, trainer):
    """Returns (state, ckpt_manager); state is restored from the latest
    checkpoint when one exists."""
    from dnn_page_vectors_tpu.train.checkpoint import CheckpointManager
    state = trainer.init_state()
    mgr = CheckpointManager(os.path.join(cfg.workdir, "ckpt"))
    if mgr.latest_step() is not None:
        state = mgr.restore(state)
    return state, mgr


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="dnn_page_vectors_tpu")
    ap.add_argument("command", choices=["train", "embed", "eval", "mine",
                                        "search", "pipeline", "configs",
                                        "init-store", "merge-store",
                                        "reset-store", "index", "append",
                                        "migrate", "refresh", "maintain",
                                        "trace",
                                        "serve-metrics", "loadtest",
                                        "partition-worker", "lint"])
    ap.add_argument("--once", action="store_true",
                    help="maintain: run ONE synchronous pass of every "
                         "pillar (janitor, compaction, rebuild) and exit "
                         "instead of looping every maintenance.interval_s")
    # -- lint (graftcheck, docs/ANALYSIS.md) -------------------------------
    ap.add_argument("--root", default=None, metavar="DIR",
                    help="lint: project root to analyze (default: this "
                         "checkout) — used by fixture tests")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="lint: baseline file (default: "
                         "<root>/.graftcheck-baseline.json)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="lint: accept every current finding into the "
                         "baseline file and exit 0")
    ap.add_argument("--changed", nargs="?", const="HEAD", default=None,
                    metavar="REF",
                    help="lint: fast mode — restrict file-scoped rules "
                         "to files changed vs REF (default HEAD: the "
                         "working tree) plus untracked files; "
                         "project-level drift/protocol rules still run "
                         "whole-repo (docs/ANALYSIS.md)")
    ap.add_argument("--tombstone", default=None, metavar="IDS",
                    help="append: comma-separated page ids to DELETE (their "
                         "vectors mask out of every retrieval path)")
    ap.add_argument("--update-ids", default=None, metavar="IDS",
                    help="append: comma-separated existing page ids to "
                         "RE-EMBED into the new generation (old rows "
                         "tombstoned automatically)")
    ap.add_argument("--attrs", nargs="+", default=None, metavar="K=V",
                    help="append: stamp every appended/updated row with "
                         "these attributes — lang=<0-255>, site=<string "
                         "or bucket 0-65535>, recency=<band 0-15> — "
                         "packed into one per-row attribute word "
                         "(docs/ANN.md 'Filtered retrieval'). Refuses on "
                         "a store with no attribute table unless "
                         "--init-attrs is also given")
    ap.add_argument("--init-attrs", dest="init_attrs", action="store_true",
                    help="append: initialize the store's attribute table "
                         "first (records the versioned bit-field layout "
                         "in the manifest; shards written before it read "
                         "as all-zero words)")
    ap.add_argument("--query", default=None,
                    help="search: free-text query to embed and retrieve for")
    ap.add_argument("--filter", dest="filter_expr", default=None,
                    metavar="EXPR",
                    help="search: attribute predicate every result must "
                         "match — 'lang==X', 'site in {a,b}', "
                         "'recency>=band', '&'-joined conjunctions "
                         "(docs/ANN.md 'Filtered retrieval'); applies to "
                         "--query, --queries, and --interactive")
    ap.add_argument("--queries", default=None, metavar="FILE",
                    help="search: batch mode — one query per line, routed "
                         "through search_many (bucket-filling vectorized "
                         "dispatch), one JSON result line per query")
    ap.add_argument("--interactive", action="store_true",
                    help="search: serve queries from stdin, one JSON result "
                         "line each (model + store loaded once)")
    ap.add_argument("--topk", type=int, default=None,
                    help="search: results to return (default eval.recall_k)")
    ap.add_argument("--nprobe", type=int, default=None,
                    help="search/eval/mine: IVF lists probed per query — "
                         "implies serve.index=ivf (docs/ANN.md; shorthand "
                         "for --set serve.index=ivf --set serve.nprobe=N)")
    ap.add_argument("--pq", action="store_true",
                    help="index: train OPQ+PQ compressed posting payloads "
                         "alongside the inverted file (docs/ANN.md) — "
                         "serve.pq_m subspaces, or an automatic ~dim/8 "
                         "when the knob is 0; search then runs on-device "
                         "ADC over m-byte codes with an exact re-rank")
    ap.add_argument("--rounds", type=int, default=2,
                    help="pipeline: train->embed->mine->train rounds")
    ap.add_argument("--config", default="cdssm_toy", choices=sorted(CONFIGS))
    ap.add_argument("--set", dest="overrides", action="append",
                    metavar="section.field=value")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--start", type=int, default=0,
                    help="embed: first page id (store-shard aligned) — for "
                         "manual fleet sharding, one corpus slice per process")
    ap.add_argument("--stop", type=int, default=None,
                    help="embed: one-past-last page id (shard aligned)")
    ap.add_argument("--profile", action="store_true",
                    help="dump a jax.profiler trace under workdir/trace")
    ap.add_argument("--json", action="store_true",
                    help="serve-metrics: emit the JSON registry snapshot "
                         "instead of the Prometheus text exposition")
    ap.add_argument("--watch", type=float, default=None, metavar="N",
                    help="serve-metrics: re-print the live SLO snapshot "
                         "every N seconds (single-line JSON per tick) "
                         "instead of one-shot; Ctrl-C stops")
    # -- loadtest (docs/SERVING.md "SLO methodology") ----------------------
    ap.add_argument("--shape", default="poisson",
                    choices=["poisson", "burst", "closed"],
                    help="loadtest: arrival process — open-loop poisson, "
                         "open-loop on/off burst, or closed-loop workers")
    ap.add_argument("--p99-ms", dest="p99_ms", type=float, default=50.0,
                    help="loadtest: the SLO target — find the max "
                         "sustained QPS with windowed p99 under this")
    ap.add_argument("--seed", type=int, default=0,
                    help="loadtest: workload seed; the same seed replays "
                         "the identical offered-load schedule")
    ap.add_argument("--distinct", type=int, default=64,
                    help="loadtest: distinct queries under the Zipfian "
                         "repeat distribution")
    ap.add_argument("--trial-s", dest="trial_s", type=float, default=None,
                    help="loadtest: measured seconds per trial (default "
                         "obs.window_s, so the rolling window exactly "
                         "turns over)")
    ap.add_argument("--warmup-s", dest="warmup_s", type=float, default=1.0,
                    help="loadtest: per-trial warmup seconds the rolling "
                         "window ages out before the measurement")
    ap.add_argument("--start-qps", dest="start_qps", type=float, default=8.0,
                    help="loadtest: first offered load probed (workers "
                         "for --shape closed)")
    ap.add_argument("--iters", type=int, default=4,
                    help="loadtest: bisection steps after the doubling "
                         "phase brackets the p99 cliff")
    ap.add_argument("--partitions", type=int, default=None, metavar="P",
                    help="loadtest/search: serve.partitions override — "
                         "split the store into P contiguous partitions "
                         "behind the scatter-gather (docs/SCALING.md "
                         "'Partitioned serving'); the report gains a "
                         "per-partition qps/p99/shed block")
    ap.add_argument("--replicas", type=int, default=None, metavar="R",
                    help="loadtest/search: serve.replicas override — R "
                         "health-routed copies of every partition "
                         "(shorthand for --set serve.replicas=R)")
    ap.add_argument("--result-cache", dest="result_cache", default=None,
                    choices=["on", "off"],
                    help="loadtest: generation-keyed result cache A/B "
                         "switch — 'on' enables serve.result_cache (and, "
                         "with --transport socket, the fleet-shared "
                         "CACHE_LOOKUP/CACHE_PUT hop) so the report gains "
                         "a result_cache block (hits, misses, hit_rate, "
                         "bytes; docs/SERVING.md 'Result cache'); 'off' "
                         "forces it off regardless of --set overrides")
    ap.add_argument("--transport", default="inproc",
                    choices=["inproc", "socket"],
                    help="loadtest: 'socket' runs the asyncio front end "
                         "(infer/server.py) over loopback — with "
                         "partitions > 1 it also spawns one "
                         "`partition-worker` SUBPROCESS per replica — and "
                         "points the driver's issue path at the socket "
                         "client, so qps@p99 covers the full network path "
                         "(docs/SERVING.md 'Network front end')")
    ap.add_argument("--front-ends", dest="front_ends", type=int, default=1,
                    metavar="N",
                    help="loadtest: run N socket front ends over ONE "
                         "shared worker fleet (docs/SCALING.md 'Scale-out "
                         "tier') — each gets its own WorkerGateway and "
                         "listener, every worker registers with all N, "
                         "and the driver spreads load across them with a "
                         "seeded client-side balancer; the report gains a "
                         "per-front-end qps/p99 block. Requires "
                         "--transport socket when N > 1")
    ap.add_argument("--balance", default="round_robin",
                    choices=["round_robin", "least_loaded"],
                    help="loadtest: client-side balancing policy across "
                         "--front-ends (seeded by --seed so runs replay)")
    ap.add_argument("--filters", dest="lt_filters", action="store_true",
                    help="loadtest: mix seeded filtered queries into the "
                         "workload (per-scenario predicate profiles over "
                         "the Zipf repeat distribution, docs/ANN.md "
                         "'Filtered retrieval'); the report gains a "
                         "per-scenario qps/p99 block")
    # -- partition-worker (docs/SERVING.md "Network front end") ------------
    ap.add_argument("--connect", default=None, metavar="HOST:PORT",
                    help="partition-worker: the front end's WorkerGateway "
                         "address to register with — comma-separated "
                         "HOST:PORT,... registers this worker with EVERY "
                         "listed gateway (multi-front-end tier)")
    ap.add_argument("--partition", type=int, default=0, metavar="I",
                    help="partition-worker: which partition of the "
                         "--partitions-way balanced split this process "
                         "serves")
    ap.add_argument("--replica", type=int, default=0, metavar="R",
                    help="partition-worker: this process's replica id "
                         "within its partition")
    ap.add_argument("--mutate-every", dest="mutate_every", type=float,
                    default=None, metavar="S",
                    help="loadtest: hot-swap refresh() every S seconds of "
                         "trial time — measures serving UNDER live "
                         "updates (docs/UPDATES.md)")
    ap.add_argument("--mutate-mode", dest="mutate_mode", default="refresh",
                    choices=["refresh", "maintain"],
                    help="loadtest: what --mutate-every fires — 'refresh' "
                         "(the plain hot-swap) or 'maintain' (alternate "
                         "tombstones+refresh with a full maintenance pass: "
                         "compaction + background index rebuilds under "
                         "fire, docs/MAINTENANCE.md)")
    ap.add_argument("--faults", default=None, metavar="PLAN",
                    help="fault-injection plan 'op:kind:at[:count],...' "
                         "(utils/faults.py; shorthand for --set "
                         "faults.plan=...). Off by default.")
    ap.add_argument("--chaos", default=None, metavar="PLAN",
                    help="loadtest: seeded network-chaos schedule armed "
                         "under the query hammer (same grammar as "
                         "--faults, over the wire ops wire_send / "
                         "wire_recv / worker_dial / gateway_accept / "
                         "cache_peer_send and kinds conn_drop / "
                         "frame_delay / frame_trunc / frame_dup). "
                         "Installed AFTER fleet start so setup never "
                         "eats the schedule; the report gains a `chaos` "
                         "block with availability/errors/injected "
                         "counts (docs/ROBUSTNESS.md).")
    args = ap.parse_args(argv)

    if args.command == "configs":
        for name in sorted(CONFIGS):
            print(name)
        return

    if args.command == "lint":
        # graftcheck static analysis (docs/ANALYSIS.md). Dispatches before
        # any model/device/jax import on purpose: the analyzer is
        # stdlib-only and must run on a jax-less box. JSON report on
        # stdout, `file:line` diagnostics on stderr, exit 1 on any
        # non-baselined finding.
        import sys

        from dnn_page_vectors_tpu.tools import analyze as graftcheck
        root = args.root or graftcheck.REPO_ROOT
        baseline = args.baseline or os.path.join(root,
                                                 graftcheck.BASELINE_NAME)
        paths = None
        if args.changed is not None:
            # the pre-commit fast path: file rules only touch what the
            # diff touches; project rules still see the whole repo
            import subprocess as _sp
            try:
                diff = _sp.run(
                    ["git", "diff", "--name-only", args.changed, "--"],
                    capture_output=True, text=True, cwd=root, check=True)
                untracked = _sp.run(
                    ["git", "ls-files", "--others", "--exclude-standard"],
                    capture_output=True, text=True, cwd=root, check=True)
            except (OSError, _sp.CalledProcessError) as e:
                detail = getattr(e, "stderr", "") or str(e)
                print(f"lint --changed: git diff against "
                      f"{args.changed!r} failed: {detail.strip()}",
                      file=sys.stderr)
                raise SystemExit(2)
            paths = sorted(
                p for p in (diff.stdout + untracked.stdout).splitlines()
                if p.endswith(".py"))
        report = graftcheck.analyze(root=root, baseline_path=baseline,
                                    paths=paths)
        if args.write_baseline:
            graftcheck.write_baseline(
                baseline, report.findings + report.baselined)
            print(json.dumps({"baseline": baseline,
                              "entries": len(report.findings)
                              + len(report.baselined)}))
            return
        if paths is not None:
            print(f"lint --changed {args.changed}: file rules over "
                  f"{report.files_scanned} changed file(s); project "
                  "rules whole-repo", file=sys.stderr)
        for f in report.findings:
            print(f.human(), file=sys.stderr)
        for key in report.stale_baseline:
            print(f"stale baseline entry (fixed? remove it): {key}",
                  file=sys.stderr)
        print(json.dumps(report.to_dict(), sort_keys=True))
        if report.exit_code:
            raise SystemExit(report.exit_code)
        return
    if args.command == "search" and not (args.query or args.queries
                                         or args.interactive):
        ap.error("search requires --query TEXT, --queries FILE, "
                 "or --interactive")
    if args.command == "trace" and not (args.query or args.queries):
        ap.error("trace requires --query TEXT or --queries FILE")

    cfg = get_config(args.config, _parse_overrides(args.overrides))
    if args.workdir:
        cfg = cfg.replace(workdir=args.workdir)
    if args.faults is not None:
        import dataclasses as _dc
        cfg = cfg.replace(faults=_dc.replace(cfg.faults, plan=args.faults))
    if args.nprobe is not None:
        import dataclasses as _dc
        cfg = cfg.replace(serve=_dc.replace(cfg.serve, index="ivf",
                                            nprobe=args.nprobe))
    if args.partitions is not None or args.replicas is not None:
        import dataclasses as _dc
        over = {}
        if args.partitions is not None:
            over["partitions"] = max(1, args.partitions)
        if args.replicas is not None:
            over["replicas"] = max(1, args.replicas)
        cfg = cfg.replace(serve=_dc.replace(cfg.serve, **over))
    if getattr(args, "result_cache", None) is not None:
        # --result-cache on/off: the A/B switch over serve.result_cache;
        # 'on' over a socket transport also enables the fleet-shared hop
        # (FLAG_RESULT_CACHE, docs/SERVING.md "Result cache")
        import dataclasses as _dc
        rc_on = args.result_cache == "on"
        cfg = cfg.replace(serve=_dc.replace(
            cfg.serve, result_cache=rc_on,
            result_cache_fleet=bool(rc_on and args.transport == "socket")))

    # fault injection (only when a plan is configured) + the always-on
    # transient-I/O retry policy — every command goes through this
    from dnn_page_vectors_tpu.utils import faults
    faults.install_from_config(cfg)
    enable_compile_cache()

    from dnn_page_vectors_tpu.parallel.mesh import multihost_init
    multihost_init()

    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    from dnn_page_vectors_tpu.utils.profiling import maybe_profile

    store_dir = os.path.join(cfg.workdir, "store")

    # Store-admin commands dispatch BEFORE the trainer build: they need no
    # model, tokenizer, or device — just the store directory and (for
    # init-store) the latest checkpoint step.
    if args.command == "reset-store":
        # Explicit administrative drop of all shards — the CLI escape hatch
        # for the populated-store geometry guard ("cannot switch dtype ...
        # reset() first"), so switching store_dtype/shard_size on a CURRENT
        # (non-stale) store never requires Python. Deliberately its own
        # command: init-store must not silently destroy non-stale vectors.
        store = VectorStore(store_dir)
        n = store.num_vectors
        store.reset()
        print(json.dumps({"store": store_dir, "dropped_vectors": n}))
        return

    if args.command == "merge-store":
        # Manual-fleet step 3: fold writer manifests into the main one once
        # every slice finished. (The jax.distributed path does this itself
        # behind a barrier; readers work without it either way — shards()
        # always sees the union view.)
        store = VectorStore(store_dir)
        store.merge_writers()
        print(json.dumps({"store": store_dir,
                          "shards": len(store.manifest["shards"]),
                          "vectors": store.num_vectors}))
        return

    if args.command == "index":
        # Build/rebuild the IVF ANN index over an embedded store
        # (docs/ANN.md). Needs no model or tokenizer — just the store and
        # a device mesh for the MXU k-means; an existing index is
        # overwritten (build is deterministic for a given store + seed).
        import time as _time

        from dnn_page_vectors_tpu.index.ivf import IVFIndex
        from dnn_page_vectors_tpu.index.pq import auto_pq_m
        from dnn_page_vectors_tpu.parallel.multihost import local_mesh
        store = VectorStore(store_dir)
        # --pq (or a non-zero serve.pq_m knob) turns on compressed
        # posting payloads; the flag alone picks an automatic ~dim/8
        # subspace count for the store's geometry
        pq_m = cfg.serve.pq_m
        if args.pq and not pq_m:
            pq_m = auto_pq_m(store.dim)
        t0 = _time.perf_counter()
        idx = IVFIndex.build(store, local_mesh(cfg.mesh),
                             nlist=cfg.serve.nlist,
                             iters=cfg.serve.kmeans_iters,
                             seed=cfg.data.seed,
                             init=cfg.serve.kmeans_init,
                             balance=cfg.serve.kmeans_balance,
                             pq_m=pq_m, pq_iters=cfg.serve.pq_iters,
                             opq_iters=cfg.serve.pq_opq_iters)
        # init->final imbalance delta: what the seeding bought (k-means++
        # vs the random draw it replaced; docs/ANN.md)
        init_imb = float(idx.manifest.get("init_imbalance", 0.0))
        # raw->balanced delta: what the assignment cap bought (the
        # balanced-init ROADMAP item; 0 when serve.kmeans_balance is off)
        raw_imb = float(idx.manifest.get("imbalance_raw", idx.imbalance))
        pq_sec = idx.manifest.get("pq") or {}
        print(json.dumps({
            "store": store_dir, "vectors": store.num_vectors,
            "nlist": idx.nlist, "imbalance": idx.imbalance,
            "kmeans_init": idx.manifest.get("init"),
            "imbalance_init": init_imb,
            "imbalance_delta": round(init_imb - idx.imbalance, 4),
            "balance_cap": idx.manifest.get("balance_cap", 0),
            "imbalance_raw": raw_imb,
            "imbalance_balance_delta": round(raw_imb - idx.imbalance, 4),
            "pq_m": idx.pq_m,
            "codebook_build_seconds": pq_sec.get("train_seconds"),
            "model_step": idx.model_step,
            "build_seconds": round(_time.perf_counter() - t0, 3),
            "fault_counters": faults.counters()}, sort_keys=True))
        return

    if args.command == "refresh":
        # Bring the IVF index up to date with an appended store
        # (docs/UPDATES.md): incremental posting append in O(new shards),
        # or a drift-triggered full rebuild. Needs no model — just the
        # store and a device mesh for the assignment pass. A serving
        # process picks the result up on its next SearchService.refresh()
        # (or `:refresh` in `search --interactive`).
        from dnn_page_vectors_tpu.index.ivf import IVFIndex
        from dnn_page_vectors_tpu.parallel.multihost import local_mesh
        store = VectorStore(store_dir)
        idx, info = IVFIndex.update(store, local_mesh(cfg.mesh),
                                    rebuild_drift=cfg.updates.rebuild_drift,
                                    nlist=cfg.serve.nlist,
                                    iters=cfg.serve.kmeans_iters,
                                    init=cfg.serve.kmeans_init)
        print(json.dumps({
            "store": store_dir, "vectors": store.num_vectors,
            "store_generation": store.generation,
            "nlist": idx.nlist, "imbalance": idx.imbalance,
            "index_generation": idx.index_generation,
            **info, "fault_counters": faults.counters()}, sort_keys=True))
        return

    if args.command == "maintain":
        # Background maintenance (docs/MAINTENANCE.md): generation
        # compaction once tombstone density crosses the threshold,
        # off-path IVF rebuilds (drift or structural staleness), and the
        # stale-artifact janitor. Needs no model — just the store and a
        # device mesh for the rebuild's k-means. --once runs a single
        # synchronous pass; without it the supervised workers poll every
        # maintenance.interval_s until Ctrl-C, one JSON line per pass
        # that did work.
        import sys
        import time as _time

        from dnn_page_vectors_tpu.maintenance import MaintenanceService
        from dnn_page_vectors_tpu.parallel.multihost import local_mesh
        try:
            store = VectorStore(store_dir)
        except FileNotFoundError:
            raise SystemExit(f"no store at {store_dir}; run 'embed' "
                             "before maintaining")
        ms = MaintenanceService(cfg, store.directory, local_mesh(cfg.mesh))
        if args.once:
            out = ms.run_once()
            print(json.dumps({"store": store_dir, **out,
                              "fault_counters": faults.counters()},
                             sort_keys=True))
            return
        print(json.dumps({"maintaining": store_dir,
                          "interval_s": cfg.maintenance.interval_s}),
              file=sys.stderr, flush=True)
        ms.start()     # the supervised worker pool: one thread per pillar
        seen = {}
        try:
            while True:
                _time.sleep(cfg.maintenance.interval_s)
                snap = ms.stats()
                for pillar, n in snap["passes"].items():
                    if n != seen.get(pillar):
                        seen[pillar] = n
                        print(json.dumps(
                            {pillar: snap["last"].get(pillar), "passes": n},
                            sort_keys=True), flush=True)
        except KeyboardInterrupt:
            ms.close()
        return

    if args.command == "partition-worker":
        # One partition replica as a real process (docs/SERVING.md
        # "Network front end"): opens the store, builds its restricted
        # view over the --partitions-way balanced split, registers with
        # the front end's WorkerGateway at --connect, heartbeats, and
        # answers vector RPCs over its slice until the gateway hangs up.
        # Needs NO model or checkpoint — just the store and a device mesh
        # for staging + the compiled top-k.
        if not args.connect:
            ap.error("partition-worker requires --connect HOST:PORT")
        from dnn_page_vectors_tpu.infer.partition_host import (
            run_partition_worker)
        partitions = max(1, args.partitions or 1)
        run_partition_worker(cfg, store_dir, args.connect,
                             partition=args.partition,
                             partitions=partitions, replica=args.replica)
        return

    if args.command == "init-store":
        # Manual-fleet step 1 (docs/SCALING.md): ONE invocation prepares and
        # stamps the store before N uncoordinated `embed --start/--stop`
        # processes write into it — those processes have no barrier between
        # them, so the reset-if-stale decision must happen exactly once here.
        from dnn_page_vectors_tpu.train.checkpoint import CheckpointManager
        mgr = CheckpointManager(os.path.join(cfg.workdir, "ckpt"))
        model_step = mgr.latest_step() or 0
        mgr.close()
        _prepare_store(store_dir, cfg, model_step)
        print(json.dumps({"store": store_dir, "model_step": model_step}))
        return

    trainer = _trainer(cfg)

    if args.command == "pipeline":
        # train -> embed -> mine -> continue-train rounds (SURVEY.md §4.4)
        from dnn_page_vectors_tpu.train.pipeline import run_pipeline
        state, mgr = _restore_or_init(cfg, trainer)
        steps_per_round = (args.steps if args.steps is not None
                           else max(1, cfg.train.steps // args.rounds))
        with maybe_profile(args.profile, cfg.workdir):
            out = run_pipeline(cfg, rounds=args.rounds,
                               steps_per_round=steps_per_round,
                               trainer=trainer, state=state,
                               ckpt_manager=mgr)
        mgr.save(int(out["state"].step), out["state"], wait=True)
        mgr.close()
        print(json.dumps({"rounds": args.rounds,
                          "recalls": out["recalls"]}, sort_keys=True))
        return

    if args.command == "train":
        state, mgr = _restore_or_init(cfg, trainer)
        # bare re-run after a crash completes to the CONFIGURED total (resume
        # equivalence, §5.4); --steps N explicitly means "N more steps".
        steps = (max(0, cfg.train.steps - int(state.step))
                 if args.steps is None else args.steps)
        with maybe_profile(args.profile, cfg.workdir):
            state, metrics = trainer.train(steps=steps, state=state,
                                           ckpt_manager=mgr)
        mgr.save(int(state.step), state, wait=True)
        mgr.close()
        print(json.dumps({"final": metrics}, sort_keys=True))
        return

    state, mgr = _restore_or_init(cfg, trainer)
    if mgr.latest_step() is None:
        import sys
        print(f"WARNING: no checkpoint under {cfg.workdir}/ckpt — "
              f"'{args.command}' is running with RANDOM params; "
              "run 'train' first (or check --workdir)", file=sys.stderr)
    mgr.close()
    embedder = _embedder(cfg, trainer, state)

    from dnn_page_vectors_tpu.parallel.multihost import barrier, process_info
    pi, pc = process_info()
    model_step = int(state.step)
    fleet = args.start != 0 or args.stop is not None

    if args.command == "migrate":
        # Rolling model migration (docs/MAINTENANCE.md "Rolling model
        # migration"): re-embed the EXISTING store to this checkpoint's
        # model step unit-by-unit — base shard table first, then each
        # appended generation — every unit committed with one atomic
        # manifest flip. The store stays serveable the whole sweep: a
        # SearchService over it serves dual-stamp mid-sweep and picks
        # each flip up on its next refresh(). Contrast `embed`, which
        # RESETS a stale-stamped store and starts over.
        from dnn_page_vectors_tpu.maintenance import (
            migrate_store, purge_stale)
        try:
            store = VectorStore(store_dir)
        except FileNotFoundError:
            raise SystemExit(f"no store at {store_dir}; run 'embed' "
                             "before migrating")
        out = migrate_store(store, trainer.corpus, embedder, model_step,
                            batch_rows=cfg.migrate.batch_rows)
        purged = {}
        if cfg.migrate.purge and out.get("action") == "migrated":
            purged = purge_stale(store, out)
        print(json.dumps({
            "store": store_dir,
            **{k: v for k, v in out.items()
               if k not in ("stale_files", "stale_dirs")},
            **purged, "store_generation": store.generation,
            "fault_counters": faults.counters()}, sort_keys=True))
        return

    if args.command == "embed":
        # vectors from an older checkpoint are stale, not resumable work: a
        # finished shard only counts if it came from the same model step.
        # An unstamped store with shards is ambiguous -> reset (fresh stores
        # have no shards, so resetting them is free). Under multi-process,
        # process 0 prepares/stamps the store before anyone writes. Manual
        # --start/--stop fleet slices must NOT each make that decision (no
        # barrier between them -> a late starter could reset a sibling's
        # fresh shards), so they require a prior `init-store` run instead —
        # and read the store's stamped geometry rather than their own
        # eval.store_shard_size (a slice launched with a divergent override
        # must not silently re-shape the shared store).
        writer = None
        if fleet:
            try:
                store = VectorStore(store_dir)
            except FileNotFoundError:
                raise SystemExit(
                    f"no store at {store_dir}; run 'init-store' once before "
                    "launching --start/--stop embed slices")
            if store.manifest.get("model_step") != model_step:
                raise SystemExit(
                    f"store at {store_dir} is stamped for model step "
                    f"{store.manifest.get('model_step')} but the checkpoint "
                    f"is at {model_step}; run 'init-store' once before "
                    "launching --start/--stop embed slices")
            # writer id: the slice's first shard index (disjoint ranges ->
            # disjoint writer manifests; see VectorStore multi-writer notes)
            writer = args.start // store.manifest["shard_size"]
        elif pi == 0:
            _prepare_store(store_dir, cfg, model_step)
        barrier("store_ready")
        if pc > 1:
            writer = pi          # the jax.distributed multi-writer path
        store = VectorStore(store_dir, dim=cfg.model.out_dim,
                            writer_id=writer)
        # per-stage pipeline breakdown (produce_wait/read/tokenize/h2d/
        # compute/d2h/write) in the final JSON: the operator sees WHICH
        # stage binds the sweep, not just the end-to-end rate
        from dnn_page_vectors_tpu.utils.profiling import PipelineProfiler
        prof = PipelineProfiler(prefix="embed.")
        with maybe_profile(args.profile, cfg.workdir):
            embedder.embed_corpus(trainer.corpus, store,
                                  start=args.start, stop=args.stop,
                                  profiler=prof)
        if pi == 0:
            print(json.dumps({"embedded": store.num_vectors,
                              "model_step": model_step,
                              "tokenize_workers": cfg.data.tokenize_workers,
                              "stages": prof.summary(),
                              "fault_counters": faults.counters()}))
    elif args.command == "append":
        # Live corpus update (docs/UPDATES.md): embed everything past the
        # store's append cursor — grow the corpus first, e.g.
        # --set data.num_pages=<new total> — into a fresh generation, with
        # optional deletions (--tombstone) and in-place page updates
        # (--update-ids), then bring the IVF index up to date when one
        # exists. Serving processes pick the generation up via refresh().
        if pc > 1:
            raise SystemExit("append is a single-process job (one "
                             "generation writer); run it on one host")
        from dnn_page_vectors_tpu.updates import append_corpus
        from dnn_page_vectors_tpu.utils import telemetry
        from dnn_page_vectors_tpu.utils.logging import MetricsLogger
        try:
            store = VectorStore(store_dir)
        except FileNotFoundError:
            raise SystemExit(f"no store at {store_dir}; run 'embed' before "
                             "appending")
        if store.manifest.get("model_step") != model_step:
            raise SystemExit(
                f"store at {store_dir} is stamped for model step "
                f"{store.manifest.get('model_step')} but the checkpoint is "
                f"at {model_step}; appended vectors must share the base "
                "params — re-run 'embed' (full re-embed) instead")
        tomb = [int(x) for x in (args.tombstone or "").split(",")
                if x.strip()]
        upd = [int(x) for x in (args.update_ids or "").split(",")
               if x.strip()]
        attr_word = None
        if args.init_attrs:
            store.init_attrs()
        if args.attrs:
            from dnn_page_vectors_tpu.index import attrs as attrs_mod
            try:
                attr_word = attrs_mod.parse_attr_assignments(args.attrs)
            except attrs_mod.FilterError as e:
                raise SystemExit(f"bad --attrs: {e}")
            if not store.attrs_enabled:
                raise SystemExit(
                    f"store at {store_dir} has no attribute table; pass "
                    "--init-attrs once to create it (older shards then "
                    "read as all-zero attribute words), or drop --attrs")
        with maybe_profile(args.profile, cfg.workdir):
            stats = append_corpus(
                embedder, trainer.corpus, store, tombstone=tomb,
                update_ids=upd, attrs=attr_word,
                log=MetricsLogger(cfg.workdir, echo=False,
                                  registry=telemetry.default_registry()))
        index_info = None
        from dnn_page_vectors_tpu.index.ivf import (
            MANIFEST as _IVF_MANIFEST, IVFIndex, index_dir)
        if cfg.updates.auto_update_index and os.path.exists(
                os.path.join(index_dir(store), _IVF_MANIFEST)):
            try:
                _, index_info = IVFIndex.update(
                    store, embedder.mesh,
                    rebuild_drift=cfg.updates.rebuild_drift,
                    nlist=cfg.serve.nlist, iters=cfg.serve.kmeans_iters,
                    init=cfg.serve.kmeans_init)
            except Exception as e:  # append succeeded; index refresh didn't
                index_info = {"error": f"{type(e).__name__}: {e}"}
        print(json.dumps({"store": store_dir,
                          "store_generation": store.generation,
                          "store_vectors": store.num_vectors, **stats,
                          "index_update": index_info,
                          "fault_counters": faults.counters()},
                         sort_keys=True))
    elif args.command == "eval":
        from dnn_page_vectors_tpu.evals.recall import evaluate_recall
        store = VectorStore(store_dir)
        index = _open_index(cfg, store)
        recall, nq = evaluate_recall(embedder, trainer.corpus, store,
                                     k=cfg.eval.recall_k, index=index,
                                     nprobe=cfg.serve.nprobe)
        if pi == 0:
            print(json.dumps({f"recall@{cfg.eval.recall_k}": recall,
                              "num_queries": nq,
                              "index": ("ivf" if index is not None
                                        else "exact")}, sort_keys=True))
    elif args.command == "search":
        # query-time retrieval over the embedded store (the serving half of
        # call stack §4.3): SearchService loads everything once — params on
        # device, store pre-staged in HBM when it fits — so --interactive
        # answers a stream of queries at per-query encode+top-k cost
        # (VERDICT r3 Weak #6: the old per-invocation cold start is now
        # only paid once).
        if pi != 0:
            # a query service is one host's job; the inference mesh is
            # process-local (no cross-process collectives), so other
            # processes simply exit instead of idling on stdin
            return
        from dnn_page_vectors_tpu.infer.serve import SearchService
        store = VectorStore(store_dir)
        store_step = store.manifest.get("model_step")
        if store_step != int(state.step):
            import sys
            print(f"WARNING: store embedded at model step {store_step} but "
                  f"the restored checkpoint is at step {int(state.step)} — "
                  "query and page vectors come from DIFFERENT params; "
                  "re-run 'embed' for meaningful rankings", file=sys.stderr)
        k = args.topk or cfg.eval.recall_k
        # one-shot queries stream shard-at-a-time (a full HBM preload for a
        # single answer is waste); --interactive / --queries pre-stage the
        # store (a batch file or a stdin session amortizes the staging)
        from dnn_page_vectors_tpu.utils import telemetry
        from dnn_page_vectors_tpu.utils.logging import MetricsLogger
        preload = 4.0 if (args.interactive or args.queries) else 0.0
        svc = SearchService(
            cfg, embedder, trainer.corpus, store, preload_hbm_gb=preload,
            log=MetricsLogger(cfg.workdir, echo=False,
                              registry=telemetry.default_registry()))
        # --profile: the answers' serve.* stages and the device ops in one
        # jax.profiler trace (docs/OBSERVABILITY.md "The combined trace")
        with maybe_profile(args.profile, cfg.workdir):
            if args.queries:
                # batch mode: every line is a query; the whole file goes
                # through ONE search_many (bucket-filling tiled dispatch),
                # one JSON result line per query in input order
                with open(args.queries) as f:
                    queries = [ln.strip() for ln in f if ln.strip()]
                results = svc.search_many(queries, k=k,
                                          filters=args.filter_expr)
                for query, res in zip(queries, results):
                    print(json.dumps({"query": query, "results": res}),
                          flush=True)
                # flushes cache/stage counters to the metrics log
                svc.close()
            elif args.interactive:
                import sys
                svc.warmup(k=k)
                print(json.dumps({"ready": True, "vectors": store.num_vectors,
                                  "hbm_resident": svc.preloaded,
                                  "degraded": svc.degraded,
                                  "fault_counters": faults.counters(),
                                  "latency_ms": round(
                                      svc.warm_latency_ms, 3)}),
                      flush=True)
                for line in sys.stdin:
                    query = line.strip()
                    if not query:
                        continue
                    if query == ":refresh":
                        # zero-downtime hot-swap to the store's current
                        # generation (after an out-of-process `append`):
                        # in-flight queries finish on the old view
                        print(json.dumps({"refreshed": svc.refresh()},
                                         sort_keys=True), flush=True)
                        continue
                    if query == ":metrics":
                        # live JSON snapshot of the serving registry (docs/
                        # OBSERVABILITY.md): flat metrics + typed instruments
                        # with windowed qps/p99 + the lifecycle event ring
                        print(json.dumps(svc.metrics_snapshot(),
                                         sort_keys=True), flush=True)
                        continue
                    print(json.dumps({"query": query,
                                      "results": svc.search(
                                          query, k=k,
                                          filters=args.filter_expr)}),
                          flush=True)
                svc.close()
            else:
                print(json.dumps({"query": args.query,
                                  "degraded": svc.degraded,
                                  "results": svc.search(
                                      args.query, k=k,
                                      filters=args.filter_expr)}))
    elif args.command == "loadtest":
        # SLO harness (docs/SERVING.md "SLO methodology"): replay a seeded
        # traffic shape against a live micro-batched service and
        # binary-search offered load for the max sustained QPS meeting the
        # windowed-p99 target. Every reported number is read from the
        # telemetry registry; trial progress streams to stderr as
        # single-line JSON (the serve-metrics --watch format), the final
        # report is ONE JSON line on stdout.
        if pi != 0:
            return
        import sys

        from dnn_page_vectors_tpu.infer.serve import SearchService
        from dnn_page_vectors_tpu.loadgen import (
            Mutator, find_qps_at_p99, make_workload)
        store = VectorStore(store_dir)
        svc = SearchService(cfg, embedder, trainer.corpus, store,
                            preload_hbm_gb=4.0)
        k = args.topk or cfg.eval.recall_k
        svc.warmup(k=k)
        svc.start_batcher()
        n_fe = max(1, int(args.front_ends))
        if n_fe > 1 and args.transport != "socket":
            raise SystemExit("--front-ends N > 1 requires --transport "
                             "socket (the balancer spreads load across N "
                             "listeners; an in-process service has none)")
        client = None
        fe_svcs = [svc]
        net_servers = []
        gateways = []
        clients = []
        worker_procs = []
        if args.transport == "socket":
            # the over-the-wire path (docs/SERVING.md "Network front
            # end"): asyncio front end over loopback; with partitions a
            # WorkerGateway + one partition-worker SUBPROCESS per
            # replica, so the measured qps@p99 crosses real process
            # boundaries and the RPC fan-out (hedging, liveness routing)
            import subprocess
            import sys as _sys

            from dnn_page_vectors_tpu.infer.partition_host import (
                WorkerGateway)
            from dnn_page_vectors_tpu.infer.server import (
                serve_in_background)
            from dnn_page_vectors_tpu.infer.transport import (
                SocketSearchClient)
            from dnn_page_vectors_tpu.loadgen import BalancedClient
            for _fe in range(1, n_fe):
                # extra front ends (docs/SCALING.md "Scale-out tier"):
                # each is a full SearchService over the SAME store with
                # its own gateway + listener; the shared worker fleet
                # below registers with every one of them
                fe = SearchService(cfg, embedder, trainer.corpus, store,
                                   preload_hbm_gb=4.0)
                fe.warmup(k=k)
                fe.start_batcher()
                fe_svcs.append(fe)
            if svc.partition_set is not None:
                for fe in fe_svcs:
                    gw = WorkerGateway(fe)
                    fe.attach_gateway(gw)
                    gateways.append(gw)
                P = svc.partition_set.partitions
                R = svc.partition_set.replicas
                connect = ",".join(f"{gw.host}:{gw.port}"
                                   for gw in gateways)
                base_cmd = [_sys.executable, "-m",
                            "dnn_page_vectors_tpu.cli", "partition-worker",
                            "--config", args.config,
                            "--workdir", cfg.workdir,
                            "--connect", connect,
                            "--partitions", str(P)]
                for pair in args.overrides or []:
                    base_cmd += ["--set", pair]
                if args.result_cache is not None:
                    # the --result-cache A/B must reach the worker
                    # subprocesses too — they advertise
                    # FLAG_RESULT_CACHE at REGISTER off their own config
                    base_cmd += [
                        "--set",
                        f"serve.result_cache={cfg.serve.result_cache}",
                        "--set", "serve.result_cache_fleet="
                                 f"{cfg.serve.result_cache_fleet}"]
                for wp in range(P):
                    for wr in range(R):
                        worker_procs.append(subprocess.Popen(
                            base_cmd + ["--partition", str(wp),
                                        "--replica", str(wr)],
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL))
                for fe_i, gw in enumerate(gateways):
                    if not gw.wait_for_workers(P * R, timeout_s=120.0):
                        print(json.dumps({
                            "warning": "not every partition worker "
                                       "registered in time; unserved "
                                       "partitions fall back to local "
                                       "views",
                            "front_end": fe_i,
                            "workers_live": len(gw.live_workers()),
                            "expected": P * R}), file=sys.stderr,
                            flush=True)
            for fe_i, fe in enumerate(fe_svcs):
                net_servers.append(serve_in_background(fe,
                                                       front_end=fe_i))
            for ns in net_servers:
                clients.append(SocketSearchClient(
                    ns.host, ns.port,
                    deadline_ms=cfg.serve.deadline_ms,
                    compress=cfg.serve.wire_compress,
                    result_cache=bool(cfg.serve.result_cache
                                      and cfg.serve.result_cache_fleet)))
            client = (clients[0] if n_fe == 1 else
                      BalancedClient(clients, policy=args.balance,
                                     seed=args.seed))
        distinct = max(1, args.distinct)
        queries = [trainer.corpus.query_text(i) for i in range(distinct)]
        scen = None
        if args.lt_filters:
            # seeded filtered-query mix (docs/ANN.md "Filtered
            # retrieval"): the default scenario predicates all match the
            # all-zero attribute word, so the filtered path exercises
            # even on a store whose shards predate init_attrs()
            from dnn_page_vectors_tpu.loadgen.workload import (
                DEFAULT_FILTER_SCENARIOS)
            scen = DEFAULT_FILTER_SCENARIOS
        wl = make_workload(args.shape, seed=args.seed, distinct=distinct,
                           profile=((k, None, 1.0),),
                           filter_scenarios=scen)
        maint = None
        if args.mutate_every and args.mutate_mode == "maintain":
            # maintenance under fire (docs/MAINTENANCE.md): alternate a
            # tombstone burst + hot-swap refresh with a full maintenance
            # pass, so the measured p99 covers compaction and background
            # index rebuilds actually running — lower
            # maintenance.compact_tombstone_density via --set to make
            # compaction fire within a short test
            from dnn_page_vectors_tpu.updates import append_corpus
            maint = svc.start_maintenance(threads=False)
            n_base = max(store.num_vectors, 1)
            tomb_state = {"next": 0}
            tomb_chunk = max(16, n_base // 64)

            def _tombstone_refresh():
                ids = sorted({(tomb_state["next"] + i) % n_base
                              for i in range(tomb_chunk)})
                tomb_state["next"] = (tomb_state["next"]
                                      + tomb_chunk) % n_base
                append_corpus(embedder, trainer.corpus, svc.store,
                              tombstone=ids)
                svc.refresh()

            mut = Mutator(ops=[("tombstone_refresh", _tombstone_refresh),
                               ("maintain", maint.run_once)],
                          period_s=args.mutate_every)
        elif args.mutate_every:
            mut = Mutator(svc.refresh, period_s=args.mutate_every)
        else:
            mut = None
        trial_s = (args.trial_s if args.trial_s is not None
                   else cfg.obs.window_s)
        if args.chaos:
            # arm the seeded chaos schedule only NOW — store build, fleet
            # start, and registration must not eat the plan's scheduled
            # calls (docs/ROBUSTNESS.md "Availability drills")
            faults.install(faults.FaultPlan.parse(args.chaos,
                                                  seed=cfg.faults.seed))
        report = find_qps_at_p99(
            svc, wl, queries, p99_target_ms=args.p99_ms,
            start=args.start_qps, iters=args.iters, duration_s=trial_s,
            warmup_s=args.warmup_s, mutator=mut, client=client,
            progress=lambda line: print(line, file=sys.stderr, flush=True),
            progress_every_s=max(1.0, trial_s / 2.0),
            front_ends=fe_svcs if n_fe > 1 else None)
        if args.transport == "socket":
            final_met = svc.metrics()
            report.update({
                "transport": "socket",
                "listen": ",".join(f"{ns.host}:{ns.port}"
                                   for ns in net_servers),
                **({"transport_totals": final_met["transport"]}
                   if "transport" in final_met else {}),
            })
            if n_fe > 1:
                report["front_ends"] = n_fe
                report["balance_policy"] = args.balance
        if args.lt_filters:
            # per-scenario qps/p99 rides every trial record
            # (loadgen/driver.py "filter_scenarios"); the headline marker
            # here just says the mix was armed
            report["filters"] = [
                {"scenario": name, "predicate": pred, "weight": w}
                for name, pred, w in scen]
        if cfg.serve.result_cache:
            # result-cache block (docs/SERVING.md "Result cache"): run
            # totals straight off the registry — per-trial deltas ride
            # each trial record (loadgen/driver.py)
            rc_met = svc.metrics()
            if "result_cache" in rc_met:
                report["result_cache"] = rc_met["result_cache"]
        if maint is not None:
            final_met = svc.metrics()
            report.update({
                "mutate_mode": args.mutate_mode,
                "maintenance": maint.stats(),
                "full_rebuilds": final_met["full_rebuilds"],
                "tombstone_density": final_met["tombstone_density"],
                "reclaimable_bytes": final_met["reclaimable_bytes"],
            })
        if svc.partition_set is not None:
            # partitioned topology + routing health (docs/SCALING.md):
            # per-partition qps/p99/shed/degraded-serve counts, plus the
            # service-level routing counters
            part_met = svc.metrics()
            report.update({
                "serve_partitions": part_met["serve_partitions"],
                "serve_replicas": part_met["serve_replicas"],
                "replica_shed": part_met["replica_shed"],
                "partition_degraded": part_met["partition_degraded"],
                "partitions": part_met["partitions"],
            })
        if args.chaos:
            # the availability drill's verdict: fraction of offered
            # queries ANSWERED (sheds excluded both sides — a shed is
            # deliberate backpressure, not lost availability)
            trials = report.get("trials", [])
            sent = sum(t.get("requests_sent", 0) for t in trials)
            errs = sum(t.get("errors", 0) for t in trials)
            sheds = sum(t.get("transport", {}).get("client_sheds", 0)
                        for t in trials)
            offered = max(sent - sheds, 1)
            report["chaos"] = {
                "plan": args.chaos,
                "offered": sent,
                "sheds": sheds,
                "errors": errs,
                "availability": round(
                    max(sent - sheds - errs, 0) / offered, 6),
                "injected": {key: v for key, v in faults.counters().items()
                             if key.startswith("injected_")
                             or key == "worker_reconnect"},
            }
        for c in clients:
            c.close()
        for ns in net_servers:
            ns.close()
        for proc in worker_procs:
            proc.terminate()
        for proc in worker_procs:
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — a stuck worker gets killed
                proc.kill()
        for gw in gateways:
            gw.close()
        for fe in fe_svcs[1:]:
            fe.close()
        svc.close()
        report.update({
            "store_vectors": store.num_vectors,
            "query_batch": svc.query_batch,
            "k": k,
            "serve_index": cfg.serve.index,
            "batch_window_adaptive": cfg.serve.batch_window_adaptive,
            "batch_window_ms": round(svc.batch_window_ms, 3),
            "recompiles": svc.recompiles,
            "warm_latency_ms": round(svc.warm_latency_ms, 3),
            "fault_counters": faults.counters(),
        })
        print(json.dumps(report))
    elif args.command in ("trace", "serve-metrics"):
        # Observability endpoints (docs/OBSERVABILITY.md). `trace` runs the
        # given queries under request-scoped tracing and exports the span
        # trees as Chrome/Perfetto trace_event JSON; `serve-metrics` probes
        # the service once and prints the Prometheus text exposition (or
        # the JSON registry snapshot with --json).
        if pi != 0:
            return
        from dnn_page_vectors_tpu.infer.serve import SearchService
        store = VectorStore(store_dir)
        svc = SearchService(cfg, embedder, trainer.corpus, store,
                            preload_hbm_gb=0.0)
        k = args.topk or cfg.eval.recall_k
        if args.command == "serve-metrics":
            # one probe query so rate/latency instruments expose live
            # numbers, not an all-zero registry
            svc.search_many([trainer.corpus.query_text(0)], k=k)
            if args.watch:
                # live mode: one single-line JSON tick of the windowed SLO
                # view every N seconds (the same line format the loadtest
                # driver emits as trial progress); Ctrl-C exits clean
                import time as _time

                from dnn_page_vectors_tpu.loadgen import snapshot_line
                try:
                    while True:
                        print(snapshot_line(svc), flush=True)
                        _time.sleep(args.watch)
                except KeyboardInterrupt:
                    pass
                return
            if args.json:
                print(json.dumps(svc.metrics_snapshot(), sort_keys=True))
            else:
                print(svc.prometheus_text(), end="")
            return
        if args.queries:
            with open(args.queries) as f:
                queries = [ln.strip() for ln in f if ln.strip()]
        else:
            queries = [args.query]
        for query in queries:       # one trace (and one span tree) each
            svc.search_many([query], k=k)
        out_path = os.path.join(cfg.workdir, "trace_events.json")
        with open(out_path, "w") as f:
            json.dump(svc.tracer.chrome_trace(), f)
        print(json.dumps({
            "trace_file": out_path,
            "traces": len(svc.tracer.traces()),
            "spans": len(svc.tracer.chrome_trace()["traceEvents"]),
            "slow_queries": len(svc.tracer.slow_queries()),
            "slow_ms": cfg.obs.slow_ms}, sort_keys=True))
    elif args.command == "mine":
        from dnn_page_vectors_tpu.mine.ann import mine_hard_negatives
        store = VectorStore(store_dir)
        index = _open_index(cfg, store)
        out = os.path.join(cfg.workdir, "hard_negatives.npy")
        # out_path at any process count: the miner's writer-slice protocol
        # keeps peak host memory O(query_block) and barriers internally
        negs = mine_hard_negatives(embedder, trainer.corpus, store,
                                   num_negatives=cfg.train.hard_negatives or 7,
                                   out_path=out, index=index,
                                   nprobe=cfg.serve.nprobe)
        if pi == 0:
            print(json.dumps({"mined": list(negs.table.shape), "path": out,
                              "index": ("ivf" if index is not None
                                        else "exact")}))


if __name__ == "__main__":
    main()
