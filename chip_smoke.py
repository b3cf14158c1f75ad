#!/usr/bin/env python3
"""Chip smoke: the main path once, on the TPU, at bert-mini's full width.

    python chip_smoke.py            # one chip: device, train, embed -> eval,
                                    # serve, flash, barrier
    python chip_smoke.py --chips 4  # four chips: ONLY data-parallel train +
                                    # row-sharded top-k vs one device

One process, no child that needs the chip. Every stage goes through
`dnn_page_vectors_tpu.cli.main([...])` in-process, in one workdir under the
system temp directory that is removed on exit. Each phase prints one JSON
line as it ends and is fatal on failure; the LAST line of stdout is
`{"ok": true, "device": {...}}` and is printed only when every phase
passed. No TPU visible -> exit 1, nothing computed.

The phases are plain functions that take their sizes; `main()` alone fixes
the full widths and enforces the device gate.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import shutil
import sys
import tempfile
import time

CONFIG = "bert_mini_v5p16"
# bert-mini's published widths (config.py:bert_mini_v5p16) — asserted from
# the resolved config; the overrides below cut scale only.
WIDTHS = {"model.encoder": "bert", "model.num_layers": 4,
          "model.num_heads": 4, "model.model_dim": 256,
          "model.mlp_dim": 1024, "model.out_dim": 256,
          "model.dtype": "bfloat16", "model.attention": "dense",
          "data.tokenizer": "wordpiece", "data.vocab_size": 30_522,
          "data.page_len": 64, "data.query_len": 16}

_compile_s = 0.0        # backend compile seconds since the last phase line


def _emit(phase: str, **fields) -> None:
    global _compile_s
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"phase": phase, **fields,
                      "compile_s": round(_compile_s, 2),
                      "peak_hbm_gb": round(
                          stats.get("peak_bytes_in_use", 0) / 2**30, 3)}),
          flush=True)
    _compile_s = 0.0


def _on_duration(event: str, seconds: float, **_) -> None:
    global _compile_s
    if event.endswith("backend_compile_duration"):
        _compile_s += seconds


def _cli(argv, stdin_text: str = "") -> list:
    """cli.main(argv) in-process; returns the JSON lines it printed."""
    from dnn_page_vectors_tpu import cli
    out = io.StringIO()
    stdin, sys.stdin = sys.stdin, io.StringIO(stdin_text)
    try:
        with contextlib.redirect_stdout(out):
            cli.main(argv)
    finally:
        sys.stdin = stdin
    return [json.loads(ln) for ln in out.getvalue().splitlines()
            if ln.startswith("{")]


def _argv(command: str, workdir: str, overrides: dict, *extra) -> list:
    argv = [command, "--config", CONFIG, "--workdir", workdir, *extra]
    for key, value in overrides.items():
        argv += ["--set", f"{key}={value}"]
    return argv


def _bytes_in_use(devs) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devs]


def _must(cond, message: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {message}")


def resolve_config(overrides: dict):
    from dnn_page_vectors_tpu.config import get_config
    return get_config(CONFIG, {k: str(v) for k, v in overrides.items()})


# -- phases -----------------------------------------------------------------

def phase_device(chips: int):
    """The device gate. Exits 1 unless JAX's default backend is `chips`
    TPU devices whose kind has a row in both peak tables."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        raise SystemExit(1)
    _must(len(devs) == chips, f"{len(devs)} devices visible, want {chips}")
    from dnn_page_vectors_tpu.utils.flops import (
        device_peak_flops, device_peak_hbm_bps)
    peak = device_peak_flops(devs[0])       # raises on an unlisted kind
    bw = device_peak_hbm_bps(devs[0])
    _emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
          count=len(devs), jax=jax.__version__,
          peak_bf16_tflops=peak / 1e12, peak_hbm_gbps=bw / 1e9,
          compile_cache=jax.config.jax_compilation_cache_dir)
    return devs[0], peak


def phase_train(workdir: str, overrides: dict) -> None:
    """`cli train`: finite falling loss, HBM in every metrics line, the
    async orbax checkpoint on disk at the last step."""
    steps = resolve_config(overrides).train.steps
    t0 = time.perf_counter()
    final = _cli(_argv("train", workdir, overrides))[-1]["final"]
    wall = time.perf_counter() - t0
    with open(os.path.join(workdir, "metrics.jsonl")) as f:
        lines = [json.loads(ln) for ln in f]
    _must(len(lines) >= 2, "fewer than two logged train steps")
    for m in lines:
        _must(math.isfinite(m["loss"]), f"loss not finite at {m['step']}")
        _must("hbm_gb_in_use" in m,
              f"no hbm_gb_in_use in the metrics line of step {m['step']}")
    _must(lines[-1]["loss"] < lines[0]["loss"],
          f"loss did not fall: {lines[0]['loss']} -> {lines[-1]['loss']}")
    _must(final["step"] == steps, f"ended at step {final['step']}")
    from dnn_page_vectors_tpu.train.checkpoint import CheckpointManager
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"))
    saved = mgr.latest_step()
    mgr.close()
    _must(saved == steps, f"latest checkpoint is step {saved}, want {steps}")
    _emit("train", steps=steps,
          batch_size=int(overrides["train.batch_size"]),
          loss_first=lines[0]["loss"], loss_last=lines[-1]["loss"],
          in_batch_acc_last=lines[-1].get("in_batch_acc"),
          hbm_gb_in_use=lines[-1].get("hbm_gb_in_use"),
          pages_per_sec_per_chip=lines[-1]["pages_per_sec_per_chip"],
          mfu=lines[-1].get("mfu"), checkpoint_step=saved,
          wall_s=round(wall, 2))


def phase_embed_eval(workdir: str, overrides: dict,
                     min_recall_over_chance: float) -> None:
    """`cli embed` of every page from the RESTORED checkpoint, `cli eval`:
    Recall@k far above chance, with the native tokenizer in use."""
    cfg = resolve_config(overrides)
    steps = cfg.train.steps
    t0 = time.perf_counter()
    emb = _cli(_argv("embed", workdir, overrides))[-1]
    embed_s = time.perf_counter() - t0
    _must(emb["embedded"] == cfg.data.num_pages,
          f"embedded {emb['embedded']} of {cfg.data.num_pages} pages")
    _must(emb["model_step"] == steps,
          f"embedded from step {emb['model_step']}, not the restored "
          f"checkpoint of step {steps}")
    # same class, same cached vocab as the tokenizer the stages just used
    from dnn_page_vectors_tpu.data.loader import build_corpus, build_tokenizer
    _, page_tok = build_tokenizer(cfg, build_corpus(cfg), cache_dir=workdir)
    _must(page_tok.vocab_size == cfg.data.vocab_size, "vocab size drifted")
    _must(page_tok._native_encoder() is not None,
          "the native (C++) subword encoder is not in use: the tokenizer "
          "fell back to pure Python")
    t0 = time.perf_counter()
    ev = _cli(_argv("eval", workdir, overrides))[-1]
    eval_s = time.perf_counter() - t0
    k = cfg.eval.recall_k
    recall = ev[f"recall@{k}"]
    chance = k / cfg.data.num_pages
    _must(recall >= min_recall_over_chance * chance,
          f"recall@{k} {recall} is not {min_recall_over_chance}x chance "
          f"({chance})")
    _emit("embed_eval", embedded=emb["embedded"],
          model_step=emb["model_step"], native_tokenizer=True,
          **{f"recall@{k}": recall}, chance=chance,
          recall_over_chance=round(recall / chance, 1),
          num_queries=ev["num_queries"], index=ev["index"],
          embed_s=round(embed_s, 2), eval_s=round(eval_s, 2),
          embed_stages=emb["stages"])


def phase_serve(workdir: str, overrides: dict, query_ids, k: int,
                tie_tol: float = 1e-3) -> None:
    """`cli search --interactive` over stdin: HBM-resident, not degraded,
    no staging fault or index fallback, ids equal to a numpy exact top-k
    over the stored vectors."""
    import numpy as np

    from dnn_page_vectors_tpu import cli
    from dnn_page_vectors_tpu.infer.vector_store import VectorStore
    cfg = resolve_config(overrides).replace(workdir=workdir)
    trainer = cli._trainer(cfg)
    queries = [trainer.corpus.query_text(i) for i in query_ids]
    t0 = time.perf_counter()
    lines = _cli(_argv("search", workdir, overrides, "--interactive",
                       "--topk", str(k)),
                 stdin_text="\n".join(queries + [":metrics"]) + "\n")
    wall = time.perf_counter() - t0
    _must(len(lines) == len(queries) + 2, f"{len(lines)} serve lines")
    ready, answers, snap = lines[0], lines[1:-1], lines[-1]
    _must(ready.get("ready") is True, f"not ready: {ready}")
    _must(ready["hbm_resident"] is True, "store is not HBM-resident")
    _must(ready["degraded"] is False, "service came up degraded")
    _must(ready["vectors"] == cfg.data.num_pages, "store size drifted")
    _must(snap["metrics"]["serve_degraded"] is False, "service degraded")
    faults = snap["metrics"].get("fault_counters", {})
    _must(not faults.get("serve_stage_faults"),
          f"HBM staging faulted: {faults}")
    bad = [e for e in snap["events"]
           if e["event"] in ("degraded", "index_degraded")]
    _must(not bad, f"fallback events fired: {bad}")
    # the reference: numpy exact top-k over the same stored vectors, with
    # the query vectors from the same restored checkpoint
    state, mgr = cli._restore_or_init(cfg, trainer)
    mgr.close()
    qv = np.asarray(cli._embedder(cfg, trainer, state).embed_texts(
        queries, tower="query"), np.float32)
    store = VectorStore(os.path.join(workdir, "store"))
    ids, vecs = zip(*[(np.asarray(s[0]), np.asarray(s[1], np.float32))
                      for s in store.iter_shards()])
    ids, vecs = np.concatenate(ids), np.concatenate(vecs)
    scores = qv @ vecs.T
    row_of = {int(pid): row for row, pid in enumerate(ids)}
    exact = gold_hits = 0
    for qi, ans in enumerate(answers):
        _must(ans["query"] == queries[qi], "answers out of order")
        got = [r["page_id"] for r in ans["results"]]
        want = ids[np.argsort(-scores[qi], kind="stable")[:k]].tolist()
        exact += got == want
        gold_hits += int(query_ids[qi]) in got
        # ids must agree except where two scores tie within `tie_tol`
        for g, w in zip(got, want):
            _must(g == w or abs(scores[qi, row_of[g]]
                                - scores[qi, row_of[w]]) <= tie_tol,
                  f"query {qi}: served {got}, numpy exact top-k {want}")
    _emit("serve", queries=len(queries), topk=k, hbm_resident=True,
          degraded=False, ids_equal_numpy=f"{exact}/{len(queries)}",
          tie_tol=tie_tol, gold_in_topk=gold_hits,
          warm_latency_ms=ready["latency_ms"], wall_s=round(wall, 2))


def phase_flash(B: int, H: int, L: int, Dh: int, seed: int,
                fwd_tol: float = 2e-2, grad_tol: float = 5e-2) -> None:
    """flash_attention forward + grad, compiled (interpret=False passed
    explicitly), with and without bias, against reference_attention on the
    same device. Tolerances are max-abs error over the reference's
    max-abs value."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dnn_page_vectors_tpu.ops.flash_attention import (
        flash_attention, reference_attention)
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, L, Dh)), jnp.bfloat16)
               for _ in range(3))
    kv_mask = jnp.asarray(np.arange(L)[None, :] < rng.integers(
        L // 2, L + 1, size=(B, 1)))
    bias_arr = jnp.asarray(rng.standard_normal((H, L, L)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, H, L, Dh)), jnp.float32)

    def rel_err(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        _must(np.isfinite(got).all(), "non-finite flash output")
        return float(np.abs(got - want).max() / np.abs(want).max())

    def flash(q, k, v, bias=None):
        return flash_attention(q, k, v, kv_mask, bias=bias, interpret=False)

    def ref(q, k, v, bias=None):
        return reference_attention(q, k, v, kv_mask, bias=bias)

    def loss_of(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    report = {}
    for name, bias in (("nobias", None), ("bias", bias_arr)):
        args = (q, k, v) if bias is None else (q, k, v, bias)
        argnums = tuple(range(len(args)))
        fwd = jax.jit(flash).lower(*args).compile()
        bwd = jax.jit(jax.grad(loss_of(flash), argnums)).lower(
            *args).compile()
        for kind, exe in (("forward", fwd), ("grad", bwd)):
            _must("tpu_custom_call" in exe.as_text(),
                  f"no tpu_custom_call in the compiled flash {kind}")
        fwd_err = rel_err(fwd(*args), jax.jit(ref)(*args))
        _must(fwd_err <= fwd_tol, f"flash {name} forward off by {fwd_err}")
        want = jax.jit(jax.grad(loss_of(ref), argnums))(*args)
        errs = [rel_err(g, r) for g, r in zip(bwd(*args), want)]
        _must(max(errs) <= grad_tol, f"flash {name} grads off by {errs}")
        report[name] = {"fwd_err": fwd_err, "grad_err": dict(
            zip(("dq", "dk", "dv", "dbias"), errs))}
    _emit("flash", shape=[B, H, L, Dh], interpret=False,
          tpu_custom_call=True, fwd_tol=fwd_tol, grad_tol=grad_tol,
          **report)


def phase_barrier(n: int, chain: int, peak_flops: float,
                  reps: int = 3) -> None:
    """One large bf16 matmul chain timed to three barriers:
    jax.block_until_ready, utils.platform.hard_sync, and pulling one
    element to the host. A barrier that returns at dispatch reads over
    peak; any over 105% of the table's peak fails."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dnn_page_vectors_tpu.utils.platform import hard_sync
    key = jax.random.key(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    # entries ~ N(0, 1/n): the chain keeps its scale, no bf16 overflow
    b = (jax.random.normal(jax.random.fold_in(key, 1), (n, n), jnp.float32)
         / np.sqrt(n)).astype(jnp.bfloat16)

    @jax.jit
    def chain_fn(a, b):
        return jax.lax.fori_loop(0, chain, lambda _, x: x @ b, a)

    barriers = {
        "block_until_ready": jax.block_until_ready,
        "hard_sync": hard_sync,
        "host_pull": lambda y: np.asarray(jax.device_get(y.ravel()[:1])),
    }
    np.asarray(chain_fn(a, b))                      # compile + warm
    flops = 2.0 * n ** 3 * chain
    tflops = {}
    for name, wait in barriers.items():
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            wait(chain_fn(a, b))
            best = min(best, time.perf_counter() - t0)
        tflops[name] = flops / best / 1e12
        _must(flops / best <= 1.05 * peak_flops,
              f"{name} reads {tflops[name]:.1f} TFLOP/s, over 105% of the "
              f"{peak_flops / 1e12:.0f} TFLOP/s peak: it does not wait "
              "for the device")
    _emit("barrier", matmul=[n, n, n], chain=chain, dtype="bfloat16",
          peak_tflops=peak_flops / 1e12,
          **{f"{name}_tflops": round(v, 2) for name, v in tflops.items()})


def phase_data_parallel(root: str, overrides: dict, chips: int, steps: int,
                        store_rows: int, queries: int, k: int, seed: int,
                        loss_rtol: float = 2e-2) -> None:
    """Data-parallel train over `chips` devices against the same global
    batch and seed on one of them, and the row-sharded exact top-k against
    the single-device answer."""
    import jax
    import numpy as np
    devs = jax.devices()[:chips]

    losses = {}
    for n in (chips, 1):
        wd = os.path.join(root, f"dp{n}")
        os.makedirs(wd, exist_ok=True)
        tok = f"tokenizer_{WIDTHS['data.tokenizer']}.json"
        if n == 1:      # same vocab, trained once
            shutil.copy(os.path.join(root, f"dp{chips}", tok),
                        os.path.join(wd, tok))
        ov = {**overrides, "mesh.data": n, "train.steps": steps,
              "train.log_every": 1}
        _cli(_argv("train", wd, ov))
        with open(os.path.join(wd, "metrics.jsonl")) as f:
            losses[n] = [json.loads(ln)["loss"] for ln in f]
        _must(len(losses[n]) == steps, f"{len(losses[n])} logged steps")
        if n == chips:
            # the mesh really spans the chips: a strict Trainer on the same
            # config places one batch and the state; look at the shards
            from dnn_page_vectors_tpu.train.loop import Trainer
            trainer = Trainer(resolve_config(ov).replace(workdir=wd))
            _must(trainer.mesh.shape["data"] == chips, "mesh shrank")
            batches = trainer.batches()
            batch = next(batches)
            on = {s.device for s in batch["page"].addressable_shards}
            _must(len(on) == chips,
                  f"batch shards sit on {len(on)} device(s), want {chips}")
            rows = {s.data.shape[0]
                    for s in batch["page"].addressable_shards}
            _must(rows == {int(ov["train.batch_size"]) // chips},
                  f"per-device batch rows {rows}")
            train_bytes = _bytes_in_use(devs)
            _must(all(b > 0 for b in train_bytes),
                  f"a device holds no bytes after the step: {train_bytes}")
            batches.close()
            del batch, batches, trainer
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(losses[chips], losses[1]))
    _must(worst <= loss_rtol,
          f"losses differ by {worst}: {losses[chips]} vs {losses[1]}")

    # row-sharded exact top-k (ops/topk.py stage_shard, P("data"))
    from dnn_page_vectors_tpu.config import MeshConfig
    from dnn_page_vectors_tpu.ops.topk import sharded_topk, stage_shard
    from dnn_page_vectors_tpu.parallel.mesh import make_mesh
    dim = WIDTHS["model.out_dim"]
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((store_rows, dim), np.float32)
    vecs = (vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
            ).astype(np.float16)
    q = rng.standard_normal((queries, dim), np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    got = {}
    for n in (chips, 1):
        mesh = make_mesh(MeshConfig(data=n, strict=True))
        before = _bytes_in_use(devs)
        pages, _ = stage_shard(vecs, store_rows, dim, mesh, words=True)
        if n == chips:
            on = {s.device for s in pages.addressable_shards}
            _must(len(on) == chips,
                  f"store shards sit on {len(on)} device(s), want {chips}")
            grew = [b - a for a, b in zip(before, _bytes_in_use(devs))]
            _must(all(g >= store_rows // chips * dim * 2 for g in grew),
                  f"staged store bytes per device: {grew}")
        sc, idx = sharded_topk(q, pages, mesh, k=k, valid=store_rows)
        got[n] = (np.asarray(sc), np.asarray(idx))
        del pages
    _must((got[chips][1] == got[1][1]).all(), "sharded top-k ids differ "
          "from the single-device answer")
    want = np.argsort(-(q @ vecs.astype(np.float32).T), axis=1,
                      kind="stable")[:, :k]
    _must((got[1][1] == want).all(), "top-k ids differ from numpy")
    _emit("data_parallel", chips=chips, steps=steps,
          batch_size=int(overrides["train.batch_size"]),
          losses_dp=losses[chips], losses_one=losses[1],
          loss_max_rel_diff=worst, loss_rtol=loss_rtol,
          train_bytes_in_use=train_bytes, store_rows=store_rows,
          topk_ids_equal=True,
          topk_score_max_diff=float(np.abs(got[chips][0]
                                           - got[1][0]).max()))


# -- driver -----------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the data-parallel phase and its "
                         "single-device comparison, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from dnn_page_vectors_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    t0 = time.perf_counter()
    dev, peak = phase_device(args.chips)

    overrides = {"data.num_pages": 100_000, "data.seed": args.seed,
                 "train.seed": args.seed, "train.batch_size": 512,
                 "train.steps": 300, "train.log_every": 50,
                 "mesh.data": 1, "mesh.strict": "true"}
    if args.chips == 4:
        overrides["train.batch_size"] = 2_048
    cfg = resolve_config(overrides)
    for key, want in WIDTHS.items():
        section, field = key.split(".")
        got = getattr(getattr(cfg, section), field)
        _must(got == want, f"{key} resolved to {got!r}, published {want!r}")

    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            phase_data_parallel(root, overrides, chips=4, steps=8,
                                store_rows=1 << 20, queries=8, k=10,
                                seed=args.seed)
        else:
            phase_train(root, overrides)
            phase_embed_eval(root, overrides, min_recall_over_chance=100.0)
            phase_serve(root, overrides, query_ids=(3, 1_234, 56_789, 99_999),
                        k=cfg.eval.recall_k)
            # bert_long_sp's attention shape (config.py:bert_long_sp)
            phase_flash(B=8, H=8, L=1024, Dh=64, seed=args.seed)
            phase_barrier(n=8192, chain=16, peak_flops=peak)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"phase": "total",
                      "wall_s": round(time.perf_counter() - t0, 1)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
