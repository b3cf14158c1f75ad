"""Config system: dataclasses + the five canonical named configs.

The five configs mirror BASELINE.json:6-12 verbatim (SURVEY.md §3 #24):
  1. cdssm_toy      — CDSSM char-trigram CNN, 10k-page toy corpus, single CPU
  2. kim_cnn_v5e8   — Word-CNN (Kim-CNN) page encoder, 1M pages, DP pjit, v5e-8
  3. bert_mini_v5p16 — two-tower BERT-mini with in-batch negatives, v5p-16
  4. hardneg_v5p64  — ANN-mined hard-negative contrastive training, 100M pages
  5. mt5_multilingual — mT5-base page encoder + cross-lingual retrieval eval

Every CLI flag round-trips through these dataclasses (SURVEY.md §5.6).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Host-side data pipeline settings."""
    tokenizer: str = "trigram"       # trigram | word | wordpiece | sentencepiece
    corpus: str = "toy"              # toy | jsonl:<path>
    num_pages: int = 10_000          # corpus size (toy generator)
    query_len: int = 16              # max words per query
    page_len: int = 64               # max words per page
    trigrams_per_word: int = 8       # K trigram ids kept per word (CDSSM)
    trigram_buckets: int = 16_384    # hash-bucket vocab for char trigrams
    vocab_size: int = 30_000         # word / subword vocab size
    languages: int = 1               # >1: cross-lingual toy corpus (config 5)
    num_topics: int = 64             # toy-corpus topics; fewer => more
                                     # near-duplicate pages per topic, harder
                                     # within-topic retrieval (mining tests)
    # >1 chunks subword batch encoding across host threads (the C++ matcher
    # releases the GIL). One thread feeds one chip (~164k pages/s measured);
    # multi-chip hosts (v5e-8) need roughly one thread per 1-2 chips.
    tokenize_threads: int = 1
    # Tokenizer WORKER pool: >1 runs the per-batch read+tokenize of the
    # bulk-embed sweep and the train batcher on N concurrent producer
    # threads, reassembled in batch order (data/loader.py
    # ordered_parallel_map) — batches stay byte-identical to the serial
    # path. Orthogonal to tokenize_threads (intra-batch C++ subword
    # chunking): workers parallelize ACROSS batches, threads WITHIN one.
    # 1 = serial producer.
    tokenize_workers: int = 4
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Encoder zoo settings. `encoder` selects the family."""
    # cdssm | kim_cnn | lstm | bert | t5 | glm4_moe_lite | granitemoehybrid
    # | falcon_h1 | qwen3_next
    encoder: str = "cdssm"
    embed_dim: int = 128             # token/word embedding width
    out_dim: int = 128               # final vector dimension (both towers)
    # conv families
    conv_widths: Tuple[int, ...] = (3,)        # cdssm: (3,); kim_cnn: (3, 4, 5)
    conv_channels: int = 256
    # transformer families
    num_layers: int = 4
    num_heads: int = 4
    mlp_dim: int = 1024
    model_dim: int = 256
    dropout: float = 0.1
    # dense | flash | ring. flash = Pallas kernel, O(L) HBM in forward AND
    # backward for BOTH variants: the t5 relative-position bias has its own
    # Pallas dbias kernel (batch-innermost accumulating grid), so biased
    # training never materialises [B,H,L,S] either (round 4).
    attention: str = "dense"
    shared_towers: bool = False      # share params between query/page towers
    dtype: str = "bfloat16"          # compute dtype on MXU
    # glm4_moe_lite (models/glm_moe.py): the published config's keys under
    # their published names (mlp_dim is its intermediate_size, the dense
    # layers' width; num_layers its num_hidden_layers) ...
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    first_k_dense_replace: int = 1
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    # ... and the share of the routed experts THIS chip holds: a contiguous
    # range of `experts_held` experts from `experts_held_start`. The router
    # always scores all n_routed_experts; 0 held = all of them.
    experts_held: int = 0
    experts_held_start: int = 0
    # recompute each block's activations in the backward pass
    remat_blocks: bool = False
    # granitemoehybrid (models/granite_hybrid.py): the published keys under
    # their published names (mlp_dim is its intermediate_size, every routed
    # expert's width; num_layers follows from layer_types; n_routed_experts
    # is its num_local_experts; num_heads its num_attention_heads)
    layer_types: Tuple[str, ...] = ()          # "mamba" | "attention" a layer
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.0078125    # the score scale, not 1/sqrt(d)
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    shared_intermediate_size: int = 1536
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # falcon_h1 (models/falcon_h1.py): the published keys under their
    # published names, beside the mamba_*, num_key_value_heads, rope_theta,
    # rms_norm_eps and embedding_multiplier above (mlp_dim is its
    # intermediate_size, num_layers its num_hidden_layers, num_heads its
    # num_attention_heads). mamba_n_groups is read by granitemoehybrid too.
    head_dim: int = 128
    mamba_d_ssm: int = 4096                    # the mixer's inner width
    mamba_n_groups: int = 1
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = ()    # z, x, B, C, dt
    ssm_out_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, ...] = ()    # gate, down
    # qwen3_next (models/qwen3_next.py): the published keys under their
    # published names, beside num_key_value_heads, head_dim, rope_theta,
    # rms_norm_eps, moe_intermediate_size and num_experts_per_tok above
    # (num_layers is its num_hidden_layers, num_heads its
    # num_attention_heads, n_routed_experts its num_experts,
    # shared_intermediate_size its shared_expert_intermediate_size, mlp_dim
    # its intermediate_size, which no layer reads: every layer is sparse)
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 1.0
    # What BulkEmbedder / SearchService hold a tower's matrices in
    # (infer/bulk_embed.py:hold_weights): float32 as trained, or bfloat16
    # (cast once at construction; what the tower computes with in float32
    # stays float32). Training always holds float32.
    weights_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh shape. Axes: data (DP) and model (TP).

    The reference scaled with torch-DDP over NCCL (BASELINE.json:5); here the
    same role is played by GSPMD sharding over this mesh, with XLA emitting
    psum/all-gather over ICI.
    """
    data: int = 1
    model: int = 1
    seq: int = 1                     # sequence/context parallelism (ring attn)
    # strict=True: fail hard when fewer devices are visible than configured
    # (production pods); strict=False: shrink to fit with a loud warning
    # (dev boxes, tests, the 1-chip sandbox).
    strict: bool = False

    @property
    def num_devices(self) -> int:
        return self.data * self.model * self.seq


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 256            # GLOBAL batch (split across mesh 'data')
    steps: int = 1_000
    optimizer: str = "adamw"         # adamw | sgd
    learning_rate: float = 1e-3
    warmup_steps: int = 100
    weight_decay: float = 0.01
    temperature_init: float = 20.0   # learnable inverse-temperature init
    hard_negatives: int = 0          # ANN-mined negatives per positive
    checkpoint_every: int = 500
    log_every: int = 50
    # Steps fused into ONE compiled dispatch via lax.scan (host sees the
    # device every scan_steps steps instead of every step). >1 amortizes
    # per-dispatch host latency — the dominant single-chip overhead for
    # small models; log_every/checkpoint_every must be multiples of it.
    scan_steps: int = 1
    # Fused/chunked contrastive loss (models/losses.py): >0 streams query
    # rows against the global in-batch (+mined) negative pool this many
    # rows at a time — logits + log-sum-exp + grad contribution per tile,
    # never materializing the [B, B(1+H)] similarity matrix — so the
    # effective negative pool scales with the global batch instead of
    # with the biggest square matrix HBM can hold. Must divide
    # batch_size. 0 = the dense reference path (byte-identical
    # pre-chunking behavior); parity pinned by tests/test_losses_fused.py.
    loss_chunk: int = 0
    # Sequence packing for long-page configs (data/loader.py pack_segments,
    # docs/MFU.md): >1 packs this many consecutive short pages into ONE
    # [data.page_len] row with a segment mask (attention and pooling never
    # cross pages; BERT positions restart per segment), so a corpus of
    # short pages stops paying full-row pad compute. batch_size still
    # counts PAGES; the compiled row batch is batch_size / pack_pages.
    # Requires a transformer tower (bert/t5) with dense or flash
    # attention. 1 = unpacked (byte-identical pre-packing behavior);
    # parity pinned by tests/test_packing.py.
    pack_pages: int = 1
    # PRNG implementation for the per-step dropout keys. "rbg" (XLA's
    # hardware RngBitGenerator) measured +22% train throughput over
    # "threefry2x32" on v5e — threefry mask generation is the single
    # largest non-matmul cost of the bert-mini step. Trade-off: rbg mask
    # bits are not guaranteed stable across XLA versions/backends
    # (irrelevant for dropout; param init stays threefry).
    dropout_rng: str = "rbg"
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class EvalConfig:
    recall_k: int = 10               # Recall@10 query->page (BASELINE.json:2)
    eval_queries: int = 1_000
    embed_batch_size: int = 512
    # Batches fused into ONE bulk-embed dispatch (lax.map over a [K, B, L]
    # stack): amortizes per-dispatch host latency on the forward-only sweep
    # (+8% embed throughput measured on v5e at K=8, round 4). 1 = one
    # dispatch per batch.
    embed_stack: int = 8
    # vector-store shard rows: the resume/parallelism unit of the bulk-embed
    # job (one shard = one manifest entry = one fleet work item)
    store_shard_size: int = 65_536
    # float16 | int8 — int8 stores symmetric per-vector-quantized codes +
    # fp16 scales: ~2x smaller shards and half the read bandwidth at
    # 1B-page scale, with recall parity pinned by tests/test_store_quant.py
    store_dtype: str = "float16"
    # Bounded pending budget of the bulk-embed background writer: how many
    # finished shards may queue for disk writeback while the device embeds
    # ahead (infer/bulk_embed.py _ShardWriter). Bounds host memory at
    # budget * shard_size rows; a slow disk backpressures the device loop.
    writeback_depth: int = 2


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Query-serving knobs (infer/serve.py, docs/SERVING.md).

    The compiled encode/top-k bucket width itself comes from
    SearchService.query_batch (mesh-derived); these knobs govern how
    concurrent traffic is coalesced into that bucket and how repeat
    queries are deduplicated."""
    # Micro-batcher window: how long the dispatcher waits for more
    # concurrent search() callers after the first request arrives before
    # dispatching the coalesced batch. A lone caller pays at most one
    # window of extra latency; under load the window fills the compiled
    # bucket and aggregate QPS scales toward bucket width.
    batch_window_ms: float = 2.0
    # Telemetry-driven adaptive batching (docs/SERVING.md "SLO
    # methodology"): when on, the micro-batch window WIDENS toward
    # batch_window_max_ms while the windowed queue-wait p99 (the
    # serve.queue_wait_ms instrument) climbs past the current window —
    # requests are stacking faster than dispatches drain, so coalescing
    # harder buys throughput — and COLLAPSES back toward batch_window_ms
    # when traffic goes idle. Off (the default) keeps the fixed window:
    # byte-identical pre-adaptive behavior. The live window is exposed as
    # the serve.batch_window_ms gauge; every change emits a window_adapt
    # event (docs/OBSERVABILITY.md).
    batch_window_adaptive: bool = False
    # Ceiling for the adaptive window (ms). Bounds the extra latency a
    # lone caller can ever pay to one max-window flush.
    batch_window_max_ms: float = 25.0
    # Most queries one coalesced dispatch may carry (tiled over full
    # compiled buckets inside search_many). Bounds per-dispatch latency.
    max_batch: int = 32
    # Compiled width of the query ENCODE: the rows a call is padded to, and
    # the most it carries (more misses tile over it). 0 = the `query_batch`
    # bucket that the top-k scan uses too. A tower whose queries are long
    # (a whole page) wants 1: padding one 1,024-token query to eight costs
    # eight encodes, and such a tower's encode takes as long a query in a
    # wider call. A multiple of the mesh's data axis.
    encode_batch: int = 0
    # Bounded request queue between callers and the dispatcher thread: a
    # full queue backpressures callers instead of buffering unboundedly.
    max_queue: int = 256
    # LRU query-embedding cache entries (0 disables). Keyed on
    # whitespace-normalized query text + the store's model step, so
    # head-of-distribution repeat queries skip tokenize+encode entirely
    # and a model/store reload (new step) invalidates every entry.
    query_cache_size: int = 4096
    # Retrieval algorithm (docs/ANN.md): "exact" = brute-force MXU top-k
    # over the whole store (byte-identical pre-index behavior, the
    # default); "ivf" = the inverted-file ANN index (index/ivf.py) with
    # automatic per-request fallback to exact when the index is missing,
    # stale, or quarantined (counted in metrics as ann_fallbacks).
    index: str = "exact"
    # IVF lists probed per query: the recall-vs-cost dial. Expected scanned
    # fraction ~ nprobe/nlist; recall-vs-exact is measured, not assumed
    # (evals.recall.recall_vs_exact, pinned by tests/test_ivf_index.py).
    nprobe: int = 8
    # IVF list count for `cli index` builds. 0 = auto (~sqrt(store rows)).
    nlist: int = 0
    # k-means iterations for the IVF coarse quantizer build.
    kmeans_iters: int = 8
    # Quantizer seeding: "kmeans++" (D²-spread seeds — lower list
    # imbalance at large nlist; the build JSON reports the init->final
    # imbalance delta) or "random" (uniform pool draw). Both seeded and
    # byte-deterministic.
    kmeans_init: str = "kmeans++"
    # Balanced final assignment (docs/ANN.md): >0 caps every list at
    # ceil(factor * N / nlist) rows during the build's assignment sweep —
    # overflow rows spill to their next-best centroid (soft cap), cutting
    # hot-list imbalance at a small recall cost. 0 disables (pure argmax,
    # the pre-balance behavior); `cli index` reports the raw->balanced
    # imbalance delta.
    kmeans_balance: float = 0.0
    # OPQ+PQ compressed posting payloads (index/pq.py, docs/ANN.md):
    # number of PQ subspaces (must divide model.out_dim). 0 = plain IVF
    # (stored-width posting gather, the pre-PQ behavior); `cli index --pq`
    # picks an automatic m (~out_dim/8) when this is 0. With PQ on, the
    # candidate gather moves m bytes/row instead of the stored row width
    # and scoring runs as on-device ADC with an exact re-rank on top.
    pq_m: int = 0
    # Per-subspace codebook k-means iterations (PQ builds).
    pq_iters: int = 8
    # OPQ rotation/codebook alternations (Ge et al. 2013). 0 = plain PQ
    # (identity rotation).
    pq_opq_iters: int = 3
    # ADC candidates exact-reranked per query from the store (the final
    # top-k always comes from stored-width rows, preserving the
    # recall-vs-exact contract). 0 = auto max(8k, 64).
    pq_rerank: int = 0
    # HBM budget for the resident hot posting set (PQ indexes only): the
    # largest lists' codes + probed-list metadata stage to device at view
    # build so their per-request host gather disappears; the non-resident
    # tail falls back to the mmap path. 0 disables.
    hot_postings_gb: float = 0.0
    # Partitioned serving (infer/partition.py, docs/SCALING.md
    # "Partitioned serving"): >1 splits the store's shard table into this
    # many contiguous partitions, each owning its shard range, its slice
    # of the IVF posting lists, and its cut of serve.hot_postings_gb;
    # search_many scatter-gathers — the coalesced bucket broadcasts once,
    # every partition answers its local top-k over ONLY its rows, and
    # results fold through the ops/topk.py partition merge tree. Clamped
    # to the shard count. 1 (with replicas=1) keeps the single-view
    # serving path byte-identical to before.
    partitions: int = 1
    # Replica sets: R copies of every partition (each host-simulated as a
    # worker thread owning an independent _ServeView), with health-based
    # routing — a replica mid-restage, degraded to the streaming path, or
    # past its queue budget sheds traffic to its siblings (`replica_shed`
    # event); a partition whose replicas are ALL degraded serves degraded
    # locally (`partition_degraded`), never an empty result slice.
    replicas: int = 1
    # Queue-depth shed budget per partition replica: a replica with more
    # than this many requests in flight stops being preferred and traffic
    # sheds to its siblings. Only a routing preference — with every
    # replica over budget the least-loaded healthy one still serves.
    replica_shed_queue: int = 8
    # -- over-the-wire serving (infer/transport.py, infer/server.py,
    # infer/partition_host.py; docs/SERVING.md "Network front end") ------
    # Listen address of the asyncio socket front end ("host:port"; port 0
    # binds an ephemeral port, reported by the server handle/CLI).
    listen: str = "127.0.0.1:0"
    # Default per-request deadline budget (ms) applied at admission when
    # a request carries none. A request that cannot make its deadline is
    # shed AT THE DOOR (serve.deadline_shed + deadline_shed event) —
    # before it can consume a micro-batch bucket slot — and one whose
    # deadline expires while queued is shed at dispatch. 0 disables.
    deadline_ms: float = 0.0
    # Hedged fan-out (partition RPC): when a partition's answer has not
    # arrived within this quantile of its observed RPC latency, the same
    # request fires at a sibling replica's worker and the first answer
    # wins (serve.hedge_fired + hedge_fired event). Needs >= 8 latency
    # samples before it ever fires; <= 0 (or >= 1) disables hedging.
    hedge_quantile: float = 0.95
    # Partition-worker heartbeat interval (seconds). A worker whose last
    # heartbeat is older than 2x this — or whose registration connection
    # dropped — is LOST (worker_lost event): routing sheds its replica
    # (reason "liveness") and the fan-out serves its slice from the
    # front end's local view until it re-registers.
    heartbeat_s: float = 0.5
    # Wire compression (docs/SERVING.md "Network front end"): negotiated
    # per connection (REGISTER flags / T_HELLO), LOSSLESS — RESULT
    # frames ship raw f32 scores + zigzag-delta varint page ids, and
    # repeated query blocks intern into per-connection slots (sent once,
    # then a 2-byte reference), so socket results stay byte-identical to
    # in-process while wire bytes/query drop >= 2.5x on repeat-heavy
    # traffic. False = every connection negotiates down to raw frames
    # (the PR-13 wire format); mixed fleets interoperate either way.
    wire_compress: bool = True
    # Generation-keyed result cache (docs/SERVING.md "Result cache"):
    # formatted top-k results keyed by (normalized text, k, nprobe, store
    # generation, index generation), probed at the admission door before a
    # repeat can consume a micro-batch bucket slot. refresh() bumps the
    # generations, so invalidation is free — a post-append repeat can
    # never serve pre-append results. Off by default: repeats then take
    # the full path (embedding cache still applies).
    result_cache: bool = False
    # Result-cache capacity (entries, LRU). 0 disables even when
    # serve.result_cache is true.
    result_cache_size: int = 4096
    # Fleet-wide sharing of the result cache over the wire: advertise
    # FLAG_RESULT_CACHE in REGISTER/HELLO and answer CACHE_LOOKUP /
    # CACHE_PUT frames, so N front ends (and the worker RPC hop) share
    # one hot set. Requires serve.result_cache; mixed fleets where one
    # side never negotiated the flag degrade to local-only caching.
    result_cache_fleet: bool = False
    # Filtered retrieval (docs/ANN.md "Filtered retrieval"): accept and
    # serve per-query attribute predicates (`lang==X`, `site in {...}`,
    # `recency>=band`, '&'-conjunctions) — advertised/confirmed per
    # connection as FLAG_FILTERS, exactly like wire compression. False =
    # this end never negotiates the flag: a gateway serves filtered
    # slices from its local view, a client raises on a filtered call.
    filters: bool = True
    # Under-filled-probe escalation: when a filtered IVF probe set yields
    # fewer than k matching rows, the probe count multiplies by this
    # factor and the scan re-runs (ivf.filter_escalations counter) until
    # k fills or every list drains. <= 1 disables escalation.
    filter_escalate: float = 4.0
    # Self-healing fleet (docs/ROBUSTNESS.md "Network failure model").
    # A partition worker that loses its gateway connection (EOF, torn
    # frame, socket error) re-dials with exponential backoff + jitter and
    # re-REGISTERs with its current generation instead of exiting. False
    # restores the PR-13 behavior: connection loss is terminal.
    reconnect: bool = True
    # First re-dial delay (seconds); doubles per consecutive failure.
    reconnect_base_s: float = 0.05
    # Backoff cap for the re-dial ramp (seconds) — also the cap for the
    # wire retry profile around dial+REGISTER (faults.retry_wire).
    reconnect_max_s: float = 2.0
    # Gateway-side per-replica circuit breaker: after this many
    # CONSECUTIVE wire failures the replica's breaker opens
    # (breaker_open event) and routing skips it — requests go straight
    # to fallback instead of paying a timeout each. <= 0 disables.
    breaker_failures: int = 3
    # How long an open breaker blocks traffic before admitting one
    # half-open probe (seconds); doubles on every failed probe.
    breaker_open_s: float = 0.25
    # Cap for the open-interval ramp (seconds).
    breaker_max_s: float = 30.0
    # Elastic fleet membership (docs/SCALING.md "Scale-out tier"): the
    # gateway re-cuts the partition split to match the live worker set —
    # a worker joining at the next tail index widens it, a draining tail
    # worker shrinks it — via a deterministic partition_shard_ranges
    # re-split and the generation-gated REFRESH handoff (fleet_resplit
    # event), with no restarts and no result set ever mixing splits.
    # Off (the default): the split is fixed at boot, exactly as before.
    elastic: bool = False


@dataclasses.dataclass(frozen=True)
class UpdatesConfig:
    """Live corpus updates (dnn_page_vectors_tpu/updates/,
    docs/UPDATES.md): append-only store generations, incremental IVF
    refresh, zero-downtime serving hot-swap."""
    # Full-rebuild trigger for IVFIndex.update: when the fraction of the
    # corpus appended since the last full k-means exceeds this, the
    # incremental posting append stops (stale centroids mis-assign enough
    # new rows to erode recall) and update() runs a fresh build instead.
    rebuild_drift: float = 0.25
    # SearchService.refresh() / `cli append` bring the IVF index up to
    # date automatically when one exists. False = store-only refresh
    # (the index goes stale and serving falls back to exact, visibly).
    auto_update_index: bool = True
    # Tombstone-aware HBM restage policy (docs/UPDATES.md): a refresh()
    # REUSES a staged device shard whose only change is new tombstones as
    # long as the staged block's dead-row fraction stays <= this threshold
    # (the dead rows are masked in the id table instead — they can occupy
    # but never win a result slot), and restages it once density crosses
    # the threshold. metrics() reports restage_skipped/restage_forced.
    # 0.0 restores the exact-ids policy (any tombstone restages).
    restage_tombstone_density: float = 0.05
    # Multi-writer append leases (docs/MAINTENANCE.md): append_corpus
    # acquires a per-writer lease on the append cursor (lease file under
    # the store manifest dir) before reading next_page_id(), so two
    # concurrent `cli append` processes can never double-assign ids. The
    # lease expires after this many seconds — a crashed writer's lease is
    # stolen (lease_stolen event) instead of blocking appends forever.
    writer_lease_s: float = 30.0
    # How long a second writer QUEUES on a held lease before giving up
    # (seconds). 0 fails fast (LeaseHeld) instead of waiting.
    lease_wait_s: float = 5.0


@dataclasses.dataclass(frozen=True)
class MaintenanceConfig:
    """Background maintenance service (dnn_page_vectors_tpu/maintenance/,
    docs/MAINTENANCE.md): online generation compaction, off-path IVF
    rebuilds, and the stale-artifact janitor — a store that ingests,
    compacts, and re-indexes continuously while serving."""
    # Compaction trigger: when the tombstone density across the generation
    # chain (dead rows / total rows) crosses this, the background compactor
    # folds the gen-NNNN chain plus the base into a fresh compacted base —
    # dead rows dropped, ids preserved, one atomic manifest pointer flip.
    compact_tombstone_density: float = 0.2
    # Worker poll period (seconds): how often each pillar worker re-checks
    # its trigger. `cli maintain --once` / run_once() ignore it.
    interval_s: float = 5.0
    # Move drift-triggered IVF full rebuilds OFF the refresh() caller: with
    # a MaintenanceService attached, refresh() defers the rebuild (the
    # incremental posting append still runs; serve.index_rebuild_pending
    # flags it) and the background builder constructs the next index
    # generation beside the live one, hot-swapping via refresh(). False
    # keeps the PR-5 inline-rebuild behavior even with maintenance running.
    bg_rebuild: bool = True
    # Autoscale pillar (docs/SCALING.md "Scale-out tier"): drive worker
    # spawn/drain decisions from the serving telemetry — scale UP when
    # the windowed queue-wait p99 or the deadline-shed rate crosses its
    # up-threshold, DOWN when queue wait sits below the down-threshold
    # with zero sheds. Decisions only fire through hooks the operator
    # attaches (MaintenanceService.attach_scaler); without hooks the
    # pillar still evaluates and emits autoscale_up/autoscale_down
    # events, so the policy is observable before it is trusted. Off by
    # default.
    autoscale: bool = False
    # Fleet-size floor/ceiling the policy may move between.
    autoscale_min_workers: int = 1
    autoscale_max_workers: int = 4
    # Scale-up triggers: windowed queue-wait p99 (ms) or deadline-shed
    # rate (sheds/s over the telemetry window) at/above these.
    autoscale_up_queue_p99_ms: float = 50.0
    autoscale_up_shed_rate: float = 0.5
    # Scale-down trigger: queue-wait p99 at/below this with a zero shed
    # rate (and at least one full cooldown of calm).
    autoscale_down_queue_p99_ms: float = 5.0
    # Minimum seconds between scaling actions — a resize's own dip must
    # not read as new pressure before the fleet settles.
    autoscale_cooldown_s: float = 30.0


@dataclasses.dataclass(frozen=True)
class MigrationConfig:
    """Rolling model migration (dnn_page_vectors_tpu/maintenance/migrate.py,
    docs/MAINTENANCE.md "Rolling model migration"): re-embed a LIVE store
    to a new model step unit-by-unit while serving runs dual-stamp. The
    sweep itself is requested at runtime (`cli migrate`, or
    MaintenanceService.request_migration); these knobs shape how it
    runs."""
    # Host-side text rows per embed call while re-embedding a shard: the
    # memory/throughput trade of the sweep's bulk encode (same role as the
    # embed pipeline's batch, but off-path — it never blocks a query).
    batch_rows: int = 4096
    # Units the migrate pillar commits per maintenance pass before
    # hot-swapping the serving view. 1 keeps each refresh window small
    # (one unit's shards restage); raise it to trade refresh frequency
    # for sweep speed on large chains.
    units_per_pass: int = 1
    # Reclaim each unit's superseded shard files right after the serving
    # view moves past them (purge_stale). False leaves the bytes for the
    # janitor — the forensic setting.
    purge: bool = True


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability (utils/telemetry.py, utils/tracing.py,
    docs/OBSERVABILITY.md): request-scoped tracing, the slow-query log,
    and the metrics registry's rolling windows. The knob table in
    docs/OBSERVABILITY.md is kept in lockstep with these fields by a
    drift test (tests/test_telemetry.py)."""
    # Request-scoped tracing on/off. Off, every span is a shared no-op
    # object — instrumented paths pay one None-check.
    enabled: bool = True
    # Slow-query threshold in milliseconds: a finished request trace whose
    # duration crosses this lands (as a full span tree) in the slow-query
    # log. 0 captures EVERY request; negative disables the log.
    slow_ms: float = -1.0
    # Bounded slow-query log entries (oldest evicted first).
    slow_log_size: int = 64
    # Recent finished traces kept for `cli trace` export (ring buffer).
    trace_buffer: int = 64
    # Rolling window (seconds) behind the live qps / error-rate /
    # cache-hit-rate / windowed-p99 numbers — "over the last N seconds",
    # not since boot.
    window_s: float = 10.0
    # Bounded percentile reservoir size (Algorithm R): histograms and
    # LatencyStats keep at most this many samples regardless of uptime;
    # below it, percentiles are exact nearest-rank.
    reservoir: int = 4096
    # Lifecycle event ring size (view hot-swap, shard quarantine, drift
    # rebuild, degraded/restored, checkpoint rollback).
    events: int = 256


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Fault injection + transient-I/O retry policy (utils/faults.py,
    docs/ROBUSTNESS.md). Injection is OFF unless `plan` is non-empty; the
    retry policy is always on (real filesystems throw transient errors
    without any help from us)."""
    # "op:kind:at[:count],..." — e.g. "shard_write:io_error:1" fails the
    # second shard write once. Empty = no injection. See utils/faults.py
    # for the op-name table and docs/ROBUSTNESS.md for the failure model.
    plan: str = ""
    seed: int = 0                    # RNG for corruption offsets/bits
    retry_attempts: int = 3          # total attempts per I/O op
    retry_backoff_s: float = 0.05    # first backoff; doubles per retry
    retry_jitter_s: float = 0.02     # uniform jitter added to each backoff


@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    eval: EvalConfig = dataclasses.field(default_factory=EvalConfig)
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    updates: UpdatesConfig = dataclasses.field(default_factory=UpdatesConfig)
    maintenance: MaintenanceConfig = dataclasses.field(
        default_factory=MaintenanceConfig)
    migrate: MigrationConfig = dataclasses.field(
        default_factory=MigrationConfig)
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    faults: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    workdir: str = "/tmp/dnn_page_vectors_tpu"

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)


def _tuple_item(text: str):
    """One item of a comma-separated tuple on the command line: an int, a
    float (the multipliers), or the text itself (layer types)."""
    for kind in (int, float):
        try:
            return kind(text)
        except ValueError:
            pass
    return text


def _nested_replace(cfg: Config, overrides: Dict[str, Any]) -> Config:
    """Apply dotted-path overrides, e.g. {"train.steps": 10}."""
    for path, value in overrides.items():
        parts = path.split(".")
        if len(parts) == 1:
            cfg = dataclasses.replace(cfg, **{parts[0]: value})
            continue
        section = getattr(cfg, parts[0])
        if not isinstance(value, (tuple, list)):
            # coerce CLI strings to the dataclass field's current type
            current = getattr(section, parts[1])
            if isinstance(current, bool):
                if value in (True, "true", "True", "1", 1):
                    value = True
                elif value in (False, "false", "False", "0", 0):
                    value = False
                else:
                    raise ValueError(
                        f"bad boolean for {path}: {value!r} (use true/false)")
            elif isinstance(current, int):
                value = int(value)
            elif isinstance(current, float):
                value = float(value)
            elif isinstance(current, tuple):
                value = tuple(_tuple_item(x) for x in str(value).split(","))
        elif isinstance(value, list):
            value = tuple(value)
        section = dataclasses.replace(section, **{parts[1]: value})
        cfg = dataclasses.replace(cfg, **{parts[0]: section})
    return cfg


# ---------------------------------------------------------------------------
# The five canonical configs (BASELINE.json:6-12).
# ---------------------------------------------------------------------------

def cdssm_toy() -> Config:
    """Config 1: 'CDSSM char-trigram CNN, 10k-page toy corpus, single-process
    CPU' (BASELINE.json:7). The integration oracle of SURVEY.md §5."""
    return Config(
        name="cdssm_toy",
        data=DataConfig(tokenizer="trigram", corpus="toy", num_pages=10_000),
        model=ModelConfig(encoder="cdssm", conv_widths=(3,), conv_channels=256,
                          embed_dim=128, out_dim=128, dtype="float32"),
        mesh=MeshConfig(data=1),
        train=TrainConfig(batch_size=256, steps=1_000),
    )


def kim_cnn_v5e8() -> Config:
    """Config 2: 'Word-CNN (Kim-CNN) page encoder, 1M pages, data-parallel
    pjit on v5e-8' (BASELINE.json:8)."""
    return Config(
        name="kim_cnn_v5e8",
        data=DataConfig(tokenizer="word", corpus="toy", num_pages=1_000_000,
                        vocab_size=100_000),
        model=ModelConfig(encoder="kim_cnn", conv_widths=(3, 4, 5),
                          conv_channels=256, embed_dim=256, out_dim=256),
        mesh=MeshConfig(data=8),
        train=TrainConfig(batch_size=4_096, steps=50_000),
    )


def lstm_words() -> Config:
    """BiLSTM word-level page encoder — the reference lineage's recurrent
    family (SURVEY.md §1 [PRIOR]; same word-tokenized corpus as config 2).
    Sized like kim_cnn_v5e8 so the two word-family encoders are directly
    comparable on the same data."""
    return Config(
        name="lstm_words",
        data=DataConfig(tokenizer="word", corpus="toy", num_pages=1_000_000,
                        vocab_size=100_000),
        model=ModelConfig(encoder="lstm", embed_dim=256, model_dim=256,
                          num_layers=1, out_dim=256),
        mesh=MeshConfig(data=8),
        train=TrainConfig(batch_size=4_096, steps=50_000),
    )


def bert_mini_v5p16() -> Config:
    """Config 3: 'Two-tower BERT-mini (query + page) with in-batch negatives
    on v5p-16' (BASELINE.json:9). BERT-mini: L=4, H=256, A=4."""
    return Config(
        name="bert_mini_v5p16",
        data=DataConfig(tokenizer="wordpiece", corpus="toy",
                        num_pages=10_000_000, vocab_size=30_522),
        model=ModelConfig(encoder="bert", num_layers=4, num_heads=4,
                          model_dim=256, mlp_dim=1024, out_dim=256),
        mesh=MeshConfig(data=16),
        train=TrainConfig(batch_size=8_192, steps=100_000,
                          learning_rate=5e-4),
    )


def hardneg_v5p64() -> Config:
    """Config 4: 'Hard-negative ANN-mined contrastive training, 100M pages,
    v5p-64' (BASELINE.json:10)."""
    return Config(
        name="hardneg_v5p64",
        data=DataConfig(tokenizer="wordpiece", corpus="toy",
                        num_pages=100_000_000, vocab_size=30_522),
        model=ModelConfig(encoder="bert", num_layers=4, num_heads=4,
                          model_dim=256, mlp_dim=1024, out_dim=256),
        mesh=MeshConfig(data=64),
        train=TrainConfig(batch_size=16_384, steps=200_000,
                          hard_negatives=7, learning_rate=5e-4),
    )


def mt5_multilingual() -> Config:
    """Config 5: 'Multilingual mT5-base page encoder + cross-lingual
    retrieval eval' (BASELINE.json:11). mT5-base encoder: L=12, d=768,
    heads=12, ff=2048; model axis gives optional TP (SURVEY.md §3 #14)."""
    return Config(
        name="mt5_multilingual",
        data=DataConfig(tokenizer="sentencepiece", corpus="toy",
                        num_pages=10_000_000, vocab_size=250_112,
                        page_len=128, languages=4),
        model=ModelConfig(encoder="t5", num_layers=12, num_heads=12,
                          model_dim=768, mlp_dim=2048, out_dim=768),
        mesh=MeshConfig(data=4, model=2),
        train=TrainConfig(batch_size=4_096, steps=100_000,
                          learning_rate=1e-4),
    )


def bert_long_sp() -> Config:
    """Long-page variant beyond the five canonical configs: 1024-token pages
    with ring-attention sequence parallelism over the mesh 'seq' axis
    (parallel/ring_attention.py) and Pallas flash attention available via
    model.attention=flash for the single-chip case. Covers the long-context
    scaling requirement the short-sequence canonical configs don't exercise."""
    return Config(
        name="bert_long_sp",
        data=DataConfig(tokenizer="wordpiece", corpus="toy",
                        num_pages=1_000_000, vocab_size=30_522,
                        page_len=1024, query_len=32),
        model=ModelConfig(encoder="bert", num_layers=4, num_heads=8,
                          model_dim=512, mlp_dim=2048, out_dim=256,
                          attention="ring"),
        mesh=MeshConfig(data=16, seq=4),
        train=TrainConfig(batch_size=2_048, steps=100_000,
                          learning_rate=5e-4),
    )


def glm47_flash_ep8() -> Config:
    """GLM-4.7-Flash (zai-org, `glm4_moe_lite`) as ONE shared tower, cut to
    one chip's share of a layer that 8 chips hold together (expert parallel:
    8 of the 64 routed experts here; attention, router and shared expert
    replicated; an eighth of the 154,880 embedding rows): 1 dense + 4 expert
    layers of the published 47, every width as published. Pages of 1,024
    tokens through causal flash attention, last-token pool, per-block
    recomputation (benchmarks/configs/glm47_flash_ep8.json states the cut)."""
    return Config(
        name="glm47_flash_ep8",
        data=DataConfig(tokenizer="wordpiece", corpus="toy",
                        num_pages=10_000_000, vocab_size=19_360,
                        page_len=1024, query_len=32),
        model=ModelConfig(encoder="glm4_moe_lite", num_layers=5,
                          num_heads=20, model_dim=2048, mlp_dim=10_240,
                          out_dim=2048, attention="flash", dropout=0.0,
                          shared_towers=True, experts_held=8,
                          experts_held_start=0, remat_blocks=True),
        mesh=MeshConfig(data=1),
        train=TrainConfig(batch_size=32, steps=100_000, learning_rate=1e-4),
    )


def granite4_h_small_ep2() -> Config:
    """Granite-4.0-H-Small (ibm-granite, `granitemoehybrid`) as ONE shared
    tower for SERVING, cut to one chip's share of a layer that 2 chips hold
    together (expert parallel: 36 of the 72 routed experts here; mixer,
    attention, router and shared expert replicated; half of the 100,352
    embedding rows): the first period of ten layers (nine Mamba-2, one
    attention) of the published 40, every width as published, weights held
    in bfloat16. Whole pages as queries: 1,024 tokens, encoded one a call
    (benchmarks/configs/granite4_h_small_ep2.json states the cut; 16 bytes
    a parameter of training state do not fit one chip)."""
    period = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    return Config(
        name="granite4_h_small_ep2",
        data=DataConfig(tokenizer="wordpiece", corpus="toy",
                        num_pages=1_048_576, vocab_size=50_176,
                        page_len=1024, query_len=1024),
        model=ModelConfig(encoder="granitemoehybrid", num_layers=10,
                          layer_types=period, num_heads=32,
                          num_key_value_heads=8, model_dim=4096, mlp_dim=768,
                          out_dim=1024, attention="flash", dropout=0.0,
                          shared_towers=True, n_routed_experts=72,
                          num_experts_per_tok=10, experts_held=36,
                          experts_held_start=0, weights_dtype="bfloat16"),
        mesh=MeshConfig(data=1),
        train=TrainConfig(batch_size=4, steps=1_000, learning_rate=1e-4),
        eval=EvalConfig(embed_batch_size=4),
        serve=ServeConfig(max_batch=4, encode_batch=1, query_cache_size=0),
    )


def falcon_h1_34b_pp12() -> Config:
    """Falcon-H1-34B-Instruct (tiiuae, `falcon_h1`) as ONE shared tower for
    SERVING: the first six whole layers of the published 72 (the first of
    twelve pipeline stages, one chip a stage, which also holds the whole
    embedding), every width, head, group and all 261,120 rows as published,
    weights held in bfloat16. In every block a Mamba-2 mixer (32 heads of
    128 in 2 groups, state 256, chunk 128) beside grouped-query attention
    (20 + 4 heads of 128, rotary at theta 1e11), then a dense SwiGLU of
    21,504; twelve muP multipliers. Whole pages as queries: 1,024 tokens,
    encoded one a call (benchmarks/configs/falcon_h1_34b_pp12.json states
    the cut; at 16 bytes a parameter of training state four layers and an
    eighth of the vocabulary are 30 GB, twice one chip)."""
    return Config(
        name="falcon_h1_34b_pp12",
        data=DataConfig(tokenizer="wordpiece", corpus="toy",
                        num_pages=1_048_576, vocab_size=261_120,
                        page_len=1024, query_len=1024),
        model=ModelConfig(
            encoder="falcon_h1", num_layers=6, num_heads=20,
            num_key_value_heads=4, head_dim=128, model_dim=5120,
            mlp_dim=21_504, out_dim=1024, attention="flash", dropout=0.0,
            shared_towers=True, rope_theta=1e11, rms_norm_eps=1e-5,
            mamba_n_heads=32, mamba_d_head=128, mamba_d_ssm=4096,
            mamba_d_state=256, mamba_n_groups=2, mamba_d_conv=4,
            mamba_chunk_size=128, mamba_expand=2,
            embedding_multiplier=5.656854249492381, ssm_in_multiplier=0.25,
            ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369,
                             0.5, 0.3535533905932738),
            ssm_out_multiplier=0.08838834764831845,
            attention_in_multiplier=1.0,
            attention_out_multiplier=0.0375,
            key_multiplier=0.011048543456039804,
            mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
            weights_dtype="bfloat16"),
        mesh=MeshConfig(data=1),
        train=TrainConfig(batch_size=4, steps=1_000, learning_rate=1e-4),
        eval=EvalConfig(embed_batch_size=4),
        serve=ServeConfig(max_batch=4, encode_batch=1, query_cache_size=0),
    )


def qwen3_next_80b_ep16() -> Config:
    """Qwen3-Next-80B-A3B-Instruct (Qwen, `qwen3_next`) as ONE shared tower,
    cut to one chip's share of a layer that 16 chips hold together (expert
    parallel: 32 of the 512 routed experts here; Gated DeltaNet, attention,
    router and shared expert replicated; an eighth of the 151,936 embedding
    rows): one whole period of the published 48 layers (three Gated DeltaNet
    layers, then gated attention), every width as published. Pages of 2,048
    tokens, last-token pool, per-block recomputation
    (benchmarks/configs/qwen3_next_80b_ep16.json states the cut)."""
    return Config(
        name="qwen3_next_80b_ep16",
        data=DataConfig(tokenizer="wordpiece", corpus="toy",
                        num_pages=10_000_000, vocab_size=18_992,
                        page_len=2048, query_len=64),
        model=ModelConfig(
            encoder="qwen3_next", num_layers=4, num_heads=16,
            num_key_value_heads=2, head_dim=256, model_dim=2048,
            mlp_dim=5120, moe_intermediate_size=512,
            shared_intermediate_size=512, n_routed_experts=512,
            num_experts_per_tok=10, experts_held=32, experts_held_start=0,
            full_attention_interval=4, linear_num_key_heads=16,
            linear_num_value_heads=32, linear_key_head_dim=128,
            linear_value_head_dim=128, linear_conv_kernel_dim=4,
            partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
            out_dim=2048, attention="flash", dropout=0.0,
            shared_towers=True, remat_blocks=True),
        mesh=MeshConfig(data=1),
        train=TrainConfig(batch_size=16, steps=100_000, learning_rate=1e-4),
    )


CONFIGS = {
    "cdssm_toy": cdssm_toy,
    "kim_cnn_v5e8": kim_cnn_v5e8,
    "lstm_words": lstm_words,
    "bert_mini_v5p16": bert_mini_v5p16,
    "hardneg_v5p64": hardneg_v5p64,
    "mt5_multilingual": mt5_multilingual,
    "bert_long_sp": bert_long_sp,
    "glm47_flash_ep8": glm47_flash_ep8,
    "granite4_h_small_ep2": granite4_h_small_ep2,
    "falcon_h1_34b_pp12": falcon_h1_34b_pp12,
    "qwen3_next_80b_ep16": qwen3_next_80b_ep16,
}


def get_config(name: str, overrides: Optional[Dict[str, Any]] = None) -> Config:
    if name not in CONFIGS:
        raise KeyError(f"unknown config {name!r}; have {sorted(CONFIGS)}")
    cfg = CONFIGS[name]()
    if overrides:
        cfg = _nested_replace(cfg, overrides)
    return cfg
