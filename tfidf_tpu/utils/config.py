"""Single-dataclass configuration with environment-variable overrides.

The reference configures itself through Spring ``application.properties``
(``src/main/resources/application.properties:1-8`` — ``zookeeper.connection``,
``mydocument.path``, ``lucene.index.path``, ``server.port``) plus raw env vars
``POD_IP`` / ``SERVER_PORT`` read in ``OnElectionAction.java:35-36,64-68``.
Here the whole surface is one frozen dataclass; every field can be overridden
by a ``TFIDF_<UPPER_NAME>`` environment variable, so a Kubernetes Deployment
can configure nodes exactly the way the reference's manifest does
(``README.MD:80-90``).
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass
from typing import Any

_ENV_PREFIX = "TFIDF_"


@dataclass(frozen=True)
class Config:
    # --- paths (reference: application.properties:5-7) ---
    documents_path: str = "./data/documents"
    index_path: str = "./data/index"

    # --- node / control plane (reference: application.properties:2,8) ---
    # May be a comma-separated ensemble connect string
    # ("c0:2181,c1:2181,c2:2181") — clients fail over across members and
    # follow follower->leader redirects (cluster/coordination.py).
    coordinator_address: str = "127.0.0.1:2181"
    host: str = "127.0.0.1"
    port: int = 8085
    # Liveness: the reference's ZooKeeper session timeout doubles as the
    # failure detector (ZookeeperConfig.java:17, sessionTimeout=3000ms).
    session_timeout_s: float = 3.0
    heartbeat_interval_s: float = 0.5

    # --- scoring model ---
    model: str = "bm25"          # "bm25" | "tfidf" | "tfidf_cosine"
    bm25_k1: float = 1.2         # Lucene BM25Similarity defaults
    bm25_b: float = 0.75
    # Parity mode reproduces Lucene quirks bit-for-bit: SmallFloat 1-byte
    # norm quantization and per-shard (non-global) IDF (Worker.java:222-241).
    lucene_parity: bool = False
    # Result ordering: the reference sorts by document NAME, not score
    # (Leader.java:80-91, comparingByKey). "score" is the sane default.
    result_order: str = "score"  # "score" | "name"
    top_k: int = 10
    # Parity mode for the cluster data plane: return EVERY matching doc
    # per query (the reference's Integer.MAX_VALUE top-k, Worker.java:230)
    # instead of exact top-k. O(corpus) per query — off by default.
    unbounded_results: bool = False
    # Server-side micro-batching of concurrent /worker/process queries
    # into one device batch; the linger is the max extra latency a lone
    # query pays while waiting for company.
    micro_batch: bool = True
    batch_linger_ms: float = 2.0
    # Adaptive linger bounds (serving pipeline, PERF.md round 6): with
    # no batch in flight the coalescer lingers only *_linger_min_ms
    # (the device is idle — dispatch at once); as the dispatcher
    # pipeline saturates the linger stretches toward *_linger_max_ms
    # (the wait hides under in-flight work and buys batch fill). Set
    # either bound negative to disable adaptation and keep the fixed
    # *_linger_ms. Env overrides: TFIDF_BATCH_LINGER_MIN_MS etc.
    batch_linger_min_ms: float = 0.2
    batch_linger_max_ms: float = 4.0
    # Concurrent in-flight micro-batches (scorer threads). 2 hides one
    # batch's device->host result fetch under the next batch's compute.
    batch_pipeline: int = 2
    # Leader scatter fan-out thread pool. Each in-flight /leader/start
    # holds one pool thread per worker RPC; with C concurrent clients
    # and W workers the pool needs ~C*W threads or the scatter itself
    # becomes the concurrency cap (and the worker micro-batcher never
    # sees full batches). (With scatter_micro_batch on, only the
    # dispatcher threads use the pool: ~scatter_pipeline * W.)
    fanout_workers: int = 16
    # Leader-side scatter batching: concurrent /leader/start queries
    # coalesce into ONE /worker/process-batch RPC per worker (packed
    # binary response, cluster/wire.py) instead of one JSON RPC per
    # (query, worker). At high client concurrency the per-query HTTP +
    # JSON Python cost on the worker is the serving-path ceiling
    # (GIL-bound); batching collapses it to one RPC per batch.
    # Unbounded-results (parity) configs use the per-query path.
    scatter_micro_batch: bool = True
    scatter_batch: int = 128
    scatter_linger_ms: float = 2.0
    # Adaptive scatter linger (same rule as batch_linger_min/max_ms):
    # idle pipeline -> linger_min (ship the group now), saturated
    # pipeline -> linger_max (fuller groups; the wait is hidden).
    scatter_linger_min_ms: float = 0.2
    scatter_linger_max_ms: float = 8.0
    # Concurrent scatter dispatcher threads: one batch's worker RPC
    # round trip overlaps the next batch's formation.
    scatter_pipeline: int = 2
    # Per-RPC timeout for the batched scatter (covers a worker's NRT
    # commit if an upload landed just before the batch).
    scatter_timeout_s: float = 60.0

    # --- dense retrieval / hybrid fusion (engine/dense.py, ops/dense.py,
    #     cluster/fusion.py) ---
    # Per-doc embedding column beside the sparse postings: populated at
    # ingest by a deterministic embedder, scored on the MXU by a blocked
    # brute-force matmul top-k, fused with the sparse stage at the
    # scatter owner-merge. Disabling drops dense/hybrid query modes
    # (they fail loudly, never silently fall back to sparse).
    embedding_enabled: bool = True
    embedding_dim: int = 64
    # Embedder registry key (engine/embedder.py). "hash" is the hermetic
    # default: signed feature hashing of token STRINGS via blake2b —
    # replica-identical vectors with zero learned weights. Real encoders
    # plug in via register_embedder().
    embedding_model: str = "hash"
    # Doc-axis chunk for the blocked dense kernel (rows per matmul).
    embedding_chunk: int = 1 << 14
    # Default fusion for mode=hybrid when the query doesn't choose:
    # "rrf" (reciprocal-rank, scale-free) | "wsum" (min-max weighted sum).
    fusion_method: str = "rrf"
    fusion_rrf_k: float = 60.0
    fusion_weight_sparse: float = 0.5
    fusion_weight_dense: float = 0.5

    # --- analyzer ---
    lowercase: bool = True
    stopwords: tuple[str, ...] = ()   # Lucene 9 StandardAnalyzer default: none
    max_token_length: int = 255       # StandardAnalyzer.maxTokenLength default

    # --- mesh / parallelism ---
    # "local": single-device engine (ShardIndex/SegmentedIndex layouts).
    # "mesh":  the index lives in ShardedArrays on a ("docs","terms")
    #          device mesh; searches run the distributed shard_map step
    #          (psum global IDF + all_gather top-k) — the serving path
    #          that subsumes the reference's whole worker pool.
    engine_mode: str = "local"         # "local" | "mesh"
    # Mesh index layout: "ell" = blocked-ELL base scored by the
    # compare/MXU kernel + COO append delta (the fast path); "coo" =
    # pure COO scatter scoring (also auto-selected for tfidf_cosine,
    # Lucene parity, and unbounded-results configs, which ELL does not
    # support).
    mesh_layout: str = "ell"           # "ell" | "coo"
    mesh_shape: tuple[int, ...] = ()   # () = all local devices on one "docs" axis
    # Multi-host bootstrap (jax.distributed over DCN). On TPU pods the
    # coordinator/process values are auto-detected; leave the defaults.
    # Elsewhere set them (or the standard JAX_COORDINATOR_ADDRESS /
    # JAX_NUM_PROCESSES / JAX_PROCESS_ID env vars).
    distributed: bool = False
    dist_coordinator: str = ""         # host:port of process 0
    dist_num_processes: int = 0        # 0 = auto-detect
    dist_process_id: int = -1          # -1 = auto-detect
    query_batch: int = 32              # padded query batch per scoring step
    # The most distinct terms a query may hold, and the width of the
    # padded [query_batch, max_query_terms] query matrices. A limit that
    # REFUSES (engine/searcher.py TooManyQueryTerms; a 400 at the
    # node's doors), never a cut to the heaviest terms: Lucene's
    # IndexSearcher.maxClauseCount, 1,024 there.
    max_query_terms: int = 32
    # In-flight query chunks inside one search_batch call. On small
    # corpora the device step is much shorter than the device->host
    # fetch RTT; depth 2 overlaps one fetch with the next chunk's
    # compute (measured best — deeper only queues serial fetches).
    search_pipeline_depth: int = 2
    # How the three pipeline stages (dispatch / d2h fetch / assemble)
    # execute: "executor" = the shared two-thread PipelineExecutor
    # (chunks from CONCURRENT search calls overlap — the serving-path
    # win on high-RTT device links); "inline" = dispatch-then-drain on
    # the calling thread (per-call overlap only); "auto" = executor on
    # accelerator backends, inline on CPU (where fetches are free and
    # the thread hand-offs are pure overhead).
    search_pipeline_mode: str = "auto"

    # --- capacity bucketing (static shapes for XLA) ---
    min_doc_capacity: int = 1024
    min_nnz_capacity: int = 1 << 16
    min_vocab_capacity: int = 1 << 15

    # --- scoring layout ---
    # "ell": padded rows-by-document, gather/MXU scoring with precomputed
    #        impacts (TPU fast path). "coo": chunked scatter scoring.
    scoring_layout: str = "ell"
    # ceiling on an ELL row's width; a document's postings past it spill
    # to COO. None: no ceiling under the top of ops.ell.ELL_WIDTH_LADDER
    ell_width_cap: int | None = None
    # Fused Pallas gather kernel for big ELL blocks (avoids the XLA
    # path's [rows, width, B] HBM materialization — the gather-bound
    # bottleneck at 1M-doc scale). Small blocks always use the XLA path.
    use_pallas: bool = True

    # --- index mode ---
    # "rebuild": every commit re-lays-out the whole corpus (static corpora)
    # "segments": Lucene-style streaming segments — commit is O(new docs),
    #             tombstone deletes, tiered merging above max_segments
    #             (merges with more than sync_merge_nnz postings run on a
    #             background thread, off the commit critical path)
    index_mode: str = "rebuild"
    max_segments: int = 8
    sync_merge_nnz: int = 1 << 20
    # Background merges bound the shared transfer queue to ~one block
    # and, while a commit is concurrently running, additionally sleep
    # pace * (per-block upload time) so the commit's puts interleave
    # instead of queueing behind the merged postings (bounds
    # streaming-commit p99 where transfers share one link).
    # 0 disables pacing.
    merge_upload_pace: float = 1.0
    # Concurrent background merges (disjoint size tiers). One merge
    # thread cannot keep up with one new segment per commit at MS MARCO
    # streaming rates; the segment backlog then grows unboundedly.
    merge_workers: int = 2

    # --- tiered postings (engine/tiering.py; segments mode only) ---
    # Device-resident hot set + host/disk cold tier with block-max
    # skipping: segments beyond the HBM budget are evicted to manifested
    # spill dirs (mmap-ed back through the storage seam on fault-in) and
    # most are provably skipped per query batch by per-segment max-score
    # bounds. Off = every segment stays device-resident (pre-tiering
    # behavior). Not supported for tfidf_cosine (no sound bound).
    tier_enabled: bool = False
    # HBM budget for the hot set, in MiB. The budget is SOFT: in-flight
    # searches keep their views alive, and the segment being scored is
    # never evicted from under itself.
    tier_hot_budget_mb: int = 512
    # Relative inflation applied to every block-max upper bound so f32
    # device rounding can never push a true score above the host-side
    # f64 bound (the skip-soundness margin).
    tier_skip_margin: float = 1e-4
    # Upload-ring prefetch depth: how many upcoming cold segments the
    # searcher streams host->HBM ahead of scoring. 2 = double buffering.
    tier_ring_depth: int = 2
    # Cold spill directory. Empty = <index_path>/cold.
    tier_cold_dir: str = ""
    # Autopilot tier policy (requires autopilot_enabled): steers the
    # hot budget toward this tier hit rate — hit rate below target
    # grows the budget, above shrinks it, clamped to the MiB bounds.
    tier_hit_target: float = 0.9
    autopilot_tier_floor_mb: int = 64
    autopilot_tier_ceiling_mb: int = 4096

    # --- storage durability (utils/storage.py) ---
    # fsync-before-ack: an acked upload's raw bytes are fsynced (file +
    # directory, group-committed across concurrent requests) BEFORE the
    # HTTP 200 leaves the worker — the WAL's durability contract
    # applied to the data plane. Off trades the crash window for
    # throughput (tests, ephemeral deployments); atomic-rename publish
    # stays on either way.
    storage_fsync: bool = True
    # Versioned checkpoint dirs retained after a successful publish
    # (the current one plus N-1 fallbacks). Load falls back to the
    # newest INTACT version when the manifest check fails, quarantining
    # the corrupt one — with 1, there is nothing to fall back to.
    storage_keep_versions: int = 2
    # Background integrity-scrub pacing inside the leader's sweep loop
    # (verify placed_docs CRCs against the ledger + the current
    # checkpoint manifest; repair rotten copies from healthy replicas
    # through the anti-entropy machinery). Each pass re-reads the whole
    # store, so the default is minutes, not seconds — real scrubbers
    # run on hour scales. Negative disables; run_integrity_scrub()
    # still works on demand (POST /admin/scrub).
    storage_scrub_ms: float = 600000.0

    # --- checkpoint ---
    # Also store the committed snapshot's device arrays in checkpoints
    # so restore skips the O(corpus) host re-layout (~6x faster restore
    # at 1M docs). Costs one device->host fetch of the snapshot at save
    # time. (The segments payload is laid out on host — no device
    # fetch.)
    checkpoint_snapshot_arrays: bool = True
    # Serving-node checkpoints (the reference persists its index on
    # every upload, Worker.java:138). Empty path = <index_path>/checkpoint.
    # interval 0 disables the periodic autosave; /admin/checkpoint
    # triggers one on demand either way. A serve node restores from the
    # checkpoint at boot and then re-walks only documents modified after
    # the save (idempotent upserts keep rebuild-from-documents intact).
    checkpoint_path: str = ""
    checkpoint_interval_s: float = 0.0

    # --- shard recovery (SURVEY §5.3 — capability the reference lacks) ---
    # The leader keeps a durable copy of every document it places (its
    # own documents dir; the reference's leader-local disk is already a
    # download source, Leader.java:112-121) and, when a worker drops out
    # of the registry, re-places that worker's documents onto survivors
    # so the full corpus stays searchable. When the dead worker rejoins
    # (same URL), the leader reconciles by deleting the moved documents
    # from it. Byte recovery covers documents placed during the current
    # leader's tenure; replica OWNERSHIP survives failover through the
    # durable placement map below.
    shard_recovery: bool = True

    # --- replication (R-way placement + failover scatter reads) ---
    # Every uploaded document is placed on this many distinct
    # least-loaded workers (capped by the live worker count). Each
    # scatter assigns exactly ONE live, breaker-closed replica to score
    # each document (the sum-merge stays double-count-free by
    # construction); when that owner fails mid-request the leader
    # re-issues only the orphaned ownership slice to a surviving
    # replica WITHIN the same request, so single-worker death loses no
    # documents. 1 = the pre-replication single-copy behavior
    # (reference parity).
    replication_factor: int = 2
    # Hedged duplicate reads (The Tail at Scale): a worker that has not
    # answered its scatter RPC after this many milliseconds gets its
    # ownership slice speculatively re-issued to the next replica; the
    # merge dedups by owner epoch (the primary's reply wins if it
    # lands). 0 disables hedging.
    scatter_hedge_ms: float = 0.0
    # Debounce for persisting the leader's placement map (doc ->
    # replica set, plus pending-reconcile state) as znodes through the
    # coordination substrate, so a NEW leader resumes with exact
    # ownership instead of an empty in-memory map. Negative disables
    # persistence (per-tenure map only).
    placement_flush_ms: float = 50.0

    # --- elastic rebalancing (cluster/rebalance.py) ---
    # Leader-side live shard migration: the sweep loop detects
    # overloaded shards (doc count above the cluster mean + slack, or
    # above the absolute cap below) and underused capacity (a freshly
    # joined worker far below the mean) and migrates doc ranges live —
    # copy to targets, durably flip ownership through the placement
    # znode, reconcile-delete the old copies. Searches stay exact
    # throughout (per-request owner assignment makes the flip atomic).
    rebalance_enabled: bool = True
    # Absolute per-worker doc-count cap: a shard above it donates docs
    # even when the cluster is otherwise balanced. 0 = no cap
    # (balance-to-mean only).
    rebalance_max_shard_docs: int = 0
    # Self-pacing for the rebalance pass inside the reconcile sweep
    # loop (the sweep interval is the floor). Negative disables the
    # automatic pass; /api/drain and run_once() still work.
    rebalance_sweep_ms: float = 5000.0
    # Self-pacing for the residue anti-entropy pass (ghost/orphan
    # reconciliation of unmapped engine copies left behind by
    # partitions — cluster/placement.py reconcile_residue). Negative
    # disables; run_residue_reconcile() still works on demand.
    residue_sweep_ms: float = 5000.0

    # --- coordination durability + quorum (cluster/wal.py, ensemble.py) ---
    # Empty data dir = in-memory substrate (the pre-durability behavior).
    # Set it and every coordinator write goes through a CRC-framed,
    # fsynced WAL with periodic snapshots; a crashed coordinator
    # restarted on the same dir recovers the full znode tree + sessions.
    coord_data_dir: str = ""
    # This member's id and the full member map ("id=host:port,..."
    # including self). With peers set the coordinator is one member of a
    # Raft-style ensemble: writes are acknowledged only after a majority
    # has them durably, so a 3-member ensemble survives the loss of any
    # one member with zero lost acknowledged writes.
    coord_node_id: str = ""
    coord_peers: str = ""
    # fsync every WAL append before acknowledging (the Raft/ZooKeeper
    # contract). Off trades the crash-tail window for throughput.
    wal_fsync: bool = True
    # Snapshot + compact the WAL every N applied commands.
    wal_snapshot_every: int = 512
    # Election timeout base (randomized 1x-2x per member) and the
    # leader's heartbeat/replication interval; commit timeout bounds how
    # long a write waits for quorum before failing WITHOUT an ack.
    ensemble_election_timeout_s: float = 1.0
    ensemble_heartbeat_s: float = 0.25
    ensemble_commit_timeout_s: float = 5.0

    # --- admission control / overload shedding (cluster/admission.py) ---
    # Master switch for the leader's front-door admission layer
    # (token-bucket rate limiting + queue-depth backpressure on the
    # /leader/* endpoints). Health/metrics endpoints are never
    # admission-controlled regardless.
    admission_enabled: bool = True
    # Per-client sustained admission rate (client id = X-Client-Id
    # header, else peer IP). 0 = unlimited (backpressure still sheds).
    admission_rate_qps: float = 0.0
    # Token-bucket capacity (burst allowance). 0 = 2x admission_rate_qps.
    admission_burst: float = 0.0
    # Backpressure watermarks on the last_scatter_queue_depth gauge
    # (queries left queued after each coalesced batch formed — the same
    # signal the k8s HPA scales workers on): at/above high_water the
    # BULK lane sheds; at/above critical interactive sheds too. 0
    # disables that watermark.
    admission_queue_high_water: int = 128
    admission_queue_critical: int = 512
    # Retry-After hint (seconds) on backpressure sheds (rate-limit
    # sheds compute the honest time-to-next-token instead).
    admission_retry_after_s: float = 0.25
    # Bound on distinct per-client token buckets (LRU-evicted beyond).
    admission_max_clients: int = 4096
    # Weighted-dequeue share of each scatter batch reserved for the
    # bulk lane while interactive traffic is queued (so neither lane
    # can starve the other; interactive always fills first). 0 = bulk
    # rides strictly behind interactive.
    scatter_bulk_share: float = 0.25
    # Leader-side query-result cache entries (LRU), keyed by the
    # df-signature + commit-generation token so any upsert/delete/
    # migration-flip/membership change invalidates — zipfian (skewed-
    # popularity) traffic answers repeats without touching a worker.
    # 0 disables the cache.
    result_cache_entries: int = 1024

    # --- scale-out query plane (cluster/router.py) ---
    # Any-node reads: a NON-leader node serves /leader/start through a
    # read-only follower view of the durable placement znode (watch-
    # refreshed) instead of refusing or falling back to the legacy
    # sum-merge (which double-counts R-replicated documents). Requires
    # placement persistence (placement_flush_ms >= 0); off = the
    # pre-router behavior.
    router_any_node_reads: bool = True
    # Mutation-plane discipline: a non-leader node (and every
    # dedicated router) forwards /leader/upload[-batch] and
    # /leader/delete to the elected leader published at /leader_info —
    # all mutations stay on the leader. Off = serve locally (legacy).
    router_forward_writes: bool = True
    # Periodic placement-view refresh backstop in milliseconds (the
    # data watch on the placement znode is the primary signal; the
    # backstop covers missed watches across coordinator failovers).
    router_refresh_ms: float = 1000.0
    # Honest-staleness threshold: when the follower view has not been
    # confirmed current for this long (coordinator partition), every
    # read response is marked degraded (X-Scatter-Degraded with
    # stale_view=1) and the router's result cache is bypassed until
    # the view self-heals. 0 disables the marker.
    router_stale_ms: float = 5000.0
    # Per-router generation-keyed result-cache entries (LRU), keyed by
    # (membership epoch, placement view version) — every observed
    # placement flush invalidates. 0 disables.
    router_cache_entries: int = 1024

    # --- resilience (cluster plane) ---
    # Leader->worker RPC retry policy: bounded attempts with exponential
    # backoff + jitter; only transient failures (connection-level, 5xx)
    # are retried — see cluster/resilience.py. deadline 0 = attempts-only.
    rpc_max_attempts: int = 3
    rpc_backoff_base_s: float = 0.05
    rpc_backoff_max_s: float = 2.0
    rpc_retry_deadline_s: float = 10.0
    # Per-worker circuit breaker: closed -> open after N consecutive
    # failed logical RPCs -> one half-open probe after reset_s. An open
    # breaker fast-fails scatter/placement calls to that worker (counted
    # as degraded, never as a silent empty merge).
    breaker_failure_threshold: int = 5
    breaker_reset_s: float = 5.0
    # Gray-failure detection: a worker whose SUCCESSFUL-call latency
    # EWMA exceeds this threshold trips its circuit breaker anyway
    # (counted in breaker_slow_trips) — a slow-but-alive worker never
    # fails a call, so consecutive-failure counting would let it drag
    # every scatter it owns to the deadline. 0 disables.
    breaker_slow_threshold_ms: float = 0.0
    # Minimum successful samples in the EWMA before a slow trip may
    # fire (one outlier RPC must not condemn a healthy worker).
    breaker_slow_min_samples: int = 5
    # Periodic leader sweep retrying failed rejoin reconciles
    # (/worker/delete) so moved documents cannot stay double-indexed
    # until the next membership event; pending names are excluded from
    # that worker's merged results meanwhile. 0 disables the sweep.
    reconcile_sweep_interval_s: float = 2.0
    # Compile-fault retry: max retries charged per query-batch bucket
    # size; a deterministic compile error (a kernel the compiler
    # refuses at a new bucket) stops being retried once the bucket's
    # budget is spent.
    compile_retry_per_bucket: int = 2

    # --- SLO autopilot (cluster/autopilot.py) ---
    # Master kill switch for the leader-side closed-loop controller
    # that tunes the serving knobs (scatter hedge delay, admission
    # watermarks, adaptive-linger ceiling, gray-failure slow-trip
    # threshold) from the live PR-9 histograms. Off = every knob keeps
    # its static config value, exactly as before; flipping it off at
    # runtime (POST /api/autopilot) reverts every managed knob to
    # static INSTANTLY.
    autopilot_enabled: bool = False
    # Control-sweep self-pacing inside the reconcile sweep loop (the
    # sweep interval is the floor). Negative disables the automatic
    # pass; run_once() still works on demand.
    autopilot_interval_ms: float = 2000.0
    # Relative hysteresis dead band: a knob moves only when the sensed
    # target differs from the current value by more than this fraction
    # — the noise filter that makes oscillation structurally hard.
    autopilot_hysteresis: float = 0.15
    # Damping: fraction of the (target - current) error applied per
    # adjustment. 1.0 would jump straight to the target (and ring on
    # noisy sensors); 0.5 converges geometrically.
    autopilot_step: float = 0.5
    # Direction confirmation: a knob moves only after this many
    # CONSECUTIVE sweeps proposed the same direction, so a one-window
    # sensor blip can never reverse an adjustment trend.
    autopilot_confirm: int = 2
    # Bound on the decision-audit ring (GET /api/autopilot).
    autopilot_ring: int = 256
    # Minimum observations a sensor window needs before its controller
    # may act (a 3-sample p95 is noise, not a signal).
    autopilot_min_window: int = 16
    # The one number the operator owns: the admitted-interactive p99
    # target the watermark controller steers toward. Everything else
    # is derived.
    autopilot_p99_slo_ms: float = 600.0
    # Hedge controller: scatter_hedge_ms tracks the windowed scatter-
    # leg p95 plus this epsilon, clamped to [floor, ceiling].
    autopilot_hedge_epsilon_ms: float = 10.0
    autopilot_hedge_floor_ms: float = 5.0
    autopilot_hedge_ceiling_ms: float = 2000.0
    # Watermark controller clamps (admission_queue_high_water; the
    # critical mark keeps the static critical/high ratio).
    autopilot_queue_floor: int = 4
    autopilot_queue_ceiling: int = 8192
    # Linger controller clamps on the adaptive scatter linger CEILING
    # (scatter_linger_max_ms; the floor bound stays static).
    autopilot_linger_floor_ms: float = 1.0
    autopilot_linger_ceiling_ms: float = 50.0
    # Slow-trip controller: breaker_slow_threshold_ms is derived from
    # the cross-worker successful-call latency-EWMA spread (median x
    # this multiple), clamped below.
    autopilot_slow_spread_mult: float = 4.0
    autopilot_slow_floor_ms: float = 50.0
    autopilot_slow_ceiling_ms: float = 5000.0

    # --- observability (utils/tracing.py, utils/metrics.py) ---
    # Bound on the in-process span ring buffer (finished spans kept for
    # GET /api/trace). Appends are GIL-atomic deque ops — the bound is
    # memory, not locking.
    trace_ring_spans: int = 4096
    # Fraction of ROOT traces sampled into the ring (children and
    # remote continuations inherit the decision). 1.0 records every
    # request; 0 disables recording and propagation while keeping trace
    # ids on the local node's log lines (correlation without retention).
    trace_sample_rate: float = 1.0
    # Threshold for the slow-query log: a /leader/start request slower
    # than this logs one warn line carrying its trace id (joinable with
    # /api/trace) and counts in `slow_queries`. 0 disables.
    trace_slow_query_ms: float = 0.0

    # --- wire-protocol versioning (cluster/protover.py) ---
    # Compat-window floor for the data planes (/leader/*, /worker/*): a
    # request declaring a wire-protocol version below this is answered
    # 426 + X-Proto-Rejected: 1 (distinct, non-retryable, never a
    # worker fault). Requests with no version header are implicitly
    # version 1 (the pre-versioning wire), so the default floor keeps
    # old binaries interoperating; raise it only after the whole fleet
    # runs a binary at or above the new floor. Versions ABOVE ours are
    # always accepted (forward compatibility — no ceiling).
    proto_min_compat: int = 1

    # --- traffic capture/replay (utils/storage.py RequestLog) ---
    # Durable request-log path for admitted /leader/start traffic
    # (query + arrival offset + lane + client id), written through the
    # storage seam's CRC-framed append log so a torn tail truncates
    # cleanly instead of corrupting the capture. Empty disables the
    # tap. `RequestLog.read(path)` returns the admitted stream with its
    # original inter-arrival offsets, for a load generator to replay.
    replay_capture_path: str = ""
    # Bound on captured entries per log (memory- and disk-bounded like
    # the trace ring); the tap stops appending once reached.
    replay_capture_max: int = 100000

    # --- ingest ---
    # C++ tokenize+count+id-map fast path (tfidf_tpu/native); falls back
    # to the pure-Python analyzer when no compiler is available or for
    # non-ASCII documents — results are identical either way.
    native_ingest: bool = True

    # --- compute-plane chaos + degradation (ISSUE 20) ---
    # Gate on the /api/device-nemesis runtime-control endpoint (the
    # scriptable device-fault injector at the JAX dispatch seams,
    # utils/device_nemesis.py). The TFIDF_DEVICE_NEMESIS env var arms
    # rules regardless of this knob — this only exposes the HTTP
    # control surface, which production deployments keep off. Named
    # *_api so the env override (TFIDF_DEVICE_NEMESIS_API) can never
    # collide with the rule-script variable.
    device_nemesis_api: bool = False
    # Host/numpy degraded scoring when the device faults repeatedly:
    # exact same bits as the XLA scoring path (engine/compute_health.py
    # mirrors the pinned-order reductions), honest latency, responses
    # stamped X-Compute-Degraded. Off = faults surface to callers and
    # leader failover is the only recourse.
    compute_fallback: bool = True
    # ComputeHealth state machine: consecutive device faults before the
    # worker reports "degraded" (health surface only) and before it
    # goes "sick" (device dispatch suspended; host fallback serves).
    compute_degraded_after: int = 2
    compute_sick_after: int = 5
    # Seconds between single-probe device retries while sick — the
    # recovery path back to the exact device plane.
    compute_probe_interval_s: float = 5.0
    # Poison-query quarantine (leader/router): a (query, plan)
    # fingerprint is quarantined after compute faults on this many
    # DISTINCT replicas (1 replica = possibly a sick device; 2+ = the
    # query itself is the trigger), then answered 422 +
    # X-Poison-Quarantined without touching workers.
    poison_quarantine_after: int = 2
    # Quarantine entry TTL and LRU bound — poison verdicts expire so a
    # fixed kernel/binary gets a retry, and the table stays bounded.
    poison_quarantine_ttl_s: float = 300.0
    poison_quarantine_max: int = 256
    # OOM backoff ladder floor: an alloc-OOM at batch B retries at B/2,
    # B/4, ... but never below this (at the floor the fallback or the
    # caller takes over) — so one huge batch degrades, not dies.
    oom_backoff_min_batch: int = 8

    # --- misc ---
    seed: int = 0

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def _coerce(raw: str, ty: type) -> Any:
    if ty is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    if ty is int:
        return int(raw)
    if ty is float:
        return float(raw)
    if ty is str:
        return raw
    # tuples and anything else: JSON
    val = json.loads(raw)
    return tuple(val) if isinstance(val, list) else val


def load_config(path: str | None = None, env: dict[str, str] | None = None,
                **overrides: Any) -> Config:
    """Build a Config from (lowest to highest precedence): defaults, a JSON
    config file, ``TFIDF_*`` environment variables, keyword overrides."""
    env = os.environ if env is None else env
    values: dict[str, Any] = {}
    if path and os.path.exists(path):
        with open(path) as f:
            loaded = json.load(f)
        for f_ in dataclasses.fields(Config):
            if f_.name in loaded:
                v = loaded[f_.name]
                values[f_.name] = tuple(v) if isinstance(v, list) else v
    for f_ in dataclasses.fields(Config):
        key = _ENV_PREFIX + f_.name.upper()
        if key in env:
            base = Config.__dataclass_fields__[f_.name].default
            # a default of None (ell_width_cap) reads as JSON
            ty = str if isinstance(base, dataclasses._MISSING_TYPE) \
                else type(base)
            values[f_.name] = _coerce(env[key], ty)
    values.update(overrides)
    return Config(**values)
