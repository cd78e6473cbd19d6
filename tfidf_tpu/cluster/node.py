"""SearchNode — the symmetric node binary (L2 + L3 + ops API).

Every node runs the same code (like the reference's single Spring Boot
binary); the role is decided at runtime by leader election. The HTTP surface
is API-compatible with the reference so a reference client can switch
unmodified:

Worker data plane (``worker/Worker.java``):
    POST /worker/process      — score a query against the local shard (:175)
    POST /worker/upload       — save + index one document (:125)
    POST /worker/upload-batch — framework addition: bulk text ingest
    GET  /worker/download     — stream a document, traversal-safe (:97)
    GET  /worker/index-size   — load metric in bytes (:147)

Leader control plane (``leader/Leader.java``):
    POST /leader/start        — scatter-gather search, sum-merge (:39-92)
    POST /leader/upload       — least-loaded placement (:153-207)
    POST /leader/upload-batch — framework addition: bulk placement
    GET  /leader/download     — local disk, else probe workers (:95-151)

Ops (``controller/Controllers.java``):
    GET  /api/status          — am-I-leader (:25-29)
    GET  /api/services        — live membership (:30-37)
    GET  /api/metrics         — framework addition: counters + timings

Intentional departures from the reference (flagged per SURVEY.md §3.2):
the scatter fan-out is parallel (the reference loops serially,
``Leader.java:51-70``); result ordering defaults to score-descending with
``result_order="name"`` reproducing the reference's alphabetical TreeMap
(``Leader.java:80-91``).
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
import zlib
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from http.server import BaseHTTPRequestHandler  # noqa: F401 (re-export)

from tfidf_tpu.cluster.admission import (LANE_BULK, AdmissionController,
                                         ResultCache)
from tfidf_tpu.cluster.autopilot import Autopilot
from tfidf_tpu.cluster.batcher import Coalescer, QueryBatcher
from tfidf_tpu.cluster.coordination import NoNodeError
from tfidf_tpu.cluster.wire import pack_hit_lists, pack_topk_arrays
from tfidf_tpu.cluster.election import LeaderElection
from tfidf_tpu.cluster.fencing import (FENCE_EPOCH_HEADER, FENCE_HEADER,
                                       FENCE_REJECTED_HEADER,
                                       FENCE_STATUS, FenceGuard)
from tfidf_tpu.cluster.nemesis import global_nemesis
from tfidf_tpu.cluster.protover import (PROTO_REJECTED_HEADER,
                                        PROTO_VERSION, proto_headers)
from tfidf_tpu.cluster.placement import PlacementFollower, PlacementMap
from tfidf_tpu.cluster.rebalance import Rebalancer
from tfidf_tpu.cluster.quarantine import (PoisonQuarantine,
                                          poison_fingerprint)
from tfidf_tpu.cluster.registry import (ServiceRegistry,
                                        publish_leader_info,
                                        read_leader_info)
from tfidf_tpu.cluster.resilience import (ClusterResilience,
                                          RpcStatusError,
                                          classify_compute_fault,
                                          is_fence_rejection)
# the read plane (scatter/merge/failover/hedge spine + the shared HTTP
# handler plumbing) lives in cluster/router.py — the scale-out query
# plane: SearchNode hosts it beside its mutation plane; the stateless
# QueryRouter hosts it alone (router.py imports nothing from this
# module at load time, so the split is cycle-free)
from tfidf_tpu.cluster.router import (ScatterReadPlane, _HttpHandlerBase,
                                      _PlaneServer, _linger_bounds,
                                      list_routers)
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.engine.searcher import TooManyQueryTerms
from tfidf_tpu.ops.analyzer import UnsupportedMediaType
from tfidf_tpu.utils import storage
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.faults import global_injector
from tfidf_tpu.utils.logging import get_logger
from tfidf_tpu.utils.metrics import global_metrics
from tfidf_tpu.utils.tracing import (SERVER_TIMING_HEADER, epoch_now,
                                     global_tracer, process_watch,
                                     propagation_headers, server_timing,
                                     span_event, trace_phase)

log = get_logger("cluster.node")


# ---- tiny HTTP client helpers (RestTemplate analog, Leader.java:42) ----
#
# Both helpers (and _ScatterClient.post below) pass through the nemesis
# shim (cluster/nemesis.py): an ``origin`` identifies the calling node
# so tests can script per-link partitions/latency/corruption without
# monkeypatching any call site. No rules armed = one emptiness check.
# They are ALSO the trace-propagation seams: when the calling thread
# has an active span, its X-Trace-Id/X-Span-Id ride every outbound
# request (explicit caller headers win on collision), so the trace
# context crosses every leader->worker RPC by construction. Every
# outbound request also stamps X-Proto-Version (cluster/protover.py)
# beside X-Leader-Epoch where that rides, and the assembled headers
# pass through the nemesis skew filter (filter_headers) so the
# rolling-upgrade chaos can mask them per link.

def http_get(url: str, timeout: float = 10.0,
             origin: str | None = None) -> bytes:
    global_nemesis.check_send(origin, url)
    h = proto_headers()
    h.update(propagation_headers())
    h = global_nemesis.filter_headers(origin, url, h)
    req = urllib.request.Request(url, headers=h)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return global_nemesis.filter_reply(origin, url, r.read())


class _ScatterClient:
    """Keep-alive HTTP POST client for the leader's per-query worker RPCs.

    The reference builds a fresh ``RestTemplate`` (and TCP connection) per
    call (``Leader.java:42,127,162``); at hundreds of scatter RPCs per
    second the connection setup + urllib opener machinery becomes a real
    per-query host cost. Fan-out pool threads are long-lived, so one
    persistent connection per (thread, worker) amortizes it away. A
    dropped keep-alive connection is retried once on a fresh one; any
    non-2xx status raises (the caller already treats per-worker errors as
    tolerated scatter failures).

    IDEMPOTENT RPCs ONLY: the stale-connection retry re-sends the whole
    request, and the first attempt may already have reached (even been
    processed by) the worker if the connection died after the body went
    out. Search reads (``/worker/process``, ``/worker/process-batch``)
    are safe; routing an upload through this client could double-apply
    it — uploads go through :func:`http_post` (no retry) instead."""

    # failures that mean "the keep-alive connection went stale between
    # requests" — retried once on a fresh connection. Timeouts and other
    # errors propagate immediately: retrying a hung worker would double
    # the leader's per-worker scatter budget.
    _RETRYABLE = (ConnectionResetError, ConnectionRefusedError,
                  BrokenPipeError)

    def __init__(self) -> None:
        self._tls = threading.local()
        # this node's endpoint identity for the nemesis shim (stamped
        # by SearchNode.start once the server port is known)
        self.origin = ""

    def pop_server_timing(self) -> str | None:
        """The ``Server-Timing`` header of the LAST 2xx reply on THIS
        thread (None: the worker sent none), popped like
        :meth:`pop_degraded`: ``tracing.trace_rpc_legs`` cuts the round
        trip at the worker's two stamps it carries."""
        v = getattr(self._tls, "server_timing", None)
        self._tls.server_timing = None
        return v

    def pop_degraded(self) -> bool:
        """Did the LAST 2xx reply on THIS thread carry
        ``X-Compute-Degraded``? Thread-local (the scatter pool runs one
        RPC per thread at a time), popped by the gatherer right after
        the call returns — so one request's degraded verdict can never
        leak into a concurrent request's health marker."""
        v = getattr(self._tls, "degraded", False)
        self._tls.degraded = False
        return v

    def post(self, base: str, path: str, data: bytes,
             timeout: float = 10.0, live: set[str] | None = None,
             headers: dict[str, str] | None = None) -> bytes:
        import http.client
        global_nemesis.check_send(self.origin, base)
        u = urllib.parse.urlparse(base)
        conns = getattr(self._tls, "conns", None)
        if conns is None:
            conns = self._tls.conns = {}
        if live is not None:   # prune departed workers' idle sockets
            for b in list(conns):
                if b not in live:
                    conns.pop(b).close()
        retryable = self._RETRYABLE + (
            http.client.BadStatusLine, http.client.CannotSendRequest,
            http.client.NotConnected)
        last: Exception | None = None
        for _ in range(2):
            c = conns.get(base)
            if c is not None and c.timeout != timeout:
                # connections cache per (thread, worker) but callers mix
                # timeouts (10s per-query scatter vs scatter_timeout_s
                # batched) — retune the live socket instead of silently
                # keeping the first caller's timeout
                c.timeout = timeout
                if c.sock is not None:
                    c.sock.settimeout(timeout)
            try:
                if c is None:
                    import socket as _socket
                    c = http.client.HTTPConnection(
                        u.hostname, u.port, timeout=timeout)
                    c.connect()
                    # http.client leaves Nagle on; with the unbuffered
                    # small-write HTTP framing both sides use, Nagle +
                    # delayed ACK can add tens of ms per RPC. Cache only
                    # AFTER the connect + setsockopt succeed — a cached
                    # never-connected object would auto-reconnect inside
                    # request() later without TCP_NODELAY
                    c.sock.setsockopt(_socket.IPPROTO_TCP,
                                      _socket.TCP_NODELAY, 1)
                    conns[base] = c
                h = {"Content-Type": "application/json"}
                h.update(proto_headers())
                h.update(propagation_headers())
                h.update(headers or {})
                h = global_nemesis.filter_headers(self.origin, base, h)
                c.request("POST", path, body=data, headers=h)
                r = c.getresponse()
                body = global_nemesis.filter_reply(self.origin, base,
                                                   r.read())
                if r.status >= 300:
                    # typed status error: the resilience layer retries
                    # gateway-transient statuses (502/503/504) and —
                    # only after Retry-After — 429 sheds; never other
                    # 4xx (application), deterministic 500s, or a
                    # worker's honest deadline refusal (the budget
                    # cannot come back — see X-Deadline-Ms)
                    ra = r.getheader("Retry-After")
                    try:
                        ra_s = float(ra) if ra else None
                    except ValueError:
                        ra_s = None   # HTTP-date form: treat as absent
                    fps = r.getheader("X-Poison-Fingerprints") or ""
                    raise RpcStatusError(
                        f"{base}{path}", r.status,
                        deadline_exceeded=(
                            r.getheader("X-Deadline-Exceeded") == "1"),
                        retry_after_s=ra_s,
                        fenced=(r.getheader(FENCE_REJECTED_HEADER)
                                == "1"),
                        proto=(r.getheader(PROTO_REJECTED_HEADER)
                               == "1"),
                        compute_fault=r.getheader("X-Compute-Fault"),
                        poison_fps=tuple(
                            f for f in fps.split(",") if f))
                # host-fallback honesty flows through the gather: a 2xx
                # served by the worker's numpy mirror is exact but
                # degraded — the gatherer pops this per-thread flag
                self._tls.degraded = (
                    r.getheader("X-Compute-Degraded") == "1")
                self._tls.server_timing = r.getheader(SERVER_TIMING_HEADER)
                return body
            except RuntimeError:
                raise
            except retryable as e:
                last = e
                c.close()
                conns.pop(base, None)
            except Exception:
                c.close()
                conns.pop(base, None)
                raise
        raise last if last is not None else RuntimeError("post failed")


def http_post(url: str, data: bytes, content_type: str = "application/json",
              timeout: float = 30.0, headers: dict | None = None,
              origin: str | None = None) -> bytes:
    global_nemesis.check_send(origin, url)
    h = {"Content-Type": content_type}
    h.update(proto_headers())
    h.update(propagation_headers())
    h.update(headers or {})
    h = global_nemesis.filter_headers(origin, url, h)
    req = urllib.request.Request(url, data=data, headers=h)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return global_nemesis.filter_reply(origin, url, r.read())


def http_get_stream(url: str, timeout: float = 30.0,
                    origin: str | None = None):
    """Streaming GET through the shared seams: nemesis-instrumented and
    trace-propagating like :func:`http_get`, but returns the OPEN
    response object for chunked copying (the download probes) instead
    of buffering the body. Reply-corruption nemesis rules do not apply
    to streams — the seam contract here is send-side (partitions,
    latency), which is what the download-path chaos needs.

    (graftcheck protocol finding, fixed: the leader's and router's
    ``/worker/download`` probes previously called ``urlopen`` raw, so
    a scripted partition could never cut the download path and the
    probe hop dropped out of the request trace.)"""
    global_nemesis.check_send(origin, url)
    h = proto_headers()
    h.update(propagation_headers())
    h = global_nemesis.filter_headers(origin, url, h)
    req = urllib.request.Request(url, headers=h)
    return urllib.request.urlopen(req, timeout=timeout)


class WorkerDeadline(RuntimeError):
    """The caller's propagated scatter budget (``X-Deadline-Ms``) ran
    out before scoring began — the worker refuses to start, the handler
    answers 504 + ``X-Deadline-Exceeded: 1``, and the leader's
    resilience layer classifies that as non-retryable."""


class SearchNode(ScatterReadPlane):
    """One node: engine + election + registry + HTTP server.

    Role split (cluster/router.py): the READ plane — the scatter /
    owner-merge / failover / hedge spine behind ``/leader/start`` and
    ``/leader/download`` — is inherited from :class:`ScatterReadPlane`
    and runs on EVERY node; only the placement view differs by role
    (the elected leader routes reads through its authoritative map, a
    non-leader through a watch-refreshed follower view of the durable
    placement znode, so any node serves exact reads without the legacy
    sum-merge's replica double-count). The MUTATION plane — placement
    routing, replication, reconcile/repair, rebalance, deletes — runs
    only on the elected leader; a non-leader forwards front-door
    mutations to the leader published at ``/leader_info``."""

    def __init__(self, config: Config | None = None, coord=None,
                 engine: Engine | None = None, coord_factory=None) -> None:
        """``coord_factory`` (no-arg callable returning a fresh coordination
        client) enables rejoin after a session expiry — the capability the
        reference lacks (its ``Application.process`` only logs and
        ``notifyAll``s on disconnect, ``app/Application.java:49-66``; an
        expired node stays out of the cluster until the pod restarts)."""
        self.config = config or Config()
        # distributed tracing knobs (utils/tracing.py): ring bound +
        # root sampling rate. The tracer is process-global (like the
        # metrics registry); in-process test clusters share one ring.
        global_tracer.configure(
            max_spans=self.config.trace_ring_spans,
            sample_rate=self.config.trace_sample_rate)
        if coord is None and coord_factory is not None:
            coord = coord_factory()
        assert coord is not None, "a coordination client is required"
        self.coord = coord
        self._coord_factory = coord_factory
        self._stopping = False
        self._watching = False   # holds a share of process_watch
        self.engine = engine or Engine(self.config)
        self.registry = ServiceRegistry(
            coord, on_change=self._on_membership_change)
        self.election = LeaderElection(coord, callback=self)
        coord.on_session_event(self._on_session_event)
        self._pool = ThreadPoolExecutor(
            max_workers=self.config.fanout_workers,
            thread_name_prefix="fanout")
        # failover/hedge slice re-issues get their OWN pool: on the
        # shared fan-out pool they would queue behind the very laggard
        # primaries they exist to race, turning hedging into a no-op
        # exactly under the saturation it targets
        self._slice_pool = ThreadPoolExecutor(
            max_workers=max(4, self.config.fanout_workers // 2),
            thread_name_prefix="slice")
        self._scatter = _ScatterClient()
        # concurrent /worker/process requests coalesce into one device
        # batch (the kernels are built for [B] batches; the reference
        # scores one query per POST, Worker.java:175-186)
        self.batcher = (QueryBatcher(
            self.engine, max_batch=self.config.query_batch,
            linger_s=self.config.batch_linger_ms / 1e3,
            pipeline=self.config.batch_pipeline,
            **_linger_bounds(self.config.batch_linger_min_ms,
                             self.config.batch_linger_max_ms))
            if self.config.micro_batch else None)
        # leader-side scatter batching: concurrent /leader/start queries
        # group into ONE batched RPC per worker (see leader_search /
        # _scatter_search_batch). The reference fans out one JSON RPC per
        # (query, worker) — Leader.java:51-70 — whose per-query Python
        # cost caps the distributed path far below the engine beneath it.
        # per-owner-set batch keys: the group key is the membership
        # epoch at SUBMIT time, so one coalesced batch never mixes
        # queries from before and after a membership transition — each
        # dispatched batch maps onto exactly one ownership world view
        self.scatter_batcher = (Coalescer(
            self._scatter_search_batch,
            max_batch=self.config.scatter_batch,
            linger_s=self.config.scatter_linger_ms / 1e3,
            pipeline=self.config.scatter_pipeline, name="scatter",
            # items are (query, mode, fusion): one coalesced batch is
            # one ownership world view AND one retrieval plan — sparse,
            # dense and hybrid queries never share a scatter RPC
            group_key=lambda q: (self._cluster_epoch, q[1], q[2])
            if isinstance(q, tuple) else (self._cluster_epoch,
                                          "sparse", None),
            bulk_share=self.config.scatter_bulk_share,
            **_linger_bounds(self.config.scatter_linger_min_ms,
                             self.config.scatter_linger_max_ms))
            if (self.config.scatter_micro_batch
                and not self.config.unbounded_results) else None)
        # overload-survival front door (cluster/admission.py): the
        # /leader/* handlers admit-or-shed BEFORE any work is queued,
        # keyed on the scatter coalescer's queue depth + per-client
        # token buckets. /api/health and /api/metrics never pass
        # through it. The depth signal is the MAX of the left-behind
        # gauge (the k8s HPA signal, refreshed at batch formation) and
        # the coalescer's live backlog — the gauge alone freezes while
        # every dispatcher thread is blocked in a stalled scatter RPC,
        # which is exactly when admitted requests would otherwise queue
        # unboundedly with zero sheds.
        self.admission = AdmissionController(
            self.config,
            depth_fn=lambda: max(
                global_metrics.get("last_scatter_queue_depth", 0.0),
                float(self.scatter_batcher.backlog())
                if self.scatter_batcher is not None else 0.0))
        # leader-side query-result cache, keyed by df_signature(): the
        # (membership epoch, commit generation) token advances on every
        # mutation this leader orchestrates — confirmed upload legs,
        # reconcile deletes, migration flips, membership transitions —
        # so a cached result can never outlive the corpus state it was
        # computed from (no TTL; invalidation rides the same version
        # plumbing that keys the engine's segment view cache)
        # disabled for unbounded-results (parity) configs, mirroring
        # scatter_batcher above: without top-k truncation every cached
        # value is a full-corpus score dict, so the entry-count bound
        # is no memory bound at all (1024 entries x 1M-doc dicts)
        self.result_cache = (ResultCache(self.config.result_cache_entries)
                             if (self.config.result_cache_entries > 0
                                 and not self.config.unbounded_results)
                             else None)
        # traffic-capture tap (utils/storage.py RequestLog): admitted
        # /leader/start requests land in a durable replayable log when
        # the knob names a path (``RequestLog.read`` gives it back)
        self.request_log = (storage.RequestLog(
            self.config.replay_capture_path,
            self.config.replay_capture_max)
            if self.config.replay_capture_path else None)
        # poison-query quarantine (ISSUE 20, cluster/quarantine.py):
        # the read plane's memory of (query, plan) pairs that killed
        # devices on distinct replicas — consulted by _serve_search
        # before any fan-out, fed by _gather_merge's per-worker blame
        self.quarantine = PoisonQuarantine(
            after=self.config.poison_quarantine_after,
            ttl_s=self.config.poison_quarantine_ttl_s,
            max_entries=self.config.poison_quarantine_max)
        self._result_gen = 0
        self._result_gen_lock = threading.Lock()
        # cached role for /api/health: the real is_leader() is a
        # coordination READ (an RPC on the client transport) — the
        # health endpoint must stay responsive while the cluster sheds,
        # so it reports the last role transition instead of blocking
        self._role = "worker"
        # near-real-time commit policy (Lucene NRT readers): uploads
        # defer the commit; the next search commits pending writes first,
        # so read-your-writes visibility matches the reference's
        # commit-per-upload (Worker.java:138) without its O(corpus)
        # per-document cost on bulk ingest
        self._dirty = False
        self._commit_lock = threading.Lock()
        # transient-compile retry budget per query-batch bucket size: a
        # successful search at a bucket refills it; a deterministic
        # compile error (e.g. OOM at a new bucket) drains it and stops
        # being retried, so it cannot double every batch's cost forever
        self._compile_retry_lock = threading.Lock()
        self._compile_retries_used: dict[int, int] = {}
        # leader-side upload placement: TTL cache over worker index
        # sizes + the R-way replica map (re-uploads route to the
        # holders, upserting every copy; see leader_upload and
        # cluster/placement.py). The map is durable: the persister
        # writes it through the coordination substrate so a NEW leader
        # resumes with exact ownership + pending-reconcile state.
        self._size_cache: tuple[float, dict[str, int]] = (0.0, {})
        # worker -> monotonic eviction time: a poll STARTED before the
        # eviction carries pre-failure data for that worker and must not
        # resurrect it into the cache (see _ensure_sizes_fresh)
        self._evicted: dict[str, float] = {}
        self.placement = PlacementMap(
            flush_ms=self.config.placement_flush_ms,
            name=str(self.config.port))
        self.placement.bind_store(lambda: self.coord)
        # leadership fence on every flush (see PlacementMap.persist_gate)
        self.placement.persist_gate = self.is_leader
        # scale-out query plane (cluster/router.py): a NON-leader node
        # serves /leader/start through this read-only follower view of
        # the durable placement znode (watch-refreshed) instead of its
        # empty post-demotion map — without it, a worker answering a
        # read would fall back to the legacy sum-merge across every
        # replica and silently double-count R-replicated documents.
        # None when any-node reads are disabled or the map is not
        # persisted (nothing to follow).
        self.placement_follower: PlacementFollower | None = None
        if (self.config.router_any_node_reads
                and self.config.placement_flush_ms >= 0):
            self.placement_follower = PlacementFollower(
                name=f"n{self.config.port}",
                refresh_ms=self.config.router_refresh_ms,
                stale_ms=self.config.router_stale_ms)
            self.placement_follower.bind_store(lambda: self.coord)
        # elected-leader address cache for the read plane's write
        # forwarding (ScatterReadPlane.leader_url)
        self._leader_cache = (0.0, None)
        # aliases kept for the lock-ordering discipline (and tests):
        # _placement/_moved ARE the placement map's dicts, guarded by
        # _placement_lock == placement.lock
        self._placement_lock = self.placement.lock
        self._placement = self.placement.replicas
        self._moved = self.placement.moved
        # Reconciles run one at a time (_reconcile_serial) so a rejoin
        # cannot interleave with an in-flight recovery.
        self._reconcile_serial = threading.Lock()
        # residue anti-entropy pacing (first pass one period in, like
        # the rebalancer: let the post-election repair settle first)
        self._residue_last = time.monotonic()
        # elastic data plane: live shard migration / drain, riding the
        # sweep loop below (cluster/rebalance.py)
        self.rebalancer = Rebalancer(self)
        # membership epoch: scatter batches group by the value at
        # SUBMIT time, so one coalesced batch never spans a membership
        # transition (one batch = one owner assignment's world view)
        self._cluster_epoch = 0
        # retry policy + per-worker circuit breakers shared by every
        # leader->worker RPC path (cluster/resilience.py)
        self.resilience = ClusterResilience(self.config)
        # LIVE hedge delay: reads on the scatter path go through this
        # attribute (not the frozen config) so the SLO autopilot can
        # track it to the observed scatter p95; initialized to — and
        # reverted to, on the kill switch — the static config value
        self.hedge_ms = float(self.config.scatter_hedge_ms)
        # closed-loop SLO autopilot (cluster/autopilot.py): leader-side
        # controller riding the sweep loop below that tunes hedge_ms,
        # the admission watermarks, the adaptive-linger ceiling, and
        # the gray-failure slow-trip threshold from the live
        # histograms — each with hysteresis, clamps, damping, a
        # decision-audit ring (GET /api/autopilot), and a kill switch
        self.autopilot = Autopilot(self)
        # leadership fencing (cluster/fencing.py): the worker-side
        # guard (highest leader epoch ever seen, durable beside the
        # index so a reboot mid-partition cannot be captured by a
        # deposed leader) and the leader-side epoch stamped on every
        # mutating worker RPC. A fence rejection triggers an immediate
        # step-down (_fence_step_down) — never a retry.
        self.fence = FenceGuard(os.path.join(self.config.index_path,
                                             "fence_epoch.json"))
        self._leader_epoch: int | None = None
        self._fence_lock = threading.Lock()
        self._fence_stepping = False
        # workers that have EVER contributed unmapped (legacy
        # sum-merge) hits: if one of them later fails, the map cannot
        # vouch for its unmapped documents — the degraded marker stays
        # honest even when no live worker echoes those docs (GIL-atomic
        # dict ops; bounded by distinct worker URLs)
        self._legacy_hit_workers: dict[str, float] = {}
        # last-observed scatter health (attempted / responded /
        # circuit-open) for the CLI summary; per-REQUEST markers are
        # returned by leader_search_with_health — the degraded header is
        # stamped from the returned value, never from this shared copy
        self._scatter_health: dict[str, int] = {}
        # periodic reconciliation sweep: retries failed /worker/delete
        # reconciles (ADVICE r5 medium — without it a failed reconcile
        # leaves moved docs double-indexed until the NEXT membership
        # event) — started in start(), runs only while leader
        self._sweep_thread = None
        if (self.config.shard_recovery
                and self.config.reconcile_sweep_interval_s > 0):
            self._sweep_thread = threading.Thread(
                target=self._reconcile_sweep_loop, daemon=True,
                name=f"reconcile-sweep-{self.config.port}")
        # the durable store of placed documents lives BESIDE the served
        # documents dir, never inside it: the leader's own boot re-walk
        # must not index copies of documents that live on other workers
        # (that would double-count them in the scatter sum-merge)
        self._store_dir = os.path.join(self.config.index_path,
                                       "placed_docs")
        # name -> CRC32 of every stored placed document: the reference
        # the integrity scrub verifies against (without an independent
        # record, bit rot in a stored doc is undetectable — the bytes
        # are their own only witness). Flushes are debounced onto the
        # sweep loop's scrub pass.
        self._store_ledger = storage.CrcLedger(
            os.path.join(self.config.index_path, "placed_docs.crc.json"))
        self._scrub_last = time.monotonic()

        # serving-node durability (the reference commits its Lucene index
        # on every upload, Worker.java:138): an on-demand /admin/checkpoint
        # endpoint plus an optional periodic autosave of dirty state
        self.checkpoint_dir = (self.config.checkpoint_path
                               or os.path.join(self.config.index_path,
                                               "checkpoint"))
        self._ckpt_lock = threading.Lock()
        self._ckpt_thread = None
        if self.config.checkpoint_interval_s > 0:
            self._ckpt_thread = threading.Thread(
                target=self._autosave_loop, daemon=True,
                name=f"ckpt-{self.config.port}")

        handler = type("Handler", (_NodeHandler,), {"node": self})
        self.httpd = _NodeServer(
            (self.config.host, self.config.port), handler)
        self.port = self.httpd.server_address[1]
        # the reference builds this from POD_IP + SERVER_PORT env vars
        # (OnElectionAction.java:35-36)
        self.url = f"http://{self.config.host}:{self.port}"
        self._server_thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True,
            name=f"node-{self.port}")

    # ---- lifecycle (app/Application.java:33-46) ----

    def _stamp_net_origin(self, coord) -> None:
        """Identify this node's outbound traffic to the nemesis shim:
        the scatter client and (when the coordination client supports
        it and a test has not already named it) the control-plane
        client share the node's own endpoint identity."""
        self._scatter.origin = self.url
        if getattr(coord, "origin", None) == "":
            coord.origin = self.url

    def start(self, rebuild: bool = True,
              rebuild_newer_than: float | None = None) -> "SearchNode":
        # what this process costs itself (CPU share, GIL wait, collector
        # pauses) rides /api/metrics for as long as a node serves
        process_watch.start()
        self._watching = True
        self._server_thread.start()
        self._stamp_net_origin(self.coord)
        if rebuild:   # boot-time re-walk (Worker.java:77-88); after a
            # checkpoint restore only documents written since the save
            # are re-analyzed (idempotent upserts)
            self.engine.build_from_directory(
                newer_than=rebuild_newer_than)
        self.placement.start_persister()
        if self.placement_follower is not None:
            # any-node read plane: follow the durable placement znode
            # (data watch + periodic backstop — cluster/placement.py)
            self.placement_follower.start()
        self.election.volunteer_for_leadership()
        self.election.reelect_leader()
        if self._ckpt_thread is not None:
            self._ckpt_thread.start()
        if self._sweep_thread is not None:
            self._sweep_thread.start()
        log.info("node started", url=self.url,
                 leader=self.election.is_leader())
        return self

    # ---- serving-node checkpoints ----

    def save_checkpoint(self) -> dict:
        """Checkpoint the engine to this node's checkpoint dir (used by
        /admin/checkpoint and the autosave loop). Serialized by a lock —
        overlapping saves would race on the version directory."""
        from tfidf_tpu.engine.checkpoint import save_checkpoint
        with self._ckpt_lock:
            t0 = time.perf_counter()
            save_checkpoint(self.engine, self.checkpoint_dir)
            dt = time.perf_counter() - t0
        global_metrics.inc("checkpoints_saved")
        global_metrics.observe("checkpoint_save", dt)
        return {"dir": self.checkpoint_dir,
                "docs": self.engine.index.num_live_docs,
                "seconds": round(dt, 2)}

    def _autosave_loop(self) -> None:
        interval = self.config.checkpoint_interval_s
        last_state = None
        while not self._stopping:
            time.sleep(interval)
            if self._stopping:
                return
            try:
                # flush deferred upload commits first — otherwise an
                # upload burst with no intervening search leaves _dirty
                # set and the loop re-saves the identical corpus forever
                self.commit_if_dirty()
                state = (self.engine.index.num_live_docs,
                         getattr(self.engine.index, "_gen", None))
                if state == last_state:
                    continue   # nothing new since the last save
                self.save_checkpoint()
                last_state = state
            except Exception as e:
                log.warning("autosave checkpoint failed", err=repr(e))

    def stop(self) -> None:
        self._stopping = True
        if self._watching:   # give start()'s share of the watch back
            self._watching = False
            process_watch.stop()
        self._store_ledger.flush(fsync=False)   # best-effort final flush
        self.placement.stop()
        if self.placement_follower is not None:
            self.placement_follower.stop()
        self.election.resign()
        self.registry.unregister_from_cluster()
        self.httpd.shutdown()
        self.httpd.server_close()
        self._pool.shutdown(wait=False)
        self._slice_pool.shutdown(wait=False)
        if self.batcher is not None:
            self.batcher.stop()
        if self.scatter_batcher is not None:
            self.scatter_batcher.stop()
        if self.request_log is not None:
            self.request_log.close()

    # ---- worker search path (Worker.java:175-186) ----

    def worker_search(self, query: str) -> list:
        """Score one query against the local engine. Default: exact top-k
        through the packed-transfer fast path, micro-batched with
        concurrent requests. ``unbounded_results=True`` restores the
        reference's full-ranking behavior (``Worker.java:230``) for
        parity."""
        # refused before it is queued: the micro-batch it would ride
        # in answers its other queries (a raise inside the batch fails
        # them all, and this endpoint turns a failure into [])
        n_terms = self.query_terms_over_limit(query)
        if n_terms is not None:
            raise TooManyQueryTerms([(query, n_terms)],
                                    self.config.max_query_terms)
        self.commit_if_dirty()
        unbounded = self.config.unbounded_results
        if self.batcher is not None:
            return self.batcher.search(query, unbounded=unbounded)
        return self.engine.search(query, unbounded=unbounded)

    # Retry gate classifier: the structured compute-fault taxonomy
    # (cluster/resilience.classify_compute_fault — the same function
    # the engine's health machine and the leader's poison quarantine
    # use, so the three can never drift). Only "compile" (retried
    # within a per-bucket budget that a deterministic refusal — a
    # kernel Mosaic rejects, a tile schedule over scoped VMEM — drains
    # at once) and "transient" (one-off dispatch failure) earn the
    # single budgeted retry; "oom" already ran the engine's batch-backoff ladder and
    # "poison" must surface unretried for the leader to quarantine.
    @staticmethod
    def _is_retryable_compute_fault(e: BaseException) -> bool:
        return classify_compute_fault(e) in ("compile", "transient")

    def _compile_bucket(self, n_queries: int) -> int:
        """Query batches pad to power-of-two buckets; the retry budget is
        tracked per bucket because a deterministic compile failure is a
        property of the compiled shape, not of one request."""
        return 1 << max(0, n_queries - 1).bit_length() if n_queries else 0

    def _search_batch_guarded(self, n_queries: int, run,
                              deadline: float | None = None):
        """Shared wrapper for the batched-scatter entrypoints: NRT
        commit, timing, and the compile/transient retry. A failure
        classified "compile" or "transient" is retried once, with a
        per-bucket-size budget: a deterministic compile error (a
        kernel the compiler refuses at a new bucket) drains the budget
        and then propagates immediately instead of doubling every
        batch's cost forever.

        ``deadline`` (monotonic seconds) is the leader's propagated
        scatter budget: re-checked AFTER the NRT commit (which can eat
        real time) and before every scoring attempt — a batch whose
        caller already gave up must not burn device time nobody will
        merge."""
        self.commit_if_dirty()
        if deadline is not None and time.monotonic() > deadline:
            global_metrics.inc("worker_deadline_refusals")
            raise WorkerDeadline("scatter deadline passed before scoring")
        bucket = self._compile_bucket(n_queries)
        t0 = time.perf_counter()
        try:
            out = run()
        except Exception as e:
            if not self._is_retryable_compute_fault(e):
                raise
            with self._compile_retry_lock:
                used = self._compile_retries_used.get(bucket, 0)
                if used >= self.config.compile_retry_per_bucket:
                    raise   # budget spent: treat as deterministic
                self._compile_retries_used[bucket] = used + 1
            global_metrics.inc("search_compile_retries")
            log.warning("search failed in compilation; retrying once",
                        err=repr(e)[:200], bucket=bucket)
            time.sleep(0.5)
            out = run()
        with self._compile_retry_lock:
            # success refills the bucket's budget: only CONSECUTIVE
            # failures at a bucket look deterministic
            self._compile_retries_used.pop(bucket, None)
        global_metrics.observe("worker_batch_search",
                               time.perf_counter() - t0)
        return out

    def worker_search_batch(self, queries: list[str],
                            k: int | None = None,
                            deadline: float | None = None) -> list[list]:
        """Score an already-formed query batch (the leader's batched
        scatter RPC). Bypasses the micro-batcher — the batch needs no
        linger for company — and runs the engine's batch path directly;
        searches are pure functions of the committed snapshot, so
        concurrent batch RPCs are safe (and their chunks OVERLAP on the
        searcher's shared pipeline executor: batch B's device programs
        dispatch while batch A's packed top-k fetch is still on the
        wire — engine/pipeline.py)."""
        return self._search_batch_guarded(
            len(queries), lambda: self.engine.search_batch(queries, k=k),
            deadline=deadline)

    def worker_search_slice(self, queries: list[str],
                            names: list[str],
                            deadline: float | None = None
                            ) -> list[list[tuple[str, float]]]:
        """Score an ownership SLICE: every matching document among
        ``names`` for each query (the leader's failover / hedged
        re-issue of a dead owner's documents). Exact within the slice —
        the full ranking is computed host-side and filtered, so a
        sliced document can never be truncated out by documents outside
        the slice."""
        nameset = set(names)

        def run() -> list[list[tuple[str, float]]]:
            res = self.engine.search_batch(queries, unbounded=True)
            return [[(h.name, h.score) for h in hits
                     if h.name in nameset] for hits in res]

        out = self._search_batch_guarded(len(queries), run,
                                         deadline=deadline)
        global_metrics.inc("worker_slice_rpcs")
        return out

    def worker_search_batch_wire(self, queries: list[str],
                                 k: int | None = None,
                                 deadline: float | None = None) -> bytes:
        """Batched scatter RPC -> packed wire reply bytes: the
        searcher's raw top-k arrays packed vectorized (``search_arrays``
        + ``pack_topk_arrays`` — no per-hit SearchHit churn on the
        serving path). Name-ordered parity results go through hit lists,
        which alone carry that order; for score-ordered results both
        produce byte-identical wire replies (tests/test_pipeline.py)."""
        if self.config.result_order == "score":
            vals, ids, _kk, names = self._search_batch_guarded(
                len(queries),
                lambda: self.engine.search_batch_arrays(queries, k=k),
                deadline=deadline)
            t0 = time.perf_counter()
            body = pack_topk_arrays(vals, ids, names)
        else:
            results = self.worker_search_batch(queries, k=k,
                                               deadline=deadline)
            t0 = time.perf_counter()
            body = pack_hit_lists(results)
        global_metrics.observe("worker_batch_pack",
                               time.perf_counter() - t0)
        return body

    def worker_search_staged_wire(self, queries: list[str],
                                  k: int | None = None,
                                  mode: str = "hybrid",
                                  deadline: float | None = None) -> bytes:
        """Two-stage scatter reply (mode dense|hybrid): ``2n`` hit
        lists on the ordinary packed wire — the first ``n`` are the
        sparse stage (empty lists for mode=dense, keeping the slot
        layout uniform), the last ``n`` the dense stage. Dense lists
        always ride ``pack_hit_lists``, never the arrays fast path:
        ``pack_topk_arrays`` drops scores <= 0, and signed-hash cosines
        are legitimately negative."""
        if mode == "hybrid":
            sparse = self.worker_search_batch(queries, k=k,
                                              deadline=deadline)
        else:
            sparse = [[] for _ in queries]
        dense = self._search_batch_guarded(
            len(queries),
            lambda: self.engine.search_dense_batch(queries, k=k),
            deadline=deadline)
        global_metrics.inc("worker_dense_batches")
        return pack_hit_lists(list(sparse) + list(dense))

    def worker_search_slice_staged(self, queries: list[str],
                                   names: list[str], mode: str,
                                   deadline: float | None = None
                                   ) -> list[list[tuple[str, float]]]:
        """Failover / hedge slice for a staged query: ``2n`` lists in
        the same (sparse block, dense block) layout as the batched
        reply, exact within the slice for BOTH stages — a failover
        must re-issue every stage the dead owner would have run."""
        if mode == "hybrid":
            sparse = self.worker_search_slice(queries, names,
                                              deadline=deadline)
        else:
            sparse = [[] for _ in queries]
            global_metrics.inc("worker_slice_rpcs")
        dmaps = self._search_batch_guarded(
            len(queries),
            lambda: self.engine.search_dense_names(queries, names),
            deadline=deadline)
        dense = [sorted(m.items(), key=lambda kv: (-kv[1], kv[0]))
                 for m in dmaps]
        return list(sparse) + dense

    def notify_write(self) -> None:
        """Mark uncommitted writes (called by the upload handler)."""
        self._dirty = True

    # ---- result-cache generation (cluster/admission.py) ----

    def bump_result_generation(self) -> None:
        """Advance the df-signature commit generation: any mutation
        that could change a score (a confirmed upload leg, a reconcile
        delete, a migration flip, a direct worker-side write) calls
        this, so every cached query result stamped with an older token
        dies at its next lookup."""
        with self._result_gen_lock:
            self._result_gen += 1

    def df_signature(self) -> tuple:
        """The result cache's generation token: (membership epoch,
        commit generation). The epoch component covers everything that
        changes WHICH shards answer (worker death/join shifts
        per-shard df); the generation component covers every commit
        the leader orchestrates on unchanged membership.

        A NON-leader serving reads has no view of the leader's commit
        generation — its token keys on the follower VIEW version
        instead (tagged so a token minted in one role can never
        collide with the other): every observed placement flush — the
        leader flushes after every df-changing commit — invalidates,
        bounding staleness by the flush debounce + watch latency. The
        LOCAL commit generation still rides along: a direct
        ``/worker/*`` write on this node changes its own engine's df
        without any placement flush (the dual-role contract)."""
        if self._role != "leader" and self._follower_active():
            with self._result_gen_lock:
                gen = self._result_gen
            return (self._cluster_epoch,
                    ("view", self.placement_follower.version, gen))
        with self._result_gen_lock:
            gen = self._result_gen
        return (self._cluster_epoch, gen)

    def commit_if_dirty(self) -> None:
        """NRT visibility point: flush deferred upload commits before
        serving a search. Clearing the flag before committing means a
        write landing mid-commit re-dirties and is flushed next time."""
        if self._dirty:
            with self._commit_lock:
                if self._dirty:
                    self._dirty = False
                    try:
                        self.engine.commit()
                    except BaseException:
                        # a failed commit must not leave the node serving
                        # stale pre-upload results forever
                        self._dirty = True
                        raise
        else:
            # a sibling search may have observed the same writes, cleared
            # the flag, and STILL be mid-commit — searching now would see
            # the pre-upload snapshot and break read-your-writes (an
            # upload's 200 means the next search finds it, matching the
            # reference's synchronous commit, Worker.java:138). Barrier
            # on the lock: free when no commit is in flight.
            with self._commit_lock:
                pass

    # ---- session-expiry recovery ----

    def _on_session_event(self, ev) -> None:
        log.warning("coordination session expired", url=self.url)
        if self._stopping or self._coord_factory is None:
            return
        threading.Thread(target=self._rejoin, daemon=True,
                         name=f"rejoin-{self.port}").start()

    def _rejoin(self) -> None:
        """Reconnect with a fresh session and re-enter election + registry.
        All prior ephemerals are gone with the old session, so this is a
        clean re-volunteer (the role may change: an ex-leader can come back
        as a worker)."""
        delay = 0.2
        while not self._stopping:
            try:
                coord = self._coord_factory()
                self.coord = coord
                self._stamp_net_origin(coord)
                self.registry = ServiceRegistry(
                    coord, on_change=self._on_membership_change)
                self.election = LeaderElection(coord, callback=self)
                coord.on_session_event(self._on_session_event)
                self.election.volunteer_for_leadership()
                self.election.reelect_leader()
                if self.placement_follower is not None:
                    # the old session's data watch died with it: force
                    # a re-arm + refresh on the NEW client (the store
                    # getter reads self.coord dynamically) — without
                    # this the any-node read view would silently fall
                    # back to poll latency forever
                    self.placement_follower._watch_armed = False
                    self.placement_follower._wake.set()
                global_metrics.inc("session_rejoins")
                log.info("rejoined cluster after session expiry",
                         url=self.url, leader=self.election.is_leader())
                # the rebuilt registry's first refresh is "initial
                # population", never a lost-transition — so a worker
                # that died DURING the outage would stay dark forever.
                # Diff the placement map against the fresh view, after
                # a grace period: a registry-wide blip expires EVERY
                # session, and diffing before the other workers finish
                # their own rejoins would re-place the whole corpus
                # only to reconcile it back seconds later.
                if (self.config.shard_recovery
                        and self.election.is_leader()):
                    threading.Thread(
                        target=self._recover_after_rejoin, daemon=True,
                        name=f"shard-recovery-{self.port}").start()
                return
            except Exception as e:
                log.warning("rejoin attempt failed", err=repr(e))
                time.sleep(delay)
                delay = min(delay * 2, 5.0)

    def _recover_after_rejoin(self) -> None:
        time.sleep(max(2 * self.config.session_timeout_s, 1.0))
        if self._stopping or not self.is_leader():
            return
        live = set(self.registry.get_all_service_addresses())
        with self._placement_lock:
            known = {w for ws in self._placement.values() for w in ws}
        lost = known - live
        if lost:
            self._reconcile_membership(lost, set())

    # ---- role transitions (leader/OnElectionAction.java:27-77) ----

    def on_elected_to_be_leader(self) -> None:
        self._role = "leader"   # cached for the non-blocking /api/health
        # leadership epoch, issued at promotion: the election znode's
        # own sequence number (strictly grows across successions —
        # cluster/fencing.py). Stamped on every mutating worker RPC and
        # into the durable placement znode; this node's own worker
        # plane advances its fence NOW so a deposed predecessor cannot
        # write here even before the first fenced RPC arrives.
        epoch = self.election.epoch()
        self._leader_epoch = epoch
        self.placement.epoch = epoch
        if epoch is not None:
            self.fence.observe(epoch)
        # the leader does not serve a shard: leave the worker pool (:30)
        self.registry.unregister_from_cluster()
        self.registry.register_for_updates()
        publish_leader_info(self.coord, self.url)
        global_metrics.inc("elections_won")
        log.info("assumed leader role", url=self.url, epoch=epoch)
        # resume ownership: load the durable placement map (and its
        # pending-reconcile state) off-thread — this callback can run
        # on the watch-dispatch thread, and the load is a coordination
        # read that must not stall other clients' events
        threading.Thread(target=self._resume_placement, daemon=True,
                         name=f"placement-resume-{self.config.port}"
                         ).start()

    def _resume_placement(self) -> None:
        """New-leader resume: merge the persisted placement map into
        memory, then enable persistence (in that order — enabling first
        could let an early flush clobber the znode before it is read),
        reconcile any workers that died while no leader was watching,
        and restore the replication factor.

        The load is retried (bounded) and persistence stays DISABLED if
        it never succeeds: flushing a near-empty in-memory map over the
        predecessor's durable one would permanently strip failover
        coverage from every document placed before this tenure — a
        stale durable map is strictly better than a clobbered one."""
        # fence round FIRST: push the new epoch to every live worker
        # NOW, so a deposed predecessor (possibly still alive behind a
        # partition) cannot land even one more write in the promotion
        # gap — without this, the split-brain window stays open until
        # this leader's first organic mutating RPC happens to reach
        # each worker
        try:
            self._fence_workers()
        except Exception as e:
            log.warning("promotion fence round failed", err=repr(e))
        loaded = self.config.placement_flush_ms < 0   # nothing to load
        if not loaded:
            delay = 0.2
            deadline = time.monotonic() + 30.0
            while not self._stopping:
                try:
                    self.placement.load()
                    loaded = True
                    break
                except Exception as e:
                    log.warning("placement map load failed; retrying",
                                err=repr(e))
                    if time.monotonic() > deadline:
                        break
                    time.sleep(delay)
                    delay = min(delay * 2, 2.0)
        if loaded:
            self.placement.set_persist_enabled(True)
        else:
            log.warning(
                "placement map load kept failing; placement persistence "
                "stays disabled this tenure (never overwrite the "
                "durable map with an unloaded in-memory one)")
        if self._stopping or not self.config.shard_recovery:
            return
        try:
            if not self.is_leader():
                return
            # resolve a predecessor's in-flight migrations FIRST (abort
            # copying-phase records) so the repair/trim pass below can
            # reclaim their stray copy legs in the same sweep
            self.rebalancer.resume_after_election()
            live = set(self.registry.get_all_service_addresses())
            with self._placement_lock:
                known = {w for ws in self._placement.values()
                         for w in ws}
            lost = known - live
            if lost:
                self._reconcile_membership(lost, set())
            else:
                self.run_replication_repair()
        except Exception as e:
            log.warning("placement resume pass failed", err=repr(e))

    def on_worker(self) -> None:
        self._role = "worker"   # cached for the non-blocking /api/health
        # a demoted node holds no leadership epoch: mutating RPCs it
        # somehow still issues would go unstamped (and its placement
        # flushes are disabled below anyway)
        self._leader_epoch = None
        self.placement.epoch = None
        # a worker must never write the leader's placement state, and
        # a DEMOTED ex-leader must not carry its tenure's map into a
        # possible later re-promotion — the durable znode (written by
        # its successors) is newer than this node's memory, so the map
        # resets and a re-election loads it fresh
        self.placement.set_persist_enabled(False)
        self.placement.reset_for_follower()
        self.registry.register_to_cluster(self.url)
        log.info("assumed worker role", url=self.url)

    def is_leader(self) -> bool:
        return self.election.is_leader()

    # ---- leadership fencing (cluster/fencing.py) ----

    def _fence_workers(self) -> None:
        """Promotion fence round: an empty, epoch-stamped
        ``/worker/delete`` to every live worker advances each worker's
        durable fence to this tenure's epoch — after it lands, no RPC
        from any predecessor can be accepted anywhere. Best-effort per
        worker (an unreachable worker is fenced by this leader's first
        real write to it, or rejects the predecessor anyway once any
        stamped RPC arrives); counted in ``fence_rounds``."""
        if self._leader_epoch is None:
            return
        workers = self.registry.get_all_service_addresses()
        if not workers:
            return
        body = json.dumps({"names": []}).encode()
        fenced = 0
        for w in workers:
            try:
                self._worker_call_fenced(
                    w, lambda w=w: http_post(
                        w + "/worker/delete", body, timeout=10.0,
                        headers=self._epoch_headers(), origin=self.url))
                fenced += 1
            except Exception as e:
                log.warning("promotion fence push failed", worker=w,
                            err=repr(e))
        if fenced:
            global_metrics.inc("fence_rounds")
            log.info("promotion fence round complete", workers=fenced,
                     epoch=self._leader_epoch)

    def _epoch_headers(self) -> dict[str, str]:
        """The fencing token for one mutating worker RPC. Empty when
        this node holds no epoch (not leader / pre-election) — workers
        never fence unstamped requests, so reference clients and
        single-node deployments are untouched."""
        epoch = self._leader_epoch
        return {FENCE_HEADER: str(epoch)} if epoch is not None else {}

    def _worker_call_fenced(self, worker: str, fn):
        """``ClusterResilience.worker_call`` for MUTATING RPCs: a
        leadership-fence rejection (403 + X-Fence-Rejected) triggers an
        immediate step-down — a newer leader exists, so this node's
        epoch can never become valid again; retrying would be the
        split-brain the fence exists to stop. The rejection still
        propagates to the caller as a failed leg (never acked)."""
        try:
            return self.resilience.worker_call(worker, fn)
        except Exception as e:
            if is_fence_rejection(e):
                self._note_fence_rejection(worker, e)
            raise

    def _note_fence_rejection(self, worker: str, e: BaseException) -> None:
        with self._fence_lock:
            if self._fence_stepping:
                return          # a step-down is already in flight
            self._fence_stepping = True
        log.warning("fenced by a newer leader epoch; stepping down",
                    worker=worker, err=repr(e),
                    my_epoch=self._leader_epoch)
        global_metrics.inc("fence_step_downs")
        span_event("fence_rejected", worker=worker,
                   stale_epoch=self._leader_epoch)
        threading.Thread(target=self._fence_step_down, daemon=True,
                         name=f"fence-stepdown-{self.port}").start()

    def _fence_step_down(self) -> None:
        """Deposed-leader demotion: drop all leader authority NOW (in
        memory — no further placement flushes, no stale map carried
        into a later tenure), then resign the election znode and
        re-enter as a fresh candidate (whose new sequence number mints
        a HIGHER epoch, so a re-promotion is safe by construction).
        Coordination may be unreachable — the very partition that got
        us deposed — so re-entry retries with backoff and defers to the
        session-expiry rejoin path the moment it takes over."""
        election = self.election
        try:
            self._leader_epoch = None
            self.placement.epoch = None
            self.placement.set_persist_enabled(False)
            self.placement.reset_for_follower()
            self._role = "worker"
            try:
                election.resign()
            except Exception as e:
                # partitioned from the coordinator: the znode is (or
                # will be) gone with the session anyway
                log.warning("resign after fence failed", err=repr(e))
            delay = 0.1
            while not self._stopping:
                if self.election is not election:
                    return   # a session-expiry rejoin took over
                try:
                    self.election.volunteer_for_leadership()
                    self.election.reelect_leader()
                    log.info("re-entered election after fence "
                             "step-down", url=self.url,
                             leader=self.election.is_leader())
                    return
                except NoNodeError:
                    # our session died during the partition: the
                    # SESSION_EXPIRED event owns recovery (rejoin with
                    # a fresh session)
                    log.info("fence step-down defers to session-expiry "
                             "rejoin")
                    return
                except Exception as e:
                    log.warning("election re-entry after fence failed; "
                                "retrying", err=repr(e))
                    time.sleep(delay)
                    delay = min(delay * 2, 2.0)
        finally:
            with self._fence_lock:
                self._fence_stepping = False

    # ---- read plane (cluster/router.py ScatterReadPlane) ----
    #
    # leader_search / leader_search_with_health / _scatter_search_batch /
    # _gather_merge (the scatter, owner-merge, failover, and hedge
    # spine) are inherited from ScatterReadPlane; only the three policy
    # hooks below are role-dependent.

    def _follower_active(self) -> bool:
        """Is the follower view usable for reads? Only once a payload
        has actually loaded — before the leader's first flush (or with
        persistence disabled) a non-leader keeps the legacy behavior
        rather than serving an empty view."""
        f = self.placement_follower
        return f is not None and f.loaded

    def _read_placement(self):
        """The placement view one read request routes under: the
        authoritative map while this node leads; the watch-refreshed
        follower view of the durable znode otherwise. The cached role
        is used (never an is_leader() coordination READ — this is the
        per-request hot path); transitions re-point the next request."""
        if self._role == "leader" or not self._follower_active():
            return self.placement
        return self.placement_follower

    # ---- shard recovery (SURVEY §5.3 — beyond the reference) ----

    def _store_path(self, name: str) -> str:
        """Resolve a name under the recovery store with the same
        traversal check as the engine's documents dir."""
        base = os.path.abspath(self._store_dir)
        target = os.path.abspath(os.path.join(base, name))
        if not (target == base or target.startswith(base + os.sep)):
            raise PermissionError(f"path escapes store dir: {name!r}")
        return target

    def _store_document(self, name: str, data: bytes) -> None:
        """Durable leader-side copy of a placed document (the recovery
        source; the reference's leader-local disk is already a download
        source, ``Leader.java:112-121``). Atomic + group-commit-fsynced
        through the durable-IO seam, with the CRC recorded in the scrub
        ledger. Best-effort: a failed store must not fail the upload it
        shadows (the replicas ARE durable — fsync-before-ack)."""
        try:
            path = self._store_path(name)
            storage.atomic_write_bytes(path, data,
                                       fsync=self.config.storage_fsync)
            self._store_ledger.record(name, zlib.crc32(data))
        except Exception as e:
            log.warning("leader document store write failed", file=name,
                        err=repr(e))

    def _store_read(self, name: str) -> bytes | None:
        """Read a stored placed document, verified against the scrub
        ledger when it has a record — a rotten recovery source must
        surface as MISSING (so recovery falls through to the replica
        download probe), never get re-placed as corrupt content."""
        try:
            path = self._store_path(name)
            if not os.path.isfile(path):
                return None
            data = storage.read_bytes(path)
            want = self._store_ledger.get(name)
            if want is not None:
                if zlib.crc32(data) != want:
                    global_metrics.inc("storage_corruptions_detected")
                    span_event("storage_corruption", file=name,
                               where="placed_docs")
                    log.warning("stored document failed CRC; treating "
                                "as missing", file=name)
                    return None
            return data
        except Exception:
            return None

    # ---- background integrity scrub (storage durability, README
    #      "Storage durability & integrity") ----

    def run_integrity_scrub(self) -> dict:
        """One scrub pass: verify every ledger-covered placed document
        against its recorded CRC, repairing a rotten local copy from a
        healthy replica through the same download probe the PR 5
        recovery uses; then verify the current checkpoint's manifest,
        quarantining a corrupt version (the next autosave re-creates
        it). Rides the leader's sweep loop (``storage_scrub_ms``);
        public so tests and operators can force a pass."""
        checked = repaired = unrepaired = 0
        for name in self._store_ledger.names():
            if self._stopping:
                break
            want = self._store_ledger.get(name)
            try:
                path = self._store_path(name)
            except PermissionError:
                continue
            if want is None or not os.path.isfile(path):
                continue
            checked += 1
            try:
                got = storage.file_crc(path)
            except OSError:
                got = None
            if got == want:
                continue
            # TOCTOU guard: a concurrent upsert may have rewritten the
            # file between the ledger read and the CRC — re-read the
            # ledger and skip if it moved (the NEXT pass judges the new
            # pair); without this, scrub could "repair" a just-acked
            # upsert back to its replica's OLD bytes or condemn a
            # perfectly valid new file
            if self._store_ledger.get(name) != want:
                continue
            # corroborate against a replica before judging (anti-
            # entropy: the workers holding the doc are the redundancy
            # this store backs). NOT leader_download — its locator
            # serves the local store first, which is exactly the copy
            # under suspicion.
            data = self._fetch_from_replicas(name)
            rcrc = zlib.crc32(data) if data is not None else None
            if rcrc is not None and got is not None and rcrc == got:
                # the replica agrees with the LOCAL FILE, not the
                # ledger: the ledger record is stale (a crash ate the
                # debounced flush after an acked upsert) — heal the
                # RECORD, never touch the healthy file
                self._store_ledger.record(name, got)
                global_metrics.inc("storage_scrub_ledger_heals")
                log.info("scrub healed stale ledger record (replica "
                         "corroborates the local file)", file=name)
                continue
            global_metrics.inc("storage_scrub_corruptions")
            span_event("storage_corruption", file=name,
                       where="placed_docs")
            if rcrc == want and self._store_ledger.get(name) == want:
                try:
                    storage.atomic_write_bytes(
                        path, data, fsync=self.config.storage_fsync)
                    repaired += 1
                    global_metrics.inc("storage_scrub_repairs")
                    log.info("scrub repaired rotten stored document "
                             "from a replica", file=name)
                    continue
                except OSError as e:
                    log.warning("scrub repair write failed", file=name,
                                err=repr(e))
            if self._store_ledger.get(name) != want:
                continue   # upsert landed mid-repair: next pass judges
            unrepaired += 1
            global_metrics.inc("storage_scrub_unrepaired")
            # deliberately NON-destructive: ledger-vs-file disagreement
            # with no replica corroboration either way could be a
            # rotten file OR a healthy upsert whose ledger flush a
            # crash ate — destroying the bytes on that evidence could
            # delete the only leader copy of an acked write. The pair
            # stays on disk, loudly recounted each pass; _store_read
            # keeps refusing the mismatch, so the suspect bytes are
            # never served as a recovery source either way.
            log.warning("scrub found ledger/file CRC disagreement with "
                        "no replica corroboration; leaving both in "
                        "place (recovery falls back to the download "
                        "probe)", file=name)
        self._store_ledger.flush(fsync=self.config.storage_fsync)
        # checkpoint integrity: a corrupt CURRENT version is quarantined
        # now, while the fallback version still exists — not discovered
        # at the next boot, when the re-walk bill comes due
        ckpt_bad = 0
        from tfidf_tpu.engine.checkpoint import (checkpoint_versions,
                                                 quarantine_version)
        for vdir in checkpoint_versions(self.checkpoint_dir):
            problems = storage.verify_manifest(vdir)
            if problems and all("manifest missing" in p
                                for p in problems):
                # pre-manifest legacy version: unverifiable, not
                # corrupt — restore_checkpoint keeps it loadable as a
                # last resort, so the scrub must not destroy it (the
                # next save supersedes it with a manifested one)
                continue
            if problems:
                ckpt_bad += 1
                span_event("storage_corruption",
                           file=os.path.basename(vdir),
                           where="checkpoint")
                log.warning("scrub found corrupt checkpoint version",
                            dir=vdir, problems=problems[:3])
                quarantine_version(vdir)
        global_metrics.inc("storage_scrub_passes")
        out = {"checked": checked, "repaired": repaired,
               "unrepaired": unrepaired, "checkpoints_quarantined":
               ckpt_bad}
        if repaired or unrepaired or ckpt_bad:
            log.info("integrity scrub pass", **out)
        return out

    def _fetch_from_replicas(self, name: str) -> bytes | None:
        """Fetch a document's bytes from the worker fleet ONLY (never
        the local durable store — the scrub calls this exactly when the
        local copy is the rotten one). Same probe discipline as
        ``leader_download_stream``'s worker loop."""
        q = urllib.parse.quote(name)
        for w in self.registry.get_all_service_addresses():
            if self.resilience.board.is_open(w):
                continue
            try:
                resp = self.resilience.worker_call(
                    w, lambda w=w: http_get_stream(
                        w + f"/worker/download?path={q}", timeout=30.0,
                        origin=self.url),
                    retry=False)
                try:
                    return resp.read()
                finally:
                    resp.close()
            except Exception:
                continue
        return None

    def _on_membership_change(self, old, new) -> None:
        """Registry watch hook (watch-dispatch thread — hand off fast).

        The leader check happens in the SPAWNED thread, not here: it is
        a coordination read (an RPC on the HTTP transport, up to the
        client's failover deadline), and this hook runs under the
        registry's notify lock on the shared watch-dispatch thread — a
        stalled leader check here would delay every other client
        event, including the election NodeDeleted that failover
        latency depends on (graftcheck lockgraph finding)."""
        # membership epoch: scatter batches formed before and after
        # this transition never share a coalesced group (the batcher's
        # submit-time group key)
        self._cluster_epoch += 1
        if self._stopping or not self.config.shard_recovery:
            return
        lost = set(old) - set(new)
        joined = set(new) - set(old)
        if lost or joined:
            threading.Thread(
                target=self._reconcile_if_leader, args=(lost, joined),
                daemon=True, name=f"shard-recovery-{self.port}").start()

    def _reconcile_if_leader(self, lost: set[str],
                             joined: set[str]) -> None:
        """Off-dispatch-thread half of the membership hook: the same
        leader gate the hook used to apply inline (is_leader is
        recomputed from live children either way, so the check was
        always racy-by-design against a concurrent re-election)."""
        if self._stopping or not self.is_leader():
            return
        self._reconcile_membership(lost, joined)

    def _reconcile_membership(self, lost: set[str],
                              joined: set[str]) -> None:
        """Re-place a dead worker's documents onto survivors (from the
        leader's durable store), and delete moved documents from a
        rejoining worker so the corpus stays single-copy.

        The reference's recovery is pod-restart + re-walk, during which
        the shard is simply unsearchable (``Worker.java:77-94``,
        ``ServiceRegistry.java:91-122``); this closes that gap for every
        document placed during the current leader's tenure.

        Reconciles run ONE AT A TIME (``_reconcile_serial``) in event
        order, so a rejoin never interleaves with an in-flight recovery;
        a recovery additionally aborts as soon as the lost worker
        reappears in the registry (the rejoiner's boot re-walk serves
        whatever was not yet re-placed), and a name only ever enters
        ``_moved`` after its confirmed placement is a DIFFERENT worker —
        deleting the sole copy is impossible by construction. The
        replication-repair pass that follows a death takes the same
        serial lock itself, so it runs AFTER this block releases it."""
        with self._reconcile_serial:
            for w in joined:
                self._reconcile_rejoined(w)
            for w in lost:
                self._recover_lost_worker(w)
        if lost and self.config.shard_recovery:
            # restore R for documents that survived on replicas (runs
            # outside the block above; repair re-acquires the serial
            # lock so it can never interleave with a reconcile delete)
            try:
                self.run_replication_repair()
            except Exception as e:
                log.warning("post-death replication repair failed",
                            err=repr(e))

    def _reconcile_rejoined(self, w: str) -> bool:
        """Delete this rejoiner's moved documents from it (one retried,
        breaker-gated RPC). The names stay in ``_moved`` — and therefore
        excluded from ``w``'s merged results — until the worker CONFIRMS
        the deletes; popping them up front would open a double-count
        window for every search that races the RPC (the transient
        variant of the ADVICE r5 finding). On failure the sweep (and any
        next join event) retries. Caller holds ``_reconcile_serial``."""
        with self._placement_lock:
            moved = set(self._moved.get(w, ()))
        if not moved:
            return True

        def rpc() -> dict:
            global_injector.check("leader.reconcile_rpc")
            return json.loads(http_post(
                w + "/worker/delete",
                json.dumps({"names": sorted(moved)}).encode(),
                timeout=120.0, headers=self._epoch_headers(),
                origin=self.url))

        try:
            resp = self._worker_call_fenced(w, rpc)
        except Exception as e:
            global_metrics.inc("reconcile_failures")
            log.warning("rejoin reconciliation failed", worker=w,
                        err=repr(e))
            return False
        # names moved DURING the RPC stay pending
        self.placement.moved_resolved(w, moved)
        # the confirmed deletes changed that worker's df — invalidate
        self.bump_result_generation()
        global_metrics.inc("reconciles_completed")
        log.info("reconciled rejoined worker", worker=w,
                 deleted=resp.get("deleted", 0))
        return True

    def _reconcile_sweep_loop(self) -> None:
        """Leader-side anti-entropy loop: retries failed rejoin
        reconciles (ADVICE r5 medium: without it a failed
        /worker/delete leaves moved documents double-indexed until the
        NEXT membership change) AND repairs the replication factor —
        re-replicating under-replicated documents after a death,
        trimming over-replication after a rejoin. Runs on every node;
        does work only while leader."""
        interval = self.config.reconcile_sweep_interval_s
        while not self._stopping:
            time.sleep(interval)
            if self._stopping:
                return
            try:
                # is_leader() can itself raise in the window where a
                # session-expiry rejoin has rebuilt the election but not
                # yet re-volunteered — a sweep thread must survive every
                # transient, or reconciles stop retrying forever
                if not self.is_leader():
                    continue
                self.run_reconcile_sweep()
                self.run_replication_repair()
                # elastic rebalance rides the same leader-side loop,
                # self-paced by rebalance_sweep_ms
                self.rebalancer.maybe_run()
                # SLO autopilot control pass (cluster/autopilot.py),
                # self-paced by autopilot_interval_ms
                self.autopilot.maybe_run()
                # residue anti-entropy (ghost/orphan reconciliation),
                # self-paced by residue_sweep_ms
                now = time.monotonic()
                if (self.config.residue_sweep_ms >= 0
                        and now - self._residue_last
                        >= self.config.residue_sweep_ms / 1e3):
                    self._residue_last = now
                    self.run_residue_reconcile()
                # background integrity scrub (storage durability),
                # self-paced by storage_scrub_ms
                if (self.config.storage_scrub_ms >= 0
                        and now - self._scrub_last
                        >= self.config.storage_scrub_ms / 1e3):
                    self._scrub_last = now
                    self.run_integrity_scrub()
            except Exception as e:
                log.warning("reconcile sweep pass failed", err=repr(e))

    def run_reconcile_sweep(self) -> int:
        """One sweep pass: retry the pending reconcile of every worker
        that is currently live (a still-dead worker has nothing indexed
        to delete; its join event or a later pass will catch it).
        Returns the number of workers converged. Public so tests and
        operators can force a pass without waiting for the timer."""
        with self._placement_lock:
            pending = [w for w, ns in self._moved.items() if ns]
        if not pending:
            return 0
        global_injector.check("leader.sweep")
        global_metrics.inc("reconcile_sweeps")
        live = set(self.registry.get_all_service_addresses())
        done = 0
        for w in pending:
            if w not in live or self._stopping:
                continue
            global_metrics.inc("reconcile_sweep_retries")
            with self._reconcile_serial:
                if self._reconcile_rejoined(w):
                    done += 1
        return done

    def _recover_lost_worker(self, w: str) -> None:
        """Handle a worker's death. Documents with surviving replicas
        stay searchable THROUGH the failover scatter path the moment
        the owner assignment recomputes — they only need their
        replication factor restored (the repair pass below). Documents
        whose LAST replica died are re-placed urgently from the durable
        store, exactly the single-copy recovery of old."""
        kept, lost = self.placement.drop_worker(w)
        if not kept and not lost:
            return
        if kept:
            log.info("worker lost; surviving replicas keep its shard "
                     "searchable", worker=w, docs=len(kept))
        replaced = 0
        missing = 0
        batch: list[dict] = []
        aborted = False
        if lost:
            log.info("re-placing lost worker's shard", worker=w,
                     docs=len(lost))
        for name in lost:
            if w in self.registry.get_all_service_addresses():
                # the worker came back mid-recovery: stop — its boot
                # re-walk serves everything not yet re-placed, and the
                # rejoin reconcile (queued behind this one) deletes
                # what was
                aborted = True
                break
            data = self._store_read(name)
            if data is None:
                # placed before this leader's tenure (or its store
                # write failed): the download probe still covers the
                # promoted-ex-worker case (the new leader's own docs
                # dir holds the shard it served before its promotion
                # removed it from the worker pool)
                try:
                    data = self.leader_download(name)
                except Exception:
                    data = None
            if data is None:
                # no byte source anywhere — count and surface: these
                # stay dark until the pod restarts, exactly the
                # reference's behavior
                missing += 1
                continue
            try:
                text = data.decode("utf-8")
                batch.append({"name": name, "text": text})
                if len(batch) >= 500:
                    replaced += self._replace_batch(batch, w)
                    batch = []
                continue
            except UnicodeDecodeError:
                pass
            try:   # non-UTF-8 (binary-extractable) docs: per-file
                self.leader_upload(name, data)
                replaced += self._note_moved([name], w)
            except Exception as e:
                log.warning("re-placement failed", file=name,
                            err=repr(e))
        if batch:
            replaced += self._replace_batch(batch, w)
        global_metrics.inc("shard_recoveries")
        global_metrics.inc("shard_docs_replaced", replaced)
        if missing:
            global_metrics.inc("shard_docs_unrecovered", missing)
            log.warning("shard recovery left documents dark (no durable "
                        "copy; placed before this leader's tenure)",
                        worker=w, unrecovered=missing)
        log.info("shard recovery complete", worker=w, replaced=replaced,
                 survived=len(kept), known=len(kept) + len(lost),
                 missing=missing, aborted=aborted)

    def _note_moved(self, names: list[str], old_worker: str) -> int:
        """Record names as moved away from ``old_worker`` — only those
        whose CONFIRMED replica set now excludes it (a doc the upload
        routed back onto a just-rejoined ``old_worker`` must not be
        scheduled for deletion from it)."""
        return self.placement.note_moved(names, old_worker)

    def _replace_batch(self, docs: list[dict], old_worker: str) -> int:
        try:
            resp = self.leader_upload_batch(docs)
        except Exception as e:
            log.warning("re-placement batch failed", err=repr(e),
                        docs=len(docs))
            return 0
        # only names a worker ACCEPTED count as moved: 'skipped' are
        # media-type rejections, 'failed' are transport-errored groups
        # that were never indexed anywhere
        not_placed = {s["name"] for s in resp.get("skipped", ())}
        not_placed.update(resp.get("failed", ()))
        return self._note_moved(
            [d["name"] for d in docs if d["name"] not in not_placed],
            old_worker)

    # ---- anti-entropy replication repair ----

    def run_replication_repair(self) -> dict:
        """One anti-entropy pass (generalizing the reconcile sweep):
        restore the replication factor for under-replicated documents
        (new copies from the durable store onto the least-loaded live
        workers not already holding them) and trim over-replication
        after rejoins (extras are scheduled for deletion through the
        same pending-reconcile machinery as moves). Public so tests and
        operators can force a pass without waiting for the timer.

        Serialized with the reconcile machinery (``_reconcile_serial``,
        taken here — callers must not hold it): a repair must never
        re-add a copy to a worker while a reconcile delete for that
        same name is on the wire, or the delete lands after the re-add
        and silently erases a mapped replica."""
        if self._stopping or not self.config.shard_recovery:
            return {}
        live = set(self.registry.get_all_service_addresses())
        if not live:
            return {}
        global_injector.check("leader.repair")
        with self._reconcile_serial:
            return self._repair_pass(live)

    def _repair_pass(self, live: set[str]) -> dict:
        """Body of :meth:`run_replication_repair`; caller holds
        ``_reconcile_serial`` (never the placement lock)."""
        r = max(1, min(self.config.replication_factor, len(live)))
        under = self.placement.under_replicated(live, r)
        added = repaired_missing = 0
        draining = self.placement.draining_snapshot()
        if under:
            global_metrics.inc("repair_passes")
            # never repair ONTO a draining worker — its drain would just
            # migrate the fresh copy straight back off
            targets_pool = [w for w in live
                            if not self.resilience.board.is_open(w)
                            and w not in draining]
            try:
                self._ensure_sizes_fresh(targets_pool or sorted(live))
            except Exception as e:
                log.warning("repair size poll failed", err=repr(e))
                return {}
            with self._placement_lock:
                sizes = dict(self._size_cache[1])
            assignments: dict[str, list[str]] = {}
            for name, reps in sorted(under.items()):
                # _load_doc_bytes covers the new-leader case (no store
                # of its own for a predecessor's placements: download
                # probe + cache back into the store)
                data = self._load_doc_bytes(name)
                if data is None:
                    repaired_missing += 1
                    continue
                cands = sorted(
                    (w for w in live
                     if w not in reps and w in sizes
                     and w not in draining
                     and not self.resilience.board.is_open(w)),
                    key=lambda w: (sizes[w], w))
                for target in cands[:r - len(reps)]:
                    sizes[target] = sizes.get(target, 0) + len(data)
                    assignments.setdefault(name, []).append(target)
            added += self._replicate_to_targets(assignments)
            if added:
                global_metrics.inc("repair_docs_replicated", added)
        trimmed = self.placement.trim_plan(live, r)
        n_trim = sum(len(ns) for ns in trimmed.values())
        if n_trim:
            # the actual deletes ride the reconcile sweep/rejoin path
            global_metrics.inc("repair_docs_trimmed", n_trim)
            log.info("scheduled over-replication trim",
                     docs=n_trim, workers=len(trimmed))
        if repaired_missing:
            global_metrics.inc("repair_docs_unrecoverable",
                               repaired_missing)
        return {"replicated": added, "trimmed": n_trim,
                "missing": repaired_missing}

    def run_residue_reconcile(self) -> dict:
        """Anti-entropy for UNMAPPED engine residue — the partition
        leftovers owner assignment can only mask, never clean. Each
        live worker reports the names its engine ACTUALLY serves
        (``GET /worker/names``); copies the placement map does not
        credit are either GHOSTS (mapped elsewhere / pending deletion:
        scheduled away through the moved machinery — they silently
        skew that shard's df/N statistics and resurface the moment the
        name leaves the map) or ORPHANS (mapped nowhere: a write that
        landed but whose placement was lost to a partition — adopted
        as a first-class confirmed replica, then R-restored by the
        repair pass). Public so tests and operators can force a pass;
        self-paced in the sweep loop by ``residue_sweep_ms``."""
        if self._stopping or not self.config.shard_recovery:
            return {}
        live = set(self.registry.get_all_service_addresses())
        if not live:
            return {}
        protected = self.placement.migrating_names()
        ghosts = orphans = 0
        with self._reconcile_serial:
            for w in sorted(live):
                if self.resilience.board.is_open(w) or self._stopping:
                    continue
                try:
                    payload = json.loads(self.resilience.worker_call(
                        w, lambda w=w: http_get(
                            w + "/worker/names", origin=self.url),
                        retry=False))
                except Exception as e:
                    log.warning("residue name fetch failed", worker=w,
                                err=repr(e))
                    continue
                names = payload.get("names")
                if not names:
                    continue   # empty engine, or a layout that can't list
                g, o = self.placement.reconcile_residue(
                    w, [str(n) for n in names], protected)
                ghosts += len(g)
                orphans += len(o)
                if g or o:
                    log.info("residue reconciled", worker=w,
                             ghosts=len(g), orphans_adopted=len(o))
            # the leader's OWN engine (an ex-worker's shard) can hold
            # the ONLY copy of an orphan — it serves no scatter, so an
            # unmapped doc here is unreachable until re-placed through
            # the normal upload path
            own = self.engine.document_names() or ()
            replaced = 0
            for name in self.placement.unplaced_of(
                    [str(n) for n in own], protected):
                if self._stopping:
                    break
                got = None
                try:
                    got = self.engine.open_document_stream(name)
                except Exception:
                    got = None
                if got is None:
                    continue
                stream, _sz = got
                try:
                    data = stream.read()
                finally:
                    stream.close()
                try:
                    self.leader_upload(name, data)
                    replaced += 1
                except Exception as e:
                    log.warning("residue re-place from own engine "
                                "failed", file=name, err=repr(e))
            if replaced:
                global_metrics.inc("residue_leader_replaced", replaced)
                log.info("re-placed orphans from the leader's own "
                         "engine", docs=replaced)
        if ghosts:
            global_metrics.inc("residue_ghosts", ghosts)
        if orphans:
            global_metrics.inc("residue_orphans_adopted", orphans)
            # adopted orphans change which shard scores those names
            self.bump_result_generation()
        global_metrics.inc("residue_sweeps")
        return {"ghosts": ghosts, "orphans": orphans}

    def _load_doc_bytes(self, name: str) -> bytes | None:
        """Byte source for replica/migration copies: the leader's
        durable store first, else the download probe (its own engine
        dir, then surviving replicas), caching probe hits back into
        the store so future copies are store-local."""
        data = self._store_read(name)
        if data is not None:
            return data
        try:
            data = self.leader_download(name)
        except Exception:
            data = None
        if data is not None:
            self._store_document(name, data)
        return data

    def _replicate_to_targets(self,
                              assignments: dict[str, list[str]]) -> int:
        """Fan NEW replica copies out to their assigned workers — text
        docs grouped into one upload-batch per worker, binary docs
        per-file — recording accepted copies in the placement map.
        Shared by the anti-entropy repair pass and the rebalancer's
        migration copy phase. Returns the number of confirmed legs."""
        batches: dict[str, list[dict]] = {}
        files: dict[str, list[tuple[str, bytes]]] = {}
        for name, targets in assignments.items():
            data = self._load_doc_bytes(name)
            if data is None:
                log.warning("no byte source for replica copy; leaving "
                            "the doc where it is", file=name)
                continue
            for target in targets:
                try:
                    batches.setdefault(target, []).append(
                        {"name": name, "text": data.decode("utf-8")})
                except UnicodeDecodeError:
                    files.setdefault(target, []).append((name, data))
        n = 0
        for target, docs in batches.items():
            n += self._add_replica_batch(target, docs)
        for target, items in files.items():
            for name, data in items:
                n += self._add_replica_file(target, name, data)
        return n

    def _add_replica_batch(self, target: str, docs: list[dict]) -> int:
        """Forward one upload-batch of NEW replica copies to ``target``
        and record the accepted ones in the placement map."""
        try:
            resp = json.loads(self._worker_call_fenced(
                target, lambda: http_post(
                    target + "/worker/upload-batch",
                    json.dumps(docs).encode(), timeout=300.0,
                    headers=self._epoch_headers(), origin=self.url)))
        except Exception as e:
            log.warning("replica repair batch failed", worker=target,
                        docs=len(docs), err=repr(e))
            return 0
        skipped = {s["name"] for s in resp.get("skipped", ())}
        n = 0
        for d in docs:
            if d["name"] in skipped:
                continue
            if self.placement.add_replica(d["name"], target):
                n += 1
            else:
                # a client delete won the race against this copy leg:
                # the landed bytes are a stray — schedule them away
                self.placement.note_stray(d["name"], target)
        return n

    def _add_replica_file(self, target: str, name: str,
                          data: bytes) -> int:
        q = urllib.parse.quote(name)
        try:
            self._worker_call_fenced(
                target, lambda: http_post(
                    target + f"/worker/upload?name={q}", data,
                    content_type="application/octet-stream",
                    headers=self._epoch_headers(), origin=self.url))
        except Exception as e:
            log.warning("replica repair upload failed", worker=target,
                        file=name, err=repr(e))
            return 0
        if not self.placement.add_replica(name, target):
            self.placement.note_stray(name, target)   # deleted mid-copy
            return 0
        return 1

    # size polls are cached this long; between polls the leader grows
    # its local estimates by the bytes it placed, so bursts still spread
    _SIZE_POLL_TTL_S = 1.0

    def _ensure_sizes_fresh(self, workers: list[str]) -> None:
        """Refresh the worker index-size TTL cache (the per-upload
        polling loop of ``Leader.java:170-179``). Raises when no worker
        answers. The serial HTTP polls run OUTSIDE ``_placement_lock`` —
        one slow/unreachable worker must not stall every concurrent
        upload handler for the poll timeout; only the freshness check
        and the install are under the lock."""
        now = time.monotonic()
        with self._placement_lock:
            # prune stale eviction records (only recent ones can race a
            # poll in flight; polls take at most the HTTP timeout)
            for w, e in list(self._evicted.items()):
                if now - e > 60.0:
                    del self._evicted[w]
            ts, sizes = self._size_cache
            if (now - ts <= self._SIZE_POLL_TTL_S
                    and set(sizes) == set(workers)):
                return
        polled = {}
        for w in workers:   # serial polling, like Leader.java:170-179
            if self.resilience.board.is_open(w):
                continue   # don't pay the poll timeout for a sick worker
            try:
                def poll(w=w) -> int:
                    global_injector.check("leader.size_poll")
                    return int(http_get(w + "/worker/index-size",
                                        origin=self.url))
                # breaker-tracked, no retry: the TTL cache re-polls soon
                # anyway, and failed polls feed the breaker so repeat
                # offenders drop out of the serial loop above
                polled[w] = self.resilience.worker_call(w, poll,
                                                        retry=False)
            except Exception as e:
                log.warning("index-size poll failed", worker=w,
                            err=repr(e))
        if not polled:
            raise RuntimeError("no reachable workers")
        with self._placement_lock:
            # drop poll results that predate a concurrent eviction: the
            # worker answered our poll, then failed an upload — keeping
            # its pre-failure size would resurrect a dead worker into
            # the cache and route uploads at it until the next TTL
            polled = {w: v for w, v in polled.items()
                      if self._evicted.get(w, -1.0) <= now}
            ts2, cur = self._size_cache
            if ts2 <= ts:   # no fresher concurrent poll landed meanwhile
                if polled:
                    self._size_cache = (now, polled)
            else:
                # a concurrent poll won the install; MERGE our results in
                # for workers it did not cover (its registry view may
                # differ from ours) so this caller's worker set is still
                # represented — discarding our poll could leave the
                # cache empty for our workers and 500 a healthy upload
                self._size_cache = (ts2, {**polled, **cur})

    def _leg_succeeded(self, name: str, worker: str,
                       nbytes: int) -> None:
        """One upload leg accepted: confirm the placement leg and bump
        the local size estimate (only for workers already present in
        the cache: re-inserting an evicted/unpolled worker at near-zero
        size would defeat the set-mismatch re-poll signal and min-route
        every new name onto it until TTL expiry)."""
        # a confirmed copy changed that worker's shard (and its df) —
        # cached query results stamped before this commit must die
        self.bump_result_generation()
        self.placement.leg_success(name, worker)
        with self._placement_lock:
            sizes = self._size_cache[1]
            if worker in sizes:
                sizes[worker] += nbytes

    def _leg_failed(self, name: str, worker: str,
                    app_reject: bool) -> None:
        """One upload leg failed: release the never-confirmed tentative
        replica (phantom cleanup lives in the placement map) and, for
        transport failures, evict the worker from the size cache so the
        next upload re-polls at once instead of re-choosing the dead
        worker until TTL expiry. A 4xx is an APPLICATION rejection from
        a healthy worker — no eviction, or interleaved bad uploads
        would force a full serial re-poll before every good one."""
        self.placement.leg_failure(name, worker)
        if not app_reject:
            with self._placement_lock:
                self._size_cache[1].pop(worker, None)
                self._evicted[worker] = time.monotonic()

    def leader_upload(self, filename: str, data: bytes) -> dict:
        """R-way least-loaded placement (generalizing
        ``Leader.java:153-207``):

        * worker index sizes are polled at most once per TTL (the
          reference polls every worker for every file,
          ``Leader.java:170-179`` — O(workers) HTTP round trips per
          document kills bulk ingest);
        * a NEW name fans out to ``replication_factor`` distinct
          least-loaded workers (capped by the live worker count); a
          name seen before routes to the workers already holding it,
          so a re-upload UPSERTS every existing copy instead of
          placing duplicates (which would diverge replicas and
          double-count in a naive merge). The map is durable through
          the coordination substrate, so holders survive leader
          failover.

        The upload succeeds when AT LEAST ONE replica accepted (the
        document is searchable); a failed leg's tentative replica is
        released and the anti-entropy repair loop restores the
        replication factor from the durable store."""
        workers = self.registry.get_all_service_addresses()
        if not workers:
            raise RuntimeError("no workers registered")
        # route NEW names away from workers with open breakers and from
        # DRAINING workers (held names still go to their holders —
        # replica continuity beats liveness, and an upsert must hit the
        # current copies even mid-drain); if every candidate is
        # excluded, fall through and let the call fail honestly rather
        # than refuse on stale breaker/drain state
        draining = self.placement.draining_snapshot()
        route_workers = (
            [w for w in workers if not self.resilience.board.is_open(w)
             and w not in draining]
            or [w for w in workers if w not in draining]
            or workers)
        with self._placement_lock:
            held = tuple(w for w in self.placement.replicas.get(
                filename, ()) if w in workers)
        if not held:
            self._ensure_sizes_fresh(route_workers)  # polls off the lock
        with self._placement_lock:
            replicas, _new = self.placement.route_locked(
                filename, workers, self._size_cache[1], route_workers,
                self.config.replication_factor)
        q = urllib.parse.quote(filename)

        def send(w: str):
            # retried (bounded) on transient transport failures: the
            # worker-side ingest is an idempotent upsert by name, so a
            # double-applied attempt converges to the same index state.
            # Epoch-stamped and fence-aware: a 403 fence rejection
            # means a newer leader exists — step down, never retry.
            return self._worker_call_fenced(
                w, lambda w=w: http_post(
                    w + f"/worker/upload?name={q}", data,
                    content_type="application/octet-stream",
                    headers=self._epoch_headers(), origin=self.url))

        futs = {self._pool.submit(send, w): w for w in replicas}
        confirmed: list[str] = []
        errors: dict[str, BaseException] = {}
        for fut, w in futs.items():
            try:
                try:
                    # bounded: ~attempts x the 30s http timeout + backoff
                    fut.result(timeout=120.0)
                except FutureTimeout:
                    # the shared pool may have QUEUED this leg behind
                    # slow scatters — only a cancelled (never-started)
                    # leg is truly failed; a running one is bounded by
                    # its own RPC timeouts and must be awaited, or a
                    # worker that eventually ACCEPTED the copy would be
                    # recorded as not holding it (unmapped duplicate =
                    # double count)
                    if fut.cancel():
                        raise
                    fut.result(timeout=900.0)
            except BaseException as e:
                errors[w] = e
                self._leg_failed(
                    filename, w,
                    app_reject=(isinstance(e, urllib.error.HTTPError)
                                and e.code < 500))
                continue
            confirmed.append(w)
            self._leg_succeeded(filename, w, len(data))
        if not confirmed:
            # every replica failed: propagate one error (an application
            # rejection — e.g. 415 — wins so the handler's status
            # mapping stays intact; all replicas see the same bytes)
            for e in errors.values():
                if isinstance(e, urllib.error.HTTPError) and e.code < 500:
                    raise e
            raise next(iter(errors.values()))
        if len(confirmed) < len(replicas):
            global_metrics.inc("uploads_partially_replicated")
        if self.config.shard_recovery:
            self._store_document(filename, data)
        global_metrics.inc("uploads_placed")
        with self._placement_lock:
            sizes = dict(self._size_cache[1])
        # the worker may be absent from the size cache (held-route after
        # an eviction skips the freshness poll) — never KeyError a
        # SUCCESSFUL upload on a logging detail
        log.info("upload placed", file=filename, workers=confirmed,
                 size=sizes.get(confirmed[0], -1))
        return {"worker": confirmed[0], "replicas": confirmed,
                "sizes": sizes}

    def leader_upload_batch(self, docs: list[dict]) -> dict:
        """Bulk ingest (framework addition — the reference only places
        one file per request): place each named document on its
        ``replication_factor`` least-loaded workers with the same
        cached policy as the per-file path, then forward ONE
        ``upload-batch`` request per worker (a document appears in R
        workers' groups). Payloads are JSON ``{"name", "text"}`` (text
        documents; binary uploads use the per-file endpoint).

        ``placed`` counts per-worker ACCEPTED copies; ``failed`` lists
        names no worker confirmed (transport-errored on every replica
        leg) — a partially-replicated name is placed (searchable) and
        the repair loop restores its missing copies later."""
        workers = self.registry.get_all_service_addresses()
        if not workers:
            raise RuntimeError("no workers registered")
        # same open-breaker + draining routing rule as the per-file path
        draining = self.placement.draining_snapshot()
        route_workers = (
            [w for w in workers if not self.resilience.board.is_open(w)
             and w not in draining]
            or [w for w in workers if w not in draining]
            or workers)
        # validate BEFORE any tracking: a KeyError mid-planning-loop
        # would leak in-flight legs for docs already routed, pinning
        # those names to never-confirmed placements forever
        for d in docs:
            if not isinstance(d, dict) or not isinstance(
                    d.get("name"), str) or not d["name"]:
                raise ValueError("every document needs a string 'name'")
            if not isinstance(d.get("text", ""), str):
                raise ValueError("document 'text' must be a string")
        # plan the split with a local estimate; placement confirmations
        # happen only for copies a worker ACCEPTED — a failed forward
        # must not leave the leader believing the unreachable worker
        # holds documents it never received. New names claim their R
        # replicas under the lock so a concurrent upload of the same
        # name routes to the same workers.
        self._ensure_sizes_fresh(route_workers)   # polls outside the lock
        per_worker: dict[str, list[dict]] = {}
        with self._placement_lock:
            # plan against a local estimate so the batch itself spreads
            # by projected size; claims/placements go through the same
            # routing rule as the per-file path
            est = {w: self._size_cache[1][w] for w in route_workers
                   if w in self._size_cache[1]}
            for d in docs:
                name = d["name"]
                reps, _new = self.placement.route_locked(
                    name, workers, est, route_workers,
                    self.config.replication_factor)
                for w in reps:
                    per_worker.setdefault(w, []).append(d)
                    # bump only workers already in the estimate: a held
                    # name routed to an unpolled worker must not inject
                    # it at near-zero size, or every later NEW name in
                    # the batch would min-route onto the
                    # possibly-unreachable worker
                    if w in est:
                        est[w] += len(d.get("text", ""))

        def forward(w: str, group: list[dict]) -> dict:
            # bounded transient retry; worker-side ingest is an
            # idempotent upsert by name (see leader_upload).
            # Epoch-stamped + fence-aware like every mutating RPC.
            return json.loads(self._worker_call_fenced(
                w, lambda: http_post(
                    w + "/worker/upload-batch",
                    json.dumps(group).encode(), timeout=300.0,
                    headers=self._epoch_headers(), origin=self.url)))

        futs = {self._pool.submit(forward, w, group): (w, group)
                for w, group in per_worker.items()}
        placed = {}
        errors = {}
        full_disk_errors: dict[str, Exception] = {}
        skipped_by_name: dict[str, dict] = {}
        confirmed_names: set[str] = set()
        for fut, (w, group) in futs.items():
            try:
                try:
                    # bounded: ~attempts x the 300s http timeout
                    resp = fut.result(timeout=1200.0)
                except FutureTimeout:
                    # same queued-vs-running distinction as the
                    # per-file path: never fail a leg that may still
                    # land on the worker
                    if fut.cancel():
                        raise
                    resp = fut.result(timeout=1200.0)
            except Exception as e:
                errors[w] = repr(e)
                # a 507 (disk full) is an app-level verdict from a
                # healthy, reachable worker: never evict its size
                # cache (a transport-failure remedy), and remember the
                # exception so an all-full-disks batch relays 507
                # instead of a retryable 500
                if isinstance(e, urllib.error.HTTPError) \
                        and e.code == storage.STORAGE_FULL_STATUS:
                    full_disk_errors[w] = e
                app_reject = (isinstance(e, urllib.error.HTTPError)
                              and (e.code < 500 or e.code
                                   == storage.STORAGE_FULL_STATUS))
                for d in group:   # settle EVERY leg, claimed or held
                    self.placement.leg_failure(d["name"], w)
                if not app_reject:      # fast re-poll on transport
                    with self._placement_lock:   # failures only
                        self._size_cache[1].pop(w, None)
                        self._evicted[w] = time.monotonic()
                continue
            # the worker reports per-doc UnsupportedMediaType skips —
            # those names were NOT indexed and must not enter the
            # placement map or the placed counts
            w_skipped = {s["name"] for s in resp.get("skipped", ())}
            for s in resp.get("skipped", ()):
                skipped_by_name.setdefault(s["name"], s)
            placed[w] = len(group) - len(w_skipped)
            for d in group:
                name = d["name"]
                if name in w_skipped:
                    self.placement.leg_failure(name, w)
                    continue
                self._leg_succeeded(name, w, len(d.get("text", "")))
                confirmed_names.add(name)
        if self.config.shard_recovery:
            for d in docs:
                if d["name"] in confirmed_names:
                    self._store_document(
                        d["name"], d.get("text", "").encode("utf-8"))
        global_metrics.inc("uploads_placed", len(confirmed_names))
        if errors and not placed:
            if len(full_disk_errors) == len(errors):
                # every leg answered 507: relay the distinct disk-full
                # verdict (non-retryable, never a breaker trip) rather
                # than a generic 500 the client would classify as a
                # retryable worker fault
                raise next(iter(full_disk_errors.values()))
            raise RuntimeError(f"all workers failed: {errors}")
        out = {"placed": placed}
        if skipped_by_name:
            out["skipped"] = list(skipped_by_name.values())
        if errors:
            out["errors"] = errors
            # names no replica confirmed and no worker skipped: never
            # indexed anywhere
            out["failed"] = [d["name"] for d in docs
                             if d["name"] not in confirmed_names
                             and d["name"] not in skipped_by_name]
        return out

    def leader_delete(self, names: list[str]) -> dict:
        """Cluster-wide document deletion (framework addition — the
        reference cannot delete a placed document at all; the jepsen
        partition workload needs a client-driven delete leg).

        Ordering makes the ack honest under crashes and partitions:

        1. the names leave the placement map and their copies enter
           the pending-reconcile (``moved``) machinery — merged search
           results exclude them IMMEDIATELY, before any worker RPC;
        2. the removal is made durable (synchronous placement flush —
           a flush failure fails the request, so an acked delete can
           never resurrect on a new leader);
        3. the leader's durable byte copy is dropped (repair can no
           longer re-place it — it already cannot, the map entry is
           gone, but the store must not outlive the doc);
        4. the worker-side deletes are pushed now (fenced, epoch-
           stamped); any failed leg is retried by the reconcile sweep
           — the pending exclusion keeps results exact meanwhile."""
        names = [str(n) for n in names]
        live = set(self.registry.get_all_service_addresses())
        # blanket-schedule across every LIVE worker, not just mapped
        # holders: a ghost copy (an upload leg recorded failed whose
        # request the worker actually processed) is masked by owner
        # assignment only while the name is mapped — the delete must
        # hunt it down everywhere or it resurrects unmapped
        scheduled = self.placement.forget(names, also=live)
        # invalidate cached results NOW — the map already excludes the
        # names, so a cache hit serving them would disagree with every
        # fresh scatter (and the fenced push loop below can stall for
        # seconds against a partitioned worker)
        self.bump_result_generation()
        if scheduled and not self._delete_flush_ok():
            raise RuntimeError(
                "delete not acknowledged: placement removal could not "
                "be made durable (the doc is gone from THIS leader's "
                "results, but a failover could resurrect it)")
        for n in names:
            try:
                path = self._store_path(n)
                if os.path.isfile(path):
                    os.remove(path)
            except Exception as e:
                log.warning("durable store cleanup failed", file=n,
                            err=repr(e))
            # purge the leader's OWN engine copy too (an ex-worker's
            # shard, or the dual-role single-node case): the residue
            # pass re-places own-engine orphans, so a lingering local
            # copy of a deleted doc would resurrect through it
            try:
                if self.engine.remove_document(n):
                    self.notify_write()
            except Exception:
                pass
        deleted = 0
        for w, ns in scheduled.items():
            if w not in live:
                continue   # sweep/rejoin reconcile owns it later

            def rpc(w=w, ns=ns) -> dict:
                global_injector.check("leader.reconcile_rpc")
                return json.loads(http_post(
                    w + "/worker/delete",
                    json.dumps({"names": sorted(ns)}).encode(),
                    timeout=120.0, headers=self._epoch_headers(),
                    origin=self.url))

            try:
                resp = self._worker_call_fenced(w, rpc)
            except Exception as e:
                global_metrics.inc("reconcile_failures")
                log.warning("delete push failed (sweep will retry)",
                            worker=w, err=repr(e))
                continue
            self.placement.moved_resolved(w, set(ns))
            deleted += int(resp.get("deleted", 0))
        if deleted:
            # the landed engine deletes shifted worker-side df: results
            # cached since the first bump were computed pre-delete
            self.bump_result_generation()
        global_metrics.inc("docs_cluster_deleted", len(names))
        return {"forgotten": len(names), "deleted": deleted}

    def _delete_flush_ok(self) -> bool:
        """Make a delete's placement removal durable. True when the
        flush landed OR persistence is structurally off (per-tenure
        map / no store bound — nothing to resurrect from); False only
        when a real durable map exists and could not be updated."""
        if (self.config.placement_flush_ms < 0
                or not self.placement._persist_enabled):
            return True
        try:
            return self.placement.flush()
        except Exception as e:
            log.warning("delete placement flush failed", err=repr(e))
            return False

    def leader_download_stream(self, rel: str):
        """Locate a document and return a readable stream + size for
        chunked proxying: local disk first, else probe every worker and
        stream the first hit through (``Leader.java:95-151`` serves
        ``FileSystemResource`` streams; buffering whole files per
        request would hold a thread's memory hostage at GB scale).

        Returns ``(fileobj, size | None)`` or ``None``; the caller owns
        closing the fileobj."""
        local = self.engine.open_document_stream(rel)
        if local is not None:
            return local
        try:   # the leader's durable recovery store is a local source too
            path = self._store_path(rel)
            if os.path.isfile(path):
                return open(path, "rb"), os.path.getsize(path)
        except PermissionError:
            raise
        except Exception:
            pass
        q = urllib.parse.quote(rel)
        for w in self.registry.get_all_service_addresses():
            if self.resilience.board.is_open(w):
                continue   # skip sick workers; another may hold the doc
            try:
                # breaker-tracked, no retry: probing the NEXT worker is
                # this loop's retry. A 404 (doc lives elsewhere) is an
                # app-level answer from a healthy worker — it does not
                # count against the breaker.
                resp = self.resilience.worker_call(
                    w, lambda w=w: http_get_stream(
                        w + f"/worker/download?path={q}", timeout=30.0,
                        origin=self.url),
                    retry=False)
                size = resp.headers.get("Content-Length")
                return resp, (int(size) if size is not None else None)
            except Exception:
                continue   # first 2xx wins; probe the next (Leader.java:144)
        return None

    def leader_download(self, rel: str) -> bytes | None:
        """Buffered convenience wrapper over the streaming path."""
        got = self.leader_download_stream(rel)
        if got is None:
            return None
        stream, _size = got
        try:
            return stream.read()
        finally:
            stream.close()

    def read_download_stream(self, rel: str):
        """The read plane's download locator (the shared
        ``/leader/download`` handler calls this on every host): a node
        serves from its engine + durable store, then probes workers."""
        return self.leader_download_stream(rel)

    # ---- mutation-plane role gate (cluster/router.py) ----

    def _should_forward_writes(self) -> bool:
        """Should this node forward a front-door mutation to the
        elected leader instead of serving it? True only for a
        NON-leader with a known, distinct leader — the mutation plane
        (placement routing, replication bookkeeping, cache
        invalidation) is leader-only state, and a worker accepting an
        upload would place documents its leader's map never learns
        about. When no leader is published (mid-election) the legacy
        local path still answers rather than failing closed."""
        if not self.config.router_forward_writes \
                or self._role == "leader":
            return False
        leader = self.leader_url()
        return bool(leader) and leader.rstrip("/") != self.url

    def read_plane_snapshot(self) -> dict:
        """``GET /api/router`` on a node: which placement world this
        node's read plane routes under (the CLI routers summary
        compares routers' views against the leader's)."""
        out = {"role": self._role, "url": self.url}
        if self._role == "leader":
            with self._placement_lock:
                docs = len(self._placement)
            out["placement"] = {"authoritative": True, "docs": docs,
                                "epoch": self.placement.epoch,
                                "gen": self.placement.gen}
        elif self._follower_active():
            out["placement"] = dict(
                self.placement_follower.view_snapshot(),
                authoritative=False)
        else:
            out["placement"] = {"authoritative": False, "loaded": False}
        return out


# the shared threaded HTTP server (cluster/router.py); the old name is
# kept for tests and embedding code
_NodeServer = _PlaneServer


class _NodeHandler(_HttpHandlerBase):
    """The symmetric node's HTTP surface: the shared read-plane routes
    (search / download / metrics / traces — cluster/router.py) plus
    the worker data plane, the leadership fence, and the leader-only
    ops endpoints."""

    node: SearchNode   # bound by SearchNode.__init__

    def _fence_check(self) -> bool:
        """Leadership fence on the mutating worker endpoints
        (``/worker/upload[-batch]``, ``/worker/delete``): a request
        stamped with a LOWER epoch than the highest this worker ever
        saw is answered with the distinct fence status (403 +
        ``X-Fence-Rejected: 1``) — the sender is a deposed leader and
        must step down, not retry. Unstamped requests (external /
        reference clients, single-node mode) are never fenced. Returns
        True when the rejection was sent. Callers read the body BEFORE
        checking so a rejected keep-alive connection stays in sync."""
        hdr = self.headers.get(FENCE_HEADER)
        if hdr is None:
            return False
        try:
            epoch = int(hdr)
        except ValueError:
            return False
        node = self.node
        global_injector.check("worker.fence")
        if node.fence.observe(epoch):
            return False
        current = node.fence.current()
        global_metrics.inc("fence_rejections")
        log.warning("fenced a stale-leader write", stale_epoch=epoch,
                    current_epoch=current, path=self.path)
        self._send(FENCE_STATUS, b"stale leader epoch",
                   "text/plain; charset=utf-8",
                   headers={FENCE_REJECTED_HEADER: "1",
                            FENCE_EPOCH_HEADER: str(current)})
        return True

    # ---- routing ----

    def do_GET(self) -> None:
        u = urllib.parse.urlparse(self.path)
        node = self.node
        self._last_span = None
        try:
            if not self._proto_gate(u.path):
                return
            if u.path == "/api/health":
                # the reserved observability lane: never admission-
                # controlled, never blocks on coordination or serving
                # locks (role is the cached last transition, depth is a
                # gauge read) — so operators can SEE a shedding node.
                # Each connection gets its own handler thread, so a
                # saturated bulk flood cannot queue ahead of this.
                self._json({
                    "ok": True, "role": node._role,
                    "proto_version": PROTO_VERSION,
                    "scatter_queue_depth": global_metrics.get(
                        "last_scatter_queue_depth", 0.0),
                    "admission": node.admission.snapshot(),
                    # embedding-column summary (dims, docs embedded,
                    # bytes resident) for the CLI status fan-out; null
                    # when the dense plane is disabled
                    "embedding": node.engine.dense_stats(),
                    # whether the C++ ingest library was built and
                    # loaded (config.native_ingest asked for it; a
                    # missing compiler quietly means the Python chain)
                    "native_ingest": node.engine.native is not None,
                    # tiered-postings residency counters (ISSUE 18):
                    # hot/cold segment counts, HBM bytes vs budget,
                    # hit/skip rates — {"enabled": false} when off.
                    # JSON body only; no header/endpoint change, so
                    # the wire fingerprint is untouched.
                    "tier": node.engine.tier_stats(),
                    # compute-plane health (ISSUE 20): the per-worker
                    # device state machine (healthy|degraded|sick),
                    # fault/fallback counters, and whether a host
                    # mirror exists for this snapshot — the leader's
                    # placement and the router's owner-merge read this
                    # to route around a sick device. JSON body only.
                    "compute": node.engine.compute_stats()})
            elif u.path == "/api/ready":
                # readinessProbe target (deploy/k8s.yaml): a SICK
                # compute plane with no host fallback cannot answer
                # queries — take the pod out of Service endpoints
                # until the device recovers. Degraded (host-fallback)
                # serving stays READY: slower, but exact. Liveness
                # stays /api/health — a sick device is not a reason
                # to restart the process (restart would not heal HBM,
                # and the WAL replay would just add downtime).
                cs = node.engine.compute_stats()
                if cs.get("state") == "sick" and not cs.get(
                        "fallback_available"):
                    self._json({"ready": False, "compute": cs}, 503,
                               headers={"Retry-After": "1"})
                else:
                    self._json({"ready": True, "compute": cs})
            elif u.path == "/api/quarantine":
                # poison-query quarantine table (leader/router-side
                # state; a plain worker answers an empty table) — the
                # CLI `quarantine` command reads this
                self._json(node.quarantine.snapshot())
            elif u.path == "/api/device-nemesis":
                # armed compute-chaos rules (observability; the POST
                # that arms them is config-gated — see do_POST)
                from tfidf_tpu.utils.device_nemesis import \
                    global_device_nemesis as _dn
                if not node.config.device_nemesis_api:
                    self._text("device nemesis disabled "
                               "(config.device_nemesis_api=False)", 403)
                    return
                self._json(_dn.snapshot())
            elif u.path == "/worker/index-size":
                self._text(str(node.engine.index_size_bytes()))
            elif u.path == "/worker/names":
                # ground truth for the leader's residue anti-entropy
                # pass: what THIS engine actually serves (names: null
                # when the index layout cannot list — mesh layouts)
                self._json({"names": node.engine.document_names()})
            elif u.path == "/worker/download":
                self._download_from_engine(u)
            elif u.path == "/leader/download":
                # the front door guards every /leader/* endpoint:
                # checkpoint downloads are bulk transfers (real file
                # I/O per request), first to shed under backpressure —
                # the shared read-plane branch (cluster/router.py)
                self._serve_leader_download(u)
            elif u.path == "/api/status":
                # same phrasing as Controllers.java:25-29
                self._text("I am the leader" if node.is_leader()
                           else "I am a worker node")
            elif u.path == "/api/services":
                self._json(node.registry.get_all_service_addresses())
            elif u.path == "/api/leader":
                # the published /leader_info znode over HTTP: the
                # leader leaves the worker pool on promotion, so
                # /api/services alone cannot name it — clients (and
                # the CLI trace fan-out, whose request spans live in
                # the LEADER's ring) discover it here from any node
                try:
                    addr = read_leader_info(node.coord)
                except Exception:
                    addr = None
                self._json({"leader": addr})
            elif u.path == "/api/drain":
                # drain progress for one worker. Leader-only like the
                # POST: a follower's placement map is reset on demotion,
                # so it would answer a vacuous {"drained": true} and an
                # operator's --wait poll could decommission a worker
                # that still holds docs under the real leader
                if not node.is_leader():
                    self._text("not the leader", 409)
                    return
                worker = self._query_param(u, "worker")
                if not worker:
                    self._text("missing worker", 400)
                    return
                self._json(node.rebalancer.drain_status(worker))
            elif u.path == "/api/autopilot":
                # autopilot state + decision-audit ring (observability
                # lane, never admission-controlled — an operator must
                # be able to audit the controller exactly while the
                # cluster it steers is shedding). ?recent=N bounds the
                # decision records returned (default 50).
                try:
                    n = int(self._query_param(u, "recent") or 50)
                except ValueError:
                    n = 50
                self._json({"autopilot": node.autopilot.snapshot(),
                            "decisions": node.autopilot.decisions(n)})
            elif u.path == "/api/router":
                # which placement world this node's read plane routes
                # under (leader: the authoritative map; worker: its
                # follower view) — the CLI routers summary compares
                # router views against the leader's answer here
                self._json(node.read_plane_snapshot())
            elif u.path == "/api/routers":
                # the registered stateless-router tier (ephemeral
                # znodes under /router_registry — cluster/router.py)
                self._json(list_routers(node.coord))
            elif self._serve_metrics(u):
                # /metrics + /api/metrics: the shared exposition branch
                # (cluster/router.py; observability lane, never
                # admission-controlled)
                pass
            elif self._serve_trace(u):
                # trace export: the shared branch (cluster/router.py;
                # observability lane, never admission-controlled)
                pass
            else:
                self._text("not found", 404)
        except Exception as e:
            self._fail_500(u, e)

    def _serve_process_batch(self) -> None:
        """The ``/worker/process-batch`` branch, from the body read to
        the reply written: what ``phase_handle_batch`` times. The 200
        reply says so itself (``Server-Timing``: when this branch began
        on the shared ``epoch_now`` clock, and how long it took to the
        reply's first write), which is where the leader cuts its
        ``scatter_rpc`` into the way out, the worker and the way
        back."""
        node = self.node
        recv_s = epoch_now()
        # batched scatter RPC (leader-internal; packed reply —
        # see cluster/wire.py). The per-query endpoint above
        # keeps the reference-compatible JSON shape. With
        # "names" the request is an ownership SLICE (failover /
        # hedged re-issue): score only those documents, exact
        # within the slice.
        global_injector.check("worker.process")
        # propagated scatter budget: the leader's remaining
        # milliseconds at dispatch; a batch whose budget is
        # already gone is refused with a 504 the resilience
        # layer treats as non-retryable — scoring it would
        # burn device time nobody will merge (the deadline is
        # re-checked after the NRT commit in
        # _search_batch_guarded)
        if self._past_deadline():
            return
        deadline = self._deadline_header()
        req = json.loads(self._body().decode("utf-8"))
        queries = [str(q) for q in req.get("queries", ())]
        k = req.get("k")
        names = req.get("names")
        # hybrid plan (wire v3): "mode" selects which scoring
        # stages run. Absent -> sparse, so v2 leaders are
        # untouched; a v2 WORKER ignoring the field replies n
        # lists where the leader expects 2n and the leader's
        # slot-count check degrades honestly (never merges a
        # misaligned reply).
        mode = str(req.get("mode", "sparse"))
        # continues the leader's scatter trace (propagated
        # headers); the engine's trace_phase events and the
        # pipeline stage events land inside this span — and so
        # do the REPLIES (200, 500, and the 504 deadline
        # refusal): _send stamps X-Trace-Id from the active
        # span, so the reply the leader logs on a failed
        # scatter leg joins the trace (graftcheck protocol
        # finding, fixed — replies used to be emitted after
        # the span closed and were never stamped; the runtime
        # protocol witness pins this)
        with self._worker_span(
                "worker.process_batch",
                queries=len(queries),
                slice=len(names) if names is not None
                else 0):
            try:
                if names is not None and mode != "sparse":
                    body = pack_hit_lists(
                        node.worker_search_slice_staged(
                            queries, [str(n) for n in names],
                            mode, deadline=deadline))
                elif names is not None:
                    body = pack_hit_lists(
                        node.worker_search_slice(
                            queries, [str(n) for n in names],
                            deadline=deadline))
                elif mode != "sparse":
                    body = node.worker_search_staged_wire(
                        queries,
                        k=int(k) if k is not None else None,
                        mode=mode, deadline=deadline)
                else:
                    body = node.worker_search_batch_wire(
                        queries,
                        k=int(k) if k is not None else None,
                        deadline=deadline)
            except WorkerDeadline as e:
                span_event("worker_deadline_refused")
                self._send(504, f"{e}".encode(),
                           "text/plain; charset=utf-8",
                           headers={"X-Deadline-Exceeded": "1"})
                return
            except TooManyQueryTerms as e:
                # the caller's fault, not the engine's: a 400 that
                # names every refused query of the batch with its
                # count and the limit. A leader of this configuration
                # refuses such a query at its own door, so only a
                # direct caller (or a leader configured wider than
                # this worker) sees it
                span_event("query_terms_refused", queries=len(e.refused))
                self._refuse_query_terms(e.refused, e.limit)
                return
            except Exception as e:
                # honest failure propagation (ADVICE r5): an
                # engine failure must surface as a 5xx the
                # leader counts in scatter_failures — NOT as an
                # HTTP 200 all-empty reply it would merge as a
                # valid zero-hit result. (The per-query
                # /worker/process endpoint above keeps the
                # reference's []-on-failure parity shape,
                # Worker.java:183; this endpoint is
                # leader-internal.) A classified compute fault
                # rides X-Compute-Fault so the leader's retry
                # gate and quarantine see the taxonomy instead
                # of string-matching the repr; a poisoned
                # output additionally names the guilty query
                # rows (X-Poison-Fingerprints) so the
                # quarantine never blames innocent cohort
                # queries that merely shared the batch.
                global_metrics.inc("worker_batch_failures")
                span_event("worker_batch_failed",
                           err=repr(e)[:120])
                log.warning("batch search failed", err=repr(e))
                eh: dict[str, str] = {}
                fault = classify_compute_fault(e)
                if fault is not None:
                    eh["X-Compute-Fault"] = fault
                    qrows = getattr(e, "queries", ())
                    if fault == "poison" and qrows:
                        eh["X-Poison-Fingerprints"] = ",".join(
                            poison_fingerprint(q, mode)
                            for q in qrows)
                self._send(
                    500,
                    f"batch search failed: {e!r}".encode(),
                    "text/plain; charset=utf-8", headers=eh)
                return
            # host-fallback honesty: when the engine served
            # this batch from the numpy mirror (degraded, not
            # wrong — scores are bit-exact), say so on the
            # wire so the leader can surface X-Compute-Degraded
            # end-to-end instead of silently presenting sick
            # hardware as healthy
            dh = ({"X-Compute-Degraded": "1"}
                  if node.engine.pop_fallback_served() else {})
            dh[SERVER_TIMING_HEADER] = server_timing(recv_s)
            self._send(200, body, "application/octet-stream",
                       headers=dh)

    def do_POST(self) -> None:
        u = urllib.parse.urlparse(self.path)
        node = self.node
        self._last_span = None
        try:
            if not self._proto_gate(u.path):
                return
            if u.path == "/worker/process":
                # same deadline refusal as the batched endpoint: the
                # leader's per-query path propagates X-Deadline-Ms too,
                # and scoring for a caller that already gave up burns
                # device time nobody merges. External reference clients
                # never send the header — parity behavior is untouched.
                if self._past_deadline():
                    return
                global_injector.check("worker.process")
                query = self._read_query()
                # the reply is emitted INSIDE the propagated span so a
                # leader-traced request's answer carries X-Trace-Id
                # (graftcheck protocol finding, fixed: replies sent
                # after the `with` closed were never trace-stamped —
                # the runtime protocol witness pins this)
                with self._worker_span("worker.process"):
                    try:
                        hits = node.worker_search(query)
                    except TooManyQueryTerms as e:
                        # ... but a query it will not score whole is
                        # refused by name, never answered in part
                        self._refuse_query_terms(e.refused, e.limit)
                        return
                    except Exception as e:
                        # reference returns [] on any failure
                        # (Worker.java:183)
                        log.warning("search failed", err=repr(e))
                        hits = []
                    # (the degraded flag is popped even on this parity
                    # endpoint: a stale thread-local would mis-stamp
                    # the NEXT batch this handler thread serves)
                    dh = ({"X-Compute-Degraded": "1"}
                          if node.engine.pop_fallback_served() else None)
                    self._json([{"document": {"name": h.name},
                                 "score": h.score} for h in hits],
                               headers=dh)
            elif u.path == "/worker/process-batch":
                with trace_phase("handle_batch"):
                    self._serve_process_batch()
            elif u.path == "/worker/upload":
                name, data = self._read_upload(u)
                if self._fence_check():   # after the body read: the
                    return                # rejected conn stays in sync
                if not name:
                    self._text("missing file name", 400)
                    return
                global_injector.check("worker.upload")
                # docs_indexed is counted once, by the index add path;
                # the commit is deferred to the next search (NRT policy,
                # see SearchNode.commit_if_dirty) — the raw file is
                # already durable on disk at this point
                try:
                    node.engine.ingest_bytes(name, data,
                                             save_to_disk=True)
                except UnsupportedMediaType as e:
                    # the Tika-parity contract: extract or refuse loudly,
                    # never index binary bytes as mojibake
                    self._text(f"unsupported media type: {e}", 415)
                    return
                except OSError as e:
                    if not storage.is_enospc(e):
                        raise
                    # disk full: the distinct 507 — non-retryable by
                    # classification and never a breaker trip (a node
                    # with a full disk still serves reads perfectly)
                    self._text("insufficient storage (disk full)", 507)
                    return
                node.notify_write()
                # a direct worker-side write also changes THIS node's
                # df — keep its own result cache honest (dual-role and
                # single-node deployments serve /leader/start here too)
                node.bump_result_generation()
                self._text(f"File {name} uploaded and indexed")
            elif u.path == "/worker/upload-batch":
                docs = json.loads(self._body().decode("utf-8"))
                if self._fence_check():
                    return
                global_injector.check("worker.upload")
                skipped = []
                staged: list[tuple] = []   # (name, tmp, path, text)
                enospc = False
                durable = node.config.storage_fsync
                try:
                    # two-phase group commit (fsync-before-ack without
                    # one fsync per document): stage every temp, ONE
                    # committer round over all of them, then publish
                    # renames + index, then ONE round over the unique
                    # directories — 2 fsync rounds per batch
                    for d in docs:
                        try:
                            staged.append((d["name"],
                                           *node.engine.stage_bytes(
                                               d["name"],
                                               d["text"].encode(
                                                   "utf-8"))))
                        except UnsupportedMediaType as e:
                            skipped.append({"name": d["name"],
                                            "error": str(e)})
                        except OSError as e:
                            if not storage.is_enospc(e):
                                raise
                            enospc = True
                            break
                    # ENOSPC is mapped to 507 from the fsync rounds
                    # too: with delayed allocation, fsync can be the
                    # FIRST syscall to report a full disk — a 500 here
                    # would trip the breaker the 507 contract protects
                    try:
                        if durable and staged and not enospc:
                            storage.global_committer.sync(
                                [t[1] for t in staged])
                        dirs: set = set()
                        if not enospc:
                            for name, tmp, path, text in staged:
                                node.engine.publish_staged(
                                    name, tmp, path, text)
                                dirs.add(os.path.dirname(path))
                            staged = []
                        if durable and dirs:
                            storage.global_committer.sync(sorted(dirs))
                    except OSError as e:
                        if not storage.is_enospc(e):
                            raise
                        enospc = True
                finally:
                    for _name, tmp, _path, _text in staged:
                        node.engine.discard_staged(tmp)
                    # mark dirty even on a mid-batch failure: the docs
                    # already ingested must become searchable at the
                    # next NRT flush, not be stranded uncommitted
                    if docs:
                        node.notify_write()
                        node.bump_result_generation()
                if enospc:
                    self._text("insufficient storage (disk full)", 507)
                    return
                self._json({"indexed": len(docs) - len(skipped),
                            "skipped": skipped})
            elif u.path == "/worker/delete":
                # shard-recovery reconciliation: remove moved documents
                # from index AND disk (a boot re-walk must not resurrect
                # them). Framework addition — the reference cannot move
                # documents between workers at all.
                names = json.loads(self._body().decode("utf-8"))
                if self._fence_check():
                    return
                names = names.get("names", []) if isinstance(names, dict) \
                    else names
                removed = sum(
                    bool(node.engine.remove_document(str(n)))
                    for n in names)
                if removed:
                    node.notify_write()
                    node.bump_result_generation()
                self._json({"deleted": removed})
            elif u.path == "/api/drain":
                # planned decommission: migrate the worker empty before
                # it leaves (leader-only — the drain mutates the
                # authoritative placement map). Body: {"worker": url,
                # "cancel": bool?}. The draining flag is durable, so a
                # leader failover restarts the drain.
                if not node.is_leader():
                    self._text("not the leader", 409)
                    return
                req = json.loads(self._body().decode("utf-8"))
                worker = req.get("worker")
                if not isinstance(worker, str) or not worker:
                    self._text("missing worker", 400)
                    return
                if req.get("cancel"):
                    self._json(node.rebalancer.cancel_drain(worker))
                else:
                    self._json(node.rebalancer.start_drain(worker))
            elif u.path == "/api/autopilot":
                # the runtime kill switch. Body: {"enabled": bool}.
                # Disabling reverts every managed knob to its static
                # config value BEFORE the reply is sent — the caller
                # observes a cluster already back on hand-tuned
                # constants. Acts on THIS node's autopilot (the loop
                # does work only while leader, so point it at the
                # leader); not admission-controlled — the switch must
                # work exactly when the front door sheds.
                req = json.loads(self._body().decode("utf-8"))
                if not isinstance(req, dict) or not isinstance(
                        req.get("enabled"), bool):
                    self._text("body must be {\"enabled\": bool}", 400)
                    return
                self._json({"autopilot":
                            node.autopilot.set_enabled(req["enabled"])})
            elif u.path == "/api/quarantine":
                # operator override after a fix rolls out: drop every
                # poison verdict on THIS node's read plane
                self._json({"cleared": node.quarantine.clear()})
            elif u.path == "/api/device-nemesis":
                # scriptable compute-plane chaos (ISSUE 20,
                # utils/device_nemesis.py) — double-gated: the config
                # knob must opt in AND the rule grammar is the same
                # one TFIDF_DEVICE_NEMESIS accepts. Body:
                # {"script": "site:kind[:prob[:k=v;...]] ..."} to arm,
                # {"clear": true} to drop rules + lift sick,
                # {"heal": true} to lift sick only. Never enabled in
                # production configs; refusing with 403 (not 404)
                # makes a misconfigured chaos suite loud.
                from tfidf_tpu.utils.device_nemesis import \
                    global_device_nemesis as _dn
                if not node.config.device_nemesis_api:
                    self._text("device nemesis disabled "
                               "(config.device_nemesis_api=False)", 403)
                    return
                req = json.loads(self._body().decode("utf-8"))
                if req.get("clear"):
                    _dn.clear()
                elif req.get("heal"):
                    _dn.heal()
                spec = req.get("script")
                rids = _dn.script(str(spec)) if spec else []
                self._json({"armed": _dn.armed, "sick": _dn.sick,
                            "rules": rids})
            elif u.path == "/admin/checkpoint":
                # on-demand durability point (reference analog: the
                # per-upload indexWriter.commit(), Worker.java:138)
                node.commit_if_dirty()
                try:
                    self._json(node.save_checkpoint())
                except OSError as e:
                    if not storage.is_enospc(e):
                        raise
                    self._text("insufficient storage (disk full)", 507)
            elif u.path == "/admin/scrub":
                # on-demand integrity-scrub pass (README "Storage
                # durability & integrity"); the sweep loop runs the
                # same pass on the storage_scrub_ms cadence
                self._json(node.run_integrity_scrub())
            elif u.path == "/leader/upload-batch":
                # uploads are bulk by default: first to shed under
                # backpressure, so ingest never crowds out interactive
                # search latency (admit BEFORE reading the body — a
                # shed upload pays at most the 1 MB drain in _shed,
                # never a JSON parse or an index slot). Mutations stay
                # on the elected leader: a non-leader forwards instead
                # of mutating state its leader's map never learns of.
                if node._should_forward_writes():
                    self._forward_write(u)
                    return
                with self._admitted("leader.upload_batch",
                                    LANE_BULK) as (sp, _lane):
                    if sp is None:
                        return
                    docs = json.loads(self._body().decode("utf-8"))
                    sp.set_attr("docs", len(docs)
                                if isinstance(docs, list) else 0)
                    try:
                        self._json(node.leader_upload_batch(docs))
                    except ValueError as e:  # malformed client payload
                        self._text(str(e), 400)
                    except urllib.error.HTTPError as e:
                        if e.code != storage.STORAGE_FULL_STATUS:
                            raise
                        # every replica leg reported a full disk:
                        # relay the distinct verdict
                        self._text("insufficient storage "
                                   "(worker disks full)", 507)
            elif u.path == "/leader/start":
                # the shared read-plane search branch
                # (cluster/router.py): front-door admission BEFORE any
                # work is queued, the trace span minted at the
                # admission point, the degraded header + (epoch,
                # generation) route stamp on the reply. Served by
                # EVERY node — a non-leader routes through its
                # placement follower view.
                self._serve_search()
            elif u.path == "/leader/delete":
                # placement-aware cluster-wide deletion (the upsert/
                # delete/search partition workload's delete leg); bulk
                # lane like every other mutating front-door endpoint.
                # Mutation plane: non-leaders forward to the leader.
                if node._should_forward_writes():
                    self._forward_write(u)
                    return
                with self._admitted("leader.delete",
                                    LANE_BULK) as (sp, _lane):
                    if sp is None:
                        return
                    req = json.loads(self._body().decode("utf-8"))
                    names = req.get("names", []) \
                        if isinstance(req, dict) else req
                    sp.set_attr("names", len(names))
                    self._json(node.leader_delete(
                        [str(n) for n in names]))
            elif u.path == "/leader/upload":
                if node._should_forward_writes():
                    self._forward_write(u)
                    return
                with self._admitted("leader.upload",
                                    LANE_BULK) as (sp, _lane):
                    if sp is None:
                        return
                    name, data = self._read_upload(u)
                    if not name:
                        self._text("missing file name", 400)
                        return
                    sp.set_attr("file", name)
                    try:
                        result = node.leader_upload(name, data)
                    except urllib.error.HTTPError as e:
                        if e.code == 415:  # worker refused the format
                            self._text("unsupported media type", 415)
                            return
                        if e.code == storage.STORAGE_FULL_STATUS:
                            # relay the worker's disk-full verdict
                            # distinctly: the client must not classify
                            # a full disk as a retryable 5xx
                            self._text("insufficient storage "
                                       "(worker disk full)", 507)
                            return
                        raise
                    self._text(f"File uploaded successfully to worker: "
                               f"{result['worker']}")
            else:
                self._text("not found", 404)
        except Exception as e:
            self._fail_500(u, e)

    def _download_from_engine(self, u) -> None:
        # URL-decode + traversal check live in Engine._safe_doc_path
        # (Worker.java:97-121 parity)
        rel = urllib.parse.unquote(self._query_param(u, "path") or "")
        try:
            got = self.node.engine.open_document_stream(rel)
        except PermissionError:
            self._text("invalid path", 400)
            return
        if got is None:
            self._text("not found", 404)
        else:
            self._stream(*got)
