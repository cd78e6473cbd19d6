"""Overload-survival front door: admission control, priority lanes,
load shedding, backpressure, and generation-keyed result caching.

The acceptance story: under a closed-loop overload the leader sheds
with an explicit ``429 + Retry-After`` instead of queueing unboundedly;
bulk traffic can never starve interactive (weighted dequeue, and bulk
sheds first under backpressure); ``/api/health`` and ``/api/metrics``
stay responsive while the cluster sheds; and every ADMITTED result is
exact — the generation-keyed result cache misses after any commit that
changes the df signature (upsert, delete, migration flip), proven
against a single-node oracle under a concurrent write workload.

The slow chaos job (``make chaos-overload``) adds a 2x-overload
zipfian closed loop with a real mid-run worker ``kill -9``: shed rate
rises, p99 of admitted interactive queries stays bounded, parity holds.
"""

import json
import random
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

from tfidf_tpu.cluster.admission import (LANE_BULK, LANE_INTERACTIVE,
                                         AdmissionController, ResultCache,
                                         TokenBucket)
from tfidf_tpu.cluster.batcher import Coalescer, _Waiter
from tfidf_tpu.cluster.coordination import (CoordinationCore,
                                            LocalCoordination)
from tfidf_tpu.cluster.node import SearchNode, http_get, http_post
from tfidf_tpu.cluster.resilience import (ClusterResilience, RetryPolicy,
                                          RpcStatusError, is_retryable,
                                          is_worker_fault, retry_after_of)
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

from tests.test_cluster import wait_until


@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


DOCS = {f"ad{i}.txt": f"common token{i} word{i % 3} extra{i % 5}"
        for i in range(12)}
QUERIES = ["common", "token3 word0", "word1 extra2", "common token7"]

_CFG = dict(
    top_k=32, min_doc_capacity=64, min_nnz_capacity=1 << 12,
    min_vocab_capacity=1 << 10, query_batch=8, max_query_terms=8,
    rpc_max_attempts=1,            # deterministic: no hidden retries
    breaker_failure_threshold=2, breaker_reset_s=0.4,
    reconcile_sweep_interval_s=0.2, placement_flush_ms=10.0,
    # admission defaults for the HTTP tests: rate limiting OFF (each
    # test arms what it exercises), watermarks far away
    admission_rate_qps=0.0, admission_queue_high_water=10_000,
    admission_queue_critical=100_000)


def _node(core, tmp_path, i, port=0, **kw):
    cfg_kw = dict(_CFG)
    cfg_kw.update(kw)
    cfg = Config(
        documents_path=str(tmp_path / f"ad{i}" / "documents"),
        index_path=str(tmp_path / f"ad{i}" / "index"),
        port=port, **cfg_kw)
    return SearchNode(cfg, coord=LocalCoordination(core, 0.1)).start()


def _mk_cluster(core, tmp_path, n=3, **kw):
    nodes = [_node(core, tmp_path, i, **kw) for i in range(n)]
    wait_until(lambda: len(
        nodes[0].registry.get_all_service_addresses()) == n - 1)
    return nodes


def _stop_all(nodes):
    for nd in nodes:
        try:
            nd.stop()
        except Exception:
            pass


def _upload_docs(leader, docs=DOCS):
    batch = [{"name": n, "text": t} for n, t in docs.items()]
    return json.loads(http_post(leader.url + "/leader/upload-batch",
                                json.dumps(batch).encode()))


def _search(leader, q, headers=None):
    return json.loads(http_post(
        leader.url + "/leader/start", json.dumps({"query": q}).encode(),
        headers=headers))


def _oracle(tmp_path, docs=DOCS, queries=QUERIES, tag="oracle", **cfg_kw):
    kw = {k: v for k, v in _CFG.items()
          if k in ("top_k", "min_doc_capacity", "min_nnz_capacity",
                   "min_vocab_capacity", "query_batch",
                   "max_query_terms")}
    kw.update(cfg_kw)
    cfg = Config(documents_path=str(tmp_path / tag / "documents"),
                 index_path=str(tmp_path / tag / "index"), **kw)
    eng = Engine(cfg)
    for n, t in docs.items():
        eng.ingest_text(n, t)
    eng.commit()
    out = {}
    for q in queries:
        out[q] = {h.name: float(h.score)
                  for h in eng.search(q, k=cfg.top_k)}
    return out


def _assert_parity(got: dict, want: dict, ctx=""):
    assert set(got) == set(want), \
        f"{ctx}: missing={set(want) - set(got)} extra={set(got) - set(want)}"
    for n, s in want.items():
        assert got[n] == pytest.approx(s, rel=1e-5), (ctx, n, got[n], s)


def _settle_signature(leader, timeout=5.0):
    """Wait until the leader's df-signature token stops advancing (all
    in-flight replica upload legs confirmed): cache-hit assertions need
    a quiescent generation, or a late second-leg confirmation between
    two searches turns an expected hit into an honest (but
    miscounted-by-the-test) miss."""
    def quiet():
        t1 = leader.df_signature()
        time.sleep(0.1)
        return leader.df_signature() == t1
    assert wait_until(quiet, timeout=timeout)


def _parity_settles(leader, q, want, ctx="", timeout=10.0):
    """wait_until-compatible exact-parity convergence: mismatches while
    replica legs land read as not-yet, the FINAL state must hold."""
    def ok():
        try:
            _assert_parity(_search(leader, q), want, ctx)
            return True
        except AssertionError:
            return False
    assert wait_until(ok, timeout=timeout), \
        f"{ctx}: never converged to oracle parity"


def _shed_info(err: urllib.error.HTTPError) -> tuple[float, str, dict]:
    """(retry_after_s, X-Shed-Reason, body) from a 429 reply."""
    assert err.code == 429
    ra = float(err.headers.get("Retry-After"))
    body = json.loads(err.read().decode())
    return ra, err.headers.get("X-Shed-Reason"), body


# ---------------------------------------------------------------------------
# Token bucket + admission controller units
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestTokenBucket:
    def test_burst_then_honest_retry_after(self):
        clk = FakeClock()
        b = TokenBucket(rate=2.0, burst=3.0, clock=clk)
        assert [b.try_take(clk()) for _ in range(3)] == [0.0, 0.0, 0.0]
        wait = b.try_take(clk())
        assert wait > 0.0
        # the hint is honest: waiting exactly that long buys admission
        clk.t += wait
        assert b.try_take(clk()) == 0.0
        # ... and not a microsecond less
        wait2 = b.try_take(clk())
        assert wait2 == pytest.approx(0.5, rel=1e-6)   # 1 token / 2 qps

    def test_refill_caps_at_burst(self):
        clk = FakeClock()
        b = TokenBucket(rate=1.0, burst=2.0, clock=clk)
        clk.t += 100.0   # long idle: tokens cap at burst, not 100
        assert b.try_take(clk()) == 0.0
        assert b.try_take(clk()) == 0.0
        assert b.try_take(clk()) > 0.0


def _admission(depth=0.0, **kw):
    cfg_kw = dict(admission_enabled=True, admission_rate_qps=0.0,
                  admission_burst=0.0, admission_queue_high_water=8,
                  admission_queue_critical=32,
                  admission_retry_after_s=0.25, admission_max_clients=64)
    cfg_kw.update(kw)
    clk = FakeClock()
    holder = {"depth": depth}
    ctl = AdmissionController(Config(**cfg_kw),
                              depth_fn=lambda: holder["depth"], clock=clk)
    return ctl, holder, clk


class TestAdmissionController:
    def test_backpressure_sheds_bulk_first_then_interactive(self):
        ctl, depth, _ = _admission()
        # below high water: everyone admitted
        depth["depth"] = 7
        assert ctl.admit("c", LANE_BULK).admitted
        assert ctl.admit("c", LANE_INTERACTIVE).admitted
        # at high water: bulk sheds, interactive survives
        depth["depth"] = 8
        d = ctl.admit("c", LANE_BULK)
        assert not d.admitted and d.reason == "backpressure"
        assert d.retry_after_s == pytest.approx(0.25)
        assert ctl.admit("c", LANE_INTERACTIVE).admitted
        # at critical: interactive sheds too
        depth["depth"] = 32
        assert not ctl.admit("c", LANE_INTERACTIVE).admitted
        assert global_metrics.get("admission_shed_backpressure") == 2
        assert global_metrics.get("admission_shed_bulk") == 1
        assert global_metrics.get("admission_shed_interactive") == 1

    def test_rate_limit_is_per_client(self):
        ctl, _, clk = _admission(admission_rate_qps=1.0,
                                 admission_burst=1.0)
        assert ctl.admit("hog").admitted
        d = ctl.admit("hog")
        assert not d.admitted and d.reason == "rate_limited"
        assert 0.0 < d.retry_after_s <= 1.0
        # a different client is untouched by the hog's bucket
        assert ctl.admit("polite").admitted
        # honoring the hint buys admission
        clk.t += d.retry_after_s
        assert ctl.admit("hog").admitted

    def test_disabled_admits_everything(self):
        ctl, depth, _ = _admission(admission_enabled=False)
        depth["depth"] = 10_000
        assert ctl.admit("c", LANE_BULK).admitted

    def test_client_buckets_lru_bounded(self):
        ctl, _, _ = _admission(admission_rate_qps=1.0,
                               admission_max_clients=2)
        for i in range(10):
            ctl.admit(f"client{i}")
        assert len(ctl._buckets) <= 2
        assert global_metrics.get("admission_clients") <= 2

    def test_zero_watermark_disables_that_tier(self):
        ctl, depth, _ = _admission(admission_queue_high_water=0,
                                   admission_queue_critical=0)
        depth["depth"] = 1_000_000
        assert ctl.admit("c", LANE_BULK).admitted
        assert ctl.admit("c", LANE_INTERACTIVE).admitted


# ---------------------------------------------------------------------------
# Weighted two-lane dequeue: bulk can never starve interactive
# ---------------------------------------------------------------------------

def _stopped_coalescer(**kw):
    """A Coalescer with its dispatchers joined: _form_batch_locked can
    then be driven deterministically against hand-stuffed queues."""
    c = Coalescer(lambda items: [None] * len(items), **kw)
    c.stop()
    return c


def _stuff(c, interactive=0, bulk=0, key=None):
    for i in range(interactive):
        w = _Waiter(f"i{i}", lane=0)
        w.key = key
        c._items.append(w)
    for i in range(bulk):
        w = _Waiter(f"b{i}", lane=1)
        w.key = key
        c._bulk.append(w)


class TestWeightedDequeue:
    def test_interactive_head_always_first(self):
        """THE no-starvation invariant: whenever any interactive item is
        queued, the formed batch leads with it — a round can never serve
        bulk while interactive waits, so bulk starving interactive is
        impossible by construction."""
        c = _stopped_coalescer(max_batch=4, bulk_share=0.25)
        _stuff(c, interactive=1, bulk=50)
        batch = c._form_batch_locked()
        assert batch[0].lane == 0

    def test_bulk_share_reserved_under_interactive_saturation(self):
        c = _stopped_coalescer(max_batch=8, bulk_share=0.25)
        _stuff(c, interactive=20, bulk=20)
        batch = c._form_batch_locked()
        assert len(batch) == 8
        lanes = [w.lane for w in batch]
        # interactive fills first, but 25% of slots went to bulk —
        # neither lane starves the other
        assert lanes.count(0) == 6 and lanes.count(1) == 2
        assert lanes[0] == 0

    def test_unused_reservation_returns_to_interactive(self):
        c = _stopped_coalescer(max_batch=8, bulk_share=0.25)
        _stuff(c, interactive=20, bulk=0)
        batch = c._form_batch_locked()
        assert [w.lane for w in batch] == [0] * 8

    def test_bulk_fills_batch_when_interactive_idle(self):
        c = _stopped_coalescer(max_batch=8, bulk_share=0.25)
        _stuff(c, interactive=0, bulk=20)
        batch = c._form_batch_locked()
        assert [w.lane for w in batch] == [1] * 8

    def test_backlog_is_live_and_discounts_one_batch(self):
        """The stall-proof backpressure input: ``backlog()`` reads the
        deques directly (the ``last_*_queue_depth`` gauge freezes while
        every dispatcher blocks inside a stalled batch_fn RPC), minus
        one batch's worth — a healthy linger window legitimately holds
        up to max_batch items the next round will take."""
        c = _stopped_coalescer(max_batch=4)
        assert c.backlog() == 0
        _stuff(c, interactive=3, bulk=1)
        assert c.backlog() == 0   # exactly one batch: healthy
        _stuff(c, interactive=5)
        assert c.backlog() == 5   # beyond a batch: genuine overload

    def test_group_key_homogeneity_holds_across_lanes(self):
        c = _stopped_coalescer(max_batch=8, bulk_share=0.5,
                               group_key=lambda item: item)
        _stuff(c, interactive=2, key="epoch1")
        w = _Waiter("bx", lane=1)
        w.key = "epoch2"   # different submit-time key: must not join
        c._bulk.append(w)
        batch = c._form_batch_locked()
        assert [x.query for x in batch] == ["i0", "i1"]
        assert len(c._bulk) == 1

    def test_live_two_lane_traffic_all_complete(self):
        """Liveness end to end: sustained interactive pressure does not
        starve bulk, and every submit (both lanes) completes."""
        seen = []
        lock = threading.Lock()

        def batch_fn(items):
            with lock:
                seen.append(list(items))
            return [f"r:{q}" for q in items]

        c = Coalescer(batch_fn, max_batch=4, linger_s=0.001,
                      pipeline=1, name="lane_live", bulk_share=0.25)
        try:
            with ThreadPoolExecutor(16) as pool:
                bulk = [pool.submit(c.submit, f"b{i}", 1)
                        for i in range(24)]
                inter = [pool.submit(c.submit, f"i{i}", 0)
                         for i in range(24)]
                assert sorted(f.result(timeout=10) for f in inter) == \
                    sorted(f"r:i{i}" for i in range(24))
                assert sorted(f.result(timeout=10) for f in bulk) == \
                    sorted(f"r:b{i}" for i in range(24))
        finally:
            c.stop()
        assert global_metrics.get("last_lane_live_bulk_depth", -1) >= 0


# ---------------------------------------------------------------------------
# Result cache unit
# ---------------------------------------------------------------------------

class TestResultCacheUnit:
    def test_hit_miss_and_generation_invalidation(self):
        rc = ResultCache(8)
        assert rc.get("q", (0, 0)) is None
        rc.put("q", (0, 0), {"a": 1.0})
        assert rc.get("q", (0, 0)) == {"a": 1.0}
        # ANY token component change kills the entry on touch
        assert rc.get("q", (0, 1)) is None
        assert len(rc) == 0
        assert global_metrics.get("cache_hits") == 1
        assert global_metrics.get("cache_misses") == 2
        assert global_metrics.get("cache_invalidations") == 1

    def test_lru_eviction_bounded(self):
        rc = ResultCache(2)
        for i in range(5):
            rc.put(f"q{i}", (0, 0), i)
        assert len(rc) == 2
        assert global_metrics.get("cache_evictions") == 3
        assert rc.get("q4", (0, 0)) == 4   # most recent survives


# ---------------------------------------------------------------------------
# Retry classifier: 429 honors Retry-After, never trips a breaker
# ---------------------------------------------------------------------------

def _http_429(retry_after="0.3"):
    return urllib.error.HTTPError(
        "http://x/leader/start", 429, "Too Many Requests",
        {"Retry-After": retry_after}, None)


class TestShedClassifier:
    def test_429_is_retryable_with_retry_after_floor(self):
        e = RpcStatusError("http://x", 429, retry_after_s=0.4)
        assert is_retryable(e)
        assert retry_after_of(e) == pytest.approx(0.4)
        assert is_retryable(_http_429())
        assert retry_after_of(_http_429()) == pytest.approx(0.3)
        # unparseable (HTTP-date) hint: still a shed, hint absent
        assert retry_after_of(_http_429("Fri, 01 Aug 2026")) == 0.0
        assert retry_after_of(RpcStatusError("http://x", 503)) is None

    def test_retry_policy_never_retries_before_retry_after(self):
        sleeps = []
        calls = []

        def fn():
            calls.append(1)
            if len(calls) == 1:
                raise RpcStatusError("http://x", 429, retry_after_s=0.7)
            return "ok"

        p = RetryPolicy(max_attempts=3, base_delay_s=0.001, jitter=0.0,
                        name="shed_test", sleep=sleeps.append)
        assert p.call(fn) == "ok"
        # the back-off slept AT LEAST the Retry-After hint, not the
        # (tiny) exponential base delay
        assert sleeps == [pytest.approx(0.7)]
        assert global_metrics.get("shed_test_shed_waits") == 1

    def test_deadline_too_small_propagates_shed_immediately(self):
        """Non-retryable-before-Retry-After: when the budget cannot
        cover the wait, the shed propagates NOW — never an early
        re-attempt that hammers the saturated leader."""
        sleeps = []

        def fn():
            raise RpcStatusError("http://x", 429, retry_after_s=5.0)

        p = RetryPolicy(max_attempts=3, base_delay_s=0.001, jitter=0.0,
                        deadline_s=0.5, sleep=sleeps.append)
        with pytest.raises(RpcStatusError):
            p.call(fn)
        assert sleeps == []   # zero early re-attempts

    def test_shed_never_trips_worker_breaker(self):
        """A 429 is healthy overload behavior: a breaker that opened on
        sheds would mark a live node dead and amplify the overload."""
        e = RpcStatusError("http://x", 429, retry_after_s=0.1)
        assert not is_worker_fault(e)
        assert not is_worker_fault(_http_429())
        res = ClusterResilience(Config(rpc_max_attempts=1,
                                       breaker_failure_threshold=1))
        for _ in range(5):
            with pytest.raises(RpcStatusError):
                res.worker_call("http://w1", lambda: (_ for _ in ()).throw(
                    RpcStatusError("http://w1", 429, retry_after_s=0.1)))
        assert res.board.breaker("http://w1").state == "closed"


# ---------------------------------------------------------------------------
# Front door over real HTTP
# ---------------------------------------------------------------------------

class TestFrontDoorHTTP:
    def test_rate_limit_shed_429_per_client(self, core, tmp_path):
        # rate 0.2 qps: hog's bucket refills a token only every 5s, so
        # the back-to-back pair below sheds deterministically even when
        # the suite runs slow (at 1 qps a search that happens to take
        # >1s — e.g. paying an XLA compile — would refill the bucket
        # between the two requests and the second would be admitted)
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2,
                            admission_rate_qps=0.2, admission_burst=1.0)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            # warm the scatter path on a different client's budget
            assert _search(leader, "common",
                           headers={"X-Client-Id": "warm"}) is not None
            assert _search(leader, "common",
                           headers={"X-Client-Id": "hog"}) is not None
            with pytest.raises(urllib.error.HTTPError) as exc:
                _search(leader, "common", headers={"X-Client-Id": "hog"})
            ra, reason, body = _shed_info(exc.value)
            assert reason == "rate_limited"
            # header is RFC 9110 delta-seconds: the precise float hint
            # lives in the body, the header rounds UP to whole seconds
            assert 0.0 < ra <= 5.0 and ra == int(ra)
            assert body["error"] == "overloaded"
            assert body["reason"] == "rate_limited"
            assert 0.0 < body["retry_after_s"] <= ra
            # a polite client with its own id is admitted concurrently
            assert _search(leader, "common",
                           headers={"X-Client-Id": "polite"}) is not None
            assert global_metrics.get("admission_shed_rate_limited") >= 1
        finally:
            _stop_all(nodes)

    def test_backpressure_sheds_bulk_then_interactive(self, core,
                                                      tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2,
                            admission_queue_high_water=50,
                            admission_queue_critical=500)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            # high water: the BULK lane sheds...
            global_metrics.set_gauge("last_scatter_queue_depth", 50)
            with pytest.raises(urllib.error.HTTPError) as exc:
                _search(leader, "common", headers={"X-Priority": "bulk"})
            _, reason, _ = _shed_info(exc.value)
            assert reason == "backpressure"
            # ... uploads default to the bulk lane and shed too,
            # BEFORE their body is read
            with pytest.raises(urllib.error.HTTPError) as exc:
                _upload_docs(leader)
            assert exc.value.code == 429
            # ... an upload explicitly marked interactive survives
            global_metrics.set_gauge("last_scatter_queue_depth", 50)
            assert json.loads(http_post(
                leader.url + "/leader/upload-batch",
                json.dumps([{"name": "vip.txt", "text": "vip common"}]
                           ).encode(),
                headers={"X-Priority": "interactive"}))
            # ... and interactive searches are admitted (the dispatch
            # resets the gauge, so re-arm before asserting)
            global_metrics.set_gauge("last_scatter_queue_depth", 50)
            assert _search(leader, "common") is not None
            # critical: interactive sheds as well
            global_metrics.set_gauge("last_scatter_queue_depth", 500)
            with pytest.raises(urllib.error.HTTPError) as exc:
                _search(leader, "common")
            _, reason, _ = _shed_info(exc.value)
            assert reason == "backpressure"
            # recovery: depth back down, everyone admitted again
            global_metrics.set_gauge("last_scatter_queue_depth", 0)
            assert _search(leader, "common",
                           headers={"X-Priority": "bulk"}) is not None
        finally:
            _stop_all(nodes)

    def test_stalled_dispatchers_still_shed(self, core, tmp_path):
        """The gauge alone freezes while every dispatcher thread is
        blocked inside a stalled scatter RPC — the live backlog read
        must keep the front door shedding through the stall instead of
        queueing every request behind it."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2,
                            scatter_batch=4,
                            admission_queue_high_water=2,
                            admission_queue_critical=4)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            assert _search(leader, "common") is not None
            # simulate the stall deterministically: batch formation
            # needs the coalescer lock, so holding it wedges every
            # dispatcher round exactly like a hung batch_fn would;
            # the gauge stays frozen at its healthy last value while
            # the queue piles up live
            sb = leader.scatter_batcher
            with sb._lock:
                global_metrics.set_gauge("last_scatter_queue_depth", 0)
                for i in range(12):
                    sb._items.append(_Waiter(f"stall{i}", lane=0))
                assert sb.backlog() > 4   # live signal sees the pile
                # admission runs BEFORE submit: the shed path never
                # touches the coalescer, so this cannot deadlock
                with pytest.raises(urllib.error.HTTPError) as exc:
                    _search(leader, "common token7")   # not yet cached
                _, reason, _ = _shed_info(exc.value)
                assert reason == "backpressure"
                # restore: pull the fake waiters back out before the
                # dispatchers wake and try to serve them
                sb._items.clear()
            assert _search(leader, "common") is not None
        finally:
            _stop_all(nodes)

    def test_download_endpoint_is_admission_controlled(self, core,
                                                       tmp_path):
        """Every /leader/* endpoint sits behind the front door —
        including the GET checkpoint-download path (real file I/O per
        request, bulk lane: first to shed)."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2,
                            admission_queue_high_water=10,
                            admission_queue_critical=1000)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            global_metrics.set_gauge("last_scatter_queue_depth", 10)
            with pytest.raises(urllib.error.HTTPError) as exc:
                http_get(leader.url + "/leader/download?path=ad0.txt")
            assert exc.value.code == 429
            assert exc.value.headers.get("X-Shed-Reason") == "backpressure"
            global_metrics.set_gauge("last_scatter_queue_depth", 0)
        finally:
            _stop_all(nodes)

    def test_shed_drains_body_so_client_sees_429(self, core, tmp_path):
        """A shed POST with a large body must still deliver the 429:
        closing with unread data in the receive queue sends RST, the
        client would see ECONNRESET (classified transient — retried
        with no Retry-After floor). The shed path drains up to 1 MB
        before closing so the reply survives."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2,
                            admission_queue_high_water=5)
        try:
            leader = nodes[0]
            global_metrics.set_gauge("last_scatter_queue_depth", 5)
            big = [{"name": "big.txt", "text": "word " * 60_000}]  # ~300KB
            with pytest.raises(urllib.error.HTTPError) as exc:
                http_post(leader.url + "/leader/upload-batch",
                          json.dumps(big).encode())
            ra, reason, body = _shed_info(exc.value)
            assert reason == "backpressure"
            assert body["error"] == "overloaded"
            global_metrics.set_gauge("last_scatter_queue_depth", 0)
        finally:
            _stop_all(nodes)

    def test_unbounded_results_disables_cache(self, core, tmp_path):
        """Parity (unbounded-results) configs skip top-k truncation, so
        a cached value would be a full-corpus score dict — the entry
        bound is no memory bound. The cache must be off there, like the
        scatter batcher already is."""
        node = _node(core, tmp_path, 0, unbounded_results=True,
                     result_cache_entries=64)
        try:
            assert node.result_cache is None
            assert node.scatter_batcher is None
        finally:
            node.stop()

    def test_health_and_metrics_never_shed(self, core, tmp_path):
        """The reserved observability lane: with the cluster at
        CRITICAL backpressure (every search lane shedding), operators
        can still see it."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2,
                            admission_queue_high_water=10,
                            admission_queue_critical=20)
        try:
            leader = nodes[0]
            global_metrics.set_gauge("last_scatter_queue_depth", 1000)
            with pytest.raises(urllib.error.HTTPError):
                _search(leader, "common")
            health = json.loads(http_get(leader.url + "/api/health"))
            assert health["ok"] is True
            assert health["role"] == "leader"
            assert health["admission"]["queue_critical"] == 20
            snap = json.loads(http_get(leader.url + "/api/metrics"))
            assert snap.get("admission_shed_total", 0) >= 1
            # a worker's health lane answers too
            wh = json.loads(http_get(nodes[1].url + "/api/health"))
            assert wh["ok"] is True and wh["role"] == "worker"
        finally:
            _stop_all(nodes)

    def test_metrics_respond_during_saturated_bulk_flood(self, core,
                                                         tmp_path):
        """The satellite pin: a saturated bulk flood (every slot bulk,
        queue nonempty the whole time) cannot queue ahead of
        /api/metrics or /api/health — each observability request gets
        its own handler thread and never enters admission or the
        coalescer."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2,
                            scatter_linger_ms=30.0,
                            scatter_linger_min_ms=30.0,
                            scatter_linger_max_ms=30.0)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            stop = threading.Event()
            errors = []

            def flood():
                while not stop.is_set():
                    try:
                        _search(leader, "common",
                                headers={"X-Priority": "bulk"})
                    except urllib.error.HTTPError as e:
                        if e.code != 429:
                            errors.append(e)
                    except Exception as e:
                        errors.append(e)

            threads = [threading.Thread(target=flood, daemon=True)
                       for _ in range(12)]
            for t in threads:
                t.start()
            try:
                time.sleep(0.3)   # let the flood saturate the coalescer
                for _ in range(5):
                    t0 = time.monotonic()
                    snap = json.loads(http_get(
                        leader.url + "/api/metrics", timeout=5.0))
                    health = json.loads(http_get(
                        leader.url + "/api/health", timeout=5.0))
                    took = time.monotonic() - t0
                    assert took < 2.0, \
                        f"observability starved: {took:.2f}s under flood"
                    assert health["ok"] is True
                    assert snap["dispatch_queries"] >= 1
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=10)
            assert not errors, errors[:3]
        finally:
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# Result cache correctness against the oracle
# ---------------------------------------------------------------------------

class TestResultCacheCluster:
    def test_hit_serves_exact_result_and_counts(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            _settle_signature(leader)   # replica legs confirm async
            want = _oracle(tmp_path)
            first = _search(leader, "common")
            _assert_parity(first, want["common"], "first")
            h0 = global_metrics.get("cache_hits")
            again = _search(leader, "common")
            assert again == first
            assert global_metrics.get("cache_hits") == h0 + 1
            # the hit did not re-enter the scatter path: health gauges
            # still describe the LAST real fan-out
            _assert_parity(again, want["common"], "cached")
        finally:
            _stop_all(nodes)

    def test_upsert_invalidates_cached_result(self, core, tmp_path):
        """Miss-after-commit, proven by parity: after an upsert changes
        the df signature, the cached entry must die — serving it would
        return scores from a corpus that no longer exists."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            _settle_signature(leader)
            before = _search(leader, "common")
            _search(leader, "common")   # ensure it is cached
            tok0 = leader.df_signature()
            docs2 = dict(DOCS, **{"ad0.txt": "common common pelican"})
            _upload_docs(leader, {"ad0.txt": docs2["ad0.txt"]})
            assert leader.df_signature() != tok0
            want2 = _oracle(tmp_path, docs=docs2, tag="oracle2")
            _parity_settles(leader, "common", want2["common"],
                            "post-upsert")
            assert _search(leader, "common") != before
            assert global_metrics.get("cache_invalidations") >= 1
        finally:
            _stop_all(nodes)

    def test_worker_delete_advances_local_signature(self, core,
                                                    tmp_path):
        """Direct worker-side mutations keep that node's own signature
        honest (dual-role and single-node deployments serve both
        families of endpoints from one process)."""
        nodes = _mk_cluster(core, tmp_path, n=2, replication_factor=1)
        try:
            leader = nodes[0]
            worker = nodes[1]
            _upload_docs(leader)
            tok0 = worker.df_signature()
            name = leader.placement.names_on(worker.url)[0]
            resp = json.loads(http_post(
                worker.url + "/worker/delete",
                json.dumps({"names": [name]}).encode()))
            assert resp["deleted"] == 1
            assert worker.df_signature() != tok0
        finally:
            _stop_all(nodes)

    def test_migration_flip_invalidates(self, core, tmp_path):
        """The PR-6 surface: a migration flip changes which shard
        scores the moved docs (per-shard df shifts with ownership) —
        cached results stamped before the flip must miss after it."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=1)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            _search(leader, "common")
            _search(leader, "common")   # cached
            tok0 = leader.df_signature()
            source = nodes[1].url
            names = leader.placement.names_on(source)[:3]
            assert names
            out = leader.rebalancer.migrate(source, names)
            assert out["moved"] == len(names)
            assert leader.df_signature() != tok0
            # results after the flip are complete (all 12 docs for the
            # all-docs query), freshly computed
            inv0 = global_metrics.get("cache_invalidations")
            got = _search(leader, "common")
            assert set(got) == set(DOCS)
            assert global_metrics.get("cache_invalidations") > inv0 - 1
        finally:
            _stop_all(nodes)

    def test_concurrent_write_workload_exact_parity(self, core,
                                                    tmp_path):
        """The satellite gate: under continuous cached read traffic, a
        sequence of df-changing commits each becomes visible EXACTLY —
        after every commit settles, the next read equals the fresh
        single-node oracle, never a stale cached score. The hammer
        threads race put() against bump_result_generation() the whole
        run; the dispatch-time token capture makes a late put of an
        old-token entry harmless (it can never be read under the new
        token)."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            versions = [f"common pelican v{i} " + "drift " * i
                        for i in range(4)]
            oracles = []
            for i, text in enumerate(versions):
                docs_i = dict(DOCS, **{"ad0.txt": text})
                oracles.append(_oracle(tmp_path, docs=docs_i,
                                       tag=f"ow{i}"))
            stop = threading.Event()
            hammer_errors = []

            def hammer():
                while not stop.is_set():
                    try:
                        _search(leader, random.choice(QUERIES))
                    except Exception as e:
                        hammer_errors.append(e)
                        return

            threads = [threading.Thread(target=hammer, daemon=True)
                       for _ in range(4)]
            for t in threads:
                t.start()
            try:
                for i, text in enumerate(versions):
                    _upload_docs(leader, {"ad0.txt": text})
                    # both replica legs land within the window; once
                    # they have, EVERY subsequent read must be fresh
                    _parity_settles(leader, "common",
                                    oracles[i]["common"], f"v{i}")
                    _settle_signature(leader)
                    for q in QUERIES:   # full parity at this version
                        _assert_parity(_search(leader, q),
                                       oracles[i][q], f"v{i}:{q}")
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=10)
            assert not hammer_errors, hammer_errors[:3]
            # the cache was genuinely exercised AND genuinely killed
            assert global_metrics.get("cache_hits") > 0
            assert global_metrics.get("cache_invalidations") > 0
        finally:
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# Chaos (slow): 2x-overload zipfian closed loop + mid-run worker kill -9
# ---------------------------------------------------------------------------

def _zipf_queries(pool: list[str], n: int, s: float = 1.1,
                  seed: int = 7) -> list[str]:
    rng = random.Random(seed)
    weights = [1.0 / (i + 1) ** s for i in range(len(pool))]
    return rng.choices(pool, weights=weights, k=n)


@pytest.mark.slow
class TestChaosOverload:
    @pytest.mark.timeout(300)
    def test_2x_overload_sheds_bounded_p99_exact_parity(self, tmp_path):
        """``make chaos-overload``: a closed-loop zipfian workload at
        ~2x the capacity the 1x phase measures, with a real mid-run
        worker ``kill -9``. Acceptance: the leader sheds explicitly
        (shed count rises past the 1x phase), the p99 latency of
        ADMITTED interactive queries stays bounded, and every admitted
        result stays in exact merge parity with the single-node oracle
        — through the kill and through a cache-invalidating upsert
        mid-run."""
        import os
        import signal
        import socket
        import subprocess
        import sys

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        env = os.environ.copy()
        env["TFIDF_JAX_PLATFORM"] = "cpu"
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.update({
            "TFIDF_REPLICATION_FACTOR": "2",
            "TFIDF_TOP_K": "64",
            "TFIDF_SESSION_TIMEOUT_S": "1.0",
            "TFIDF_HEARTBEAT_INTERVAL_S": "0.2",
            "TFIDF_RECONCILE_SWEEP_INTERVAL_S": "0.5",
            "TFIDF_MIN_DOC_CAPACITY": "64",
            "TFIDF_MIN_NNZ_CAPACITY": "4096",
            "TFIDF_MIN_VOCAB_CAPACITY": "1024",
            "TFIDF_QUERY_BATCH": "4",
            "TFIDF_MAX_QUERY_TERMS": "8",
            # overload mechanics on laptop-scale hardware: a SMALL
            # scatter batch leaves queued items behind each dispatch
            # round (the depth gauge backpressure keys on), LOW
            # watermarks so the 2x phase genuinely sheds, rate limiting
            # off (backpressure is the subject), cache on (zipfian
            # repeats are its best case — the head of the distribution
            # answers leader-side while the tail keeps the workers hot)
            "TFIDF_SCATTER_BATCH": "2",
            "TFIDF_SCATTER_PIPELINE": "1",
            "TFIDF_ADMISSION_QUEUE_HIGH_WATER": "1",
            "TFIDF_ADMISSION_QUEUE_CRITICAL": "3",
            "TFIDF_RESULT_CACHE_ENTRIES": "256",
        })
        coord_port = free_port()
        procs = {}

        def spawn(tag, args):
            p = subprocess.Popen(
                [sys.executable, "-m", "tfidf_tpu", *args],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
            procs[tag] = p
            return p

        def wait_pred(pred, timeout=60.0, interval=0.2):
            deadline = time.monotonic() + timeout
            last = None
            while time.monotonic() < deadline:
                try:
                    if pred():
                        return True
                except Exception as e:
                    last = e
                time.sleep(interval)
            raise AssertionError(f"timed out; last={last!r}")

        def node_args(i, port):
            return ["serve", "--port", str(port), "--host", "127.0.0.1",
                    "--coordinator-address", f"127.0.0.1:{coord_port}",
                    "--documents-path", str(tmp_path / f"ov{i}" / "docs"),
                    "--index-path", str(tmp_path / f"ov{i}" / "index")]

        try:
            spawn("coord", ["coordinator", "--listen",
                            f"127.0.0.1:{coord_port}"])
            wait_pred(lambda: socket.create_connection(
                ("127.0.0.1", coord_port), timeout=1.0).close() or True)
            ports = [free_port() for _ in range(3)]
            urls = [f"http://127.0.0.1:{p}" for p in ports]
            for i, p in enumerate(ports):
                spawn(f"n{i}", node_args(i, p))
                wait_pred(lambda u=urls[i]: http_get(
                    u + "/api/status", timeout=5.0), timeout=120)
            leader = urls[0]
            wait_pred(lambda: len(json.loads(http_get(
                leader + "/api/services"))) == 2)

            batch = [{"name": n, "text": t} for n, t in DOCS.items()]
            http_post(leader + "/leader/upload-batch",
                      json.dumps(batch).encode())
            # a WIDE distinct-query pool: the zipf head hits the
            # result cache, the long tail keeps real scatter traffic
            # flowing (with 4 distinct queries the cache would absorb
            # the whole 2x phase and nothing would ever shed)
            qpool = QUERIES + [f"token{i} word{j}" for i in range(12)
                               for j in range(3)] + \
                [f"extra{k} common" for k in range(5)]
            want = _oracle(tmp_path, queries=qpool, top_k=64)

            def parity_now():
                for q in QUERIES:
                    got = json.loads(http_post(
                        leader + "/leader/start",
                        json.dumps({"query": q}).encode()))
                    _assert_parity(got, want[q], ctx=q)
                return True
            wait_pred(parity_now, timeout=120, interval=1.0)

            zipf = _zipf_queries(qpool, 4000)
            lat_lock = threading.Lock()
            nonce = [0]

            def run_phase(n_clients: int, seconds: float,
                          mid_phase=None) -> dict:
                """Closed loop: each client posts, measures, repeats.
                The zipf HEAD repeats (the cache's best case); a 40%
                tail gets a unique OOV nonce appended — score-neutral
                (parity still checked against the base query's oracle)
                but cache-busting, modeling the effectively-unique long
                tail real user populations produce. Returns
                admitted-interactive latencies + shed count."""
                lats: list[float] = []
                sheds = [0]
                errors: list[BaseException] = []
                stop_at = time.monotonic() + seconds
                idx = [0]

                def client(cid: int):
                    while time.monotonic() < stop_at:
                        with lat_lock:
                            base = zipf[idx[0] % len(zipf)]
                            idx[0] += 1
                            q = base
                            if idx[0] % 5 < 3:   # the unique tail
                                nonce[0] += 1
                                q = f"{base} zzuniq{nonce[0]}"
                        t0 = time.monotonic()
                        try:
                            got = json.loads(http_post(
                                leader + "/leader/start",
                                json.dumps({"query": q}).encode(),
                                headers={"X-Client-Id": f"c{cid}"},
                                timeout=30.0))
                            dt = time.monotonic() - t0
                            with lat_lock:
                                lats.append(dt)
                            # admitted => exact: every response
                            # parity-checked against the oracle
                            _assert_parity(got, want[base], ctx=q)
                        except urllib.error.HTTPError as e:
                            if e.code == 429:
                                ra = float(
                                    e.headers.get("Retry-After", 0.05))
                                with lat_lock:
                                    sheds[0] += 1
                                time.sleep(min(ra, 0.5))
                            else:
                                errors.append(e)
                                return
                        except Exception as e:
                            errors.append(e)
                            return

                threads = [threading.Thread(target=client, args=(i,),
                                            daemon=True)
                           for i in range(n_clients)]
                for t in threads:
                    t.start()
                if mid_phase is not None:
                    time.sleep(seconds / 2)
                    mid_phase()
                for t in threads:
                    t.join(timeout=seconds + 60)
                assert not errors, errors[:3]
                lats.sort()
                return {"n": len(lats), "sheds": sheds[0],
                        "p50": lats[len(lats) // 2] if lats else 0.0,
                        "p99": lats[int(len(lats) * 0.99)]
                        if lats else 0.0}

            one_x = run_phase(4, 8.0)
            assert one_x["n"] > 0

            def kill_and_upsert():
                # the mid-run chaos: SIGKILL a worker AND land a
                # cache-invalidating commit while 2x load runs. The
                # upsert must model the polite client: uploads default
                # to the bulk lane, which is (by design) exactly what
                # the saturated 2x phase sheds first — so mark it
                # interactive and honor Retry-After until admitted
                victim = procs.pop("n2")
                os.kill(victim.pid, signal.SIGKILL)
                victim.wait(timeout=10)
                body = json.dumps([{"name": "ad0.txt",
                                    "text": DOCS["ad0.txt"]}]).encode()
                for _ in range(40):
                    try:
                        http_post(leader + "/leader/upload-batch", body,
                                  headers={"X-Priority": "interactive"})
                        return
                    except urllib.error.HTTPError as e:
                        if e.code != 429:
                            raise
                        time.sleep(min(float(
                            e.headers.get("Retry-After", 0.1)), 0.5))
                raise AssertionError("mid-run upsert never admitted")

            two_x = run_phase(12, 16.0, mid_phase=kill_and_upsert)
            assert two_x["n"] > 0

            # shed rate RISES under overload (the 1x phase may shed a
            # little during warm transients; 2x must shed more)
            assert two_x["sheds"] > one_x["sheds"], (one_x, two_x)
            # p99 of ADMITTED interactive queries stays bounded: within
            # 4x of the 1x p99 (CI-generous; the acceptance bar is 2x
            # on quiet hardware) and an absolute
            # ceiling that unbounded queueing would blow through
            assert two_x["p99"] <= max(4.0 * one_x["p99"], 2.0), \
                (one_x, two_x)
            # the cluster still answers exactly after the storm
            wait_pred(parity_now, timeout=60, interval=1.0)
            snap = json.loads(http_get(leader + "/api/metrics"))
            assert snap.get("admission_shed_total", 0) >= two_x["sheds"]
        finally:
            for p in procs.values():
                try:
                    p.kill()
                except Exception:
                    pass
            for p in procs.values():
                try:
                    p.wait(timeout=10)
                except Exception:
                    pass
