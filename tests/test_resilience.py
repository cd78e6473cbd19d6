"""Cluster resilience layer (ISSUE 1): retry policy, per-worker circuit
breakers, reconciliation sweep, and honest failure propagation.

Fast deterministic tests run in tier-1; the probabilistic chaos jobs are
marked ``slow`` (``pytest tests/test_resilience.py -m slow``). The chaos
acceptance bar: with faults armed on worker RPCs, heartbeats, and
reconciles, the leader (a) never merges a failed worker batch as a
successful empty result, (b) converges the reconciliation sweep so no
document is double-counted after rejoin, and (c) drives breakers through
open/half-open/closed with retry counts bounded by injector fire
counters.
"""

import json
import socket
import urllib.error
import urllib.request

import jax
import pytest

from tfidf_tpu.cluster.coordination import CoordinationCore, LocalCoordination
from tfidf_tpu.cluster.node import SearchNode, http_get, http_post
from tfidf_tpu.cluster.resilience import (BreakerBoard, CircuitBreaker,
                                          CircuitOpenError, RetryPolicy,
                                          RpcStatusError, is_retryable,
                                          is_worker_fault)
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.faults import (KNOWN_FAULT_POINTS, FaultInjected,
                                    FaultInjector, global_injector)
from tfidf_tpu.utils.metrics import global_metrics

from tests.test_cluster import wait_until


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------

class TestRetryPolicy:
    def _policy(self, **kw):
        self.sleeps = []
        kw.setdefault("jitter", 0.0)
        return RetryPolicy(sleep=self.sleeps.append, **kw)

    def test_retries_transient_then_succeeds(self):
        p = self._policy(max_attempts=3, base_delay_s=0.1)
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            if calls["n"] < 3:
                raise ConnectionResetError("blip")
            return "ok"

        assert p.call(fn) == "ok"
        assert calls["n"] == 3
        assert self.sleeps == [0.1, 0.2]   # exponential, no jitter

    def test_non_retryable_raises_immediately(self):
        p = self._policy(max_attempts=5)
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            raise ValueError("app bug")

        with pytest.raises(ValueError):
            p.call(fn)
        assert calls["n"] == 1 and self.sleeps == []

    def test_attempts_bounded_and_last_error_raised(self):
        p = self._policy(max_attempts=3)
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            raise ConnectionRefusedError(f"dead {calls['n']}")

        with pytest.raises(ConnectionRefusedError, match="dead 3"):
            p.call(fn)
        assert calls["n"] == 3 and len(self.sleeps) == 2

    def test_deadline_stops_early(self):
        now = [0.0]
        p = RetryPolicy(max_attempts=10, base_delay_s=1.0, jitter=0.0,
                        deadline_s=2.5, sleep=lambda s: None,
                        clock=lambda: now[0])
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            now[0] += 1.0   # each attempt takes 1s of fake time
            raise ConnectionResetError("slow")

        with pytest.raises(ConnectionResetError):
            p.call(fn)
        # attempt 1 (t=1) retries (1+1.0 <= 2.5), attempt 2 (t=2) would
        # need t=2 + 2.0 > 2.5 -> raises instead of sleeping
        assert calls["n"] == 2

    def test_backoff_caps_at_max_delay(self):
        p = RetryPolicy(base_delay_s=0.5, max_delay_s=1.0, jitter=0.0)
        assert p.backoff_delay(1) == 0.5
        assert p.backoff_delay(2) == 1.0
        assert p.backoff_delay(5) == 1.0

    def test_jitter_stays_in_band(self):
        p = RetryPolicy(base_delay_s=1.0, max_delay_s=8.0, jitter=0.25)
        for attempt in (1, 2, 3):
            base = min(8.0, 2.0 ** (attempt - 1))
            for _ in range(50):
                d = p.backoff_delay(attempt)
                assert base * 0.75 <= d <= base * 1.25

    def test_backoff_fault_point_fires(self):
        global_injector.arm("resilience.backoff", action="delay",
                            delay_s=0.0)
        p = self._policy(max_attempts=2)
        with pytest.raises(ConnectionResetError):
            p.call(lambda: (_ for _ in ()).throw(ConnectionResetError()))
        assert global_injector.fired.get("resilience.backoff") == 1


class TestClassifiers:
    def test_retryable(self):
        assert is_retryable(ConnectionResetError())
        # gateway-transient statuses retry; a deterministic 500 (e.g. a
        # worker engine crash on this batch) fails fast — retrying would
        # multiply the sick worker's engine load per scatter
        assert is_retryable(RpcStatusError("u", 503))
        assert not is_retryable(RpcStatusError("u", 500))
        assert not is_retryable(RpcStatusError("u", 415))
        assert is_retryable(FaultInjected("chaos"))
        assert not is_retryable(socket.timeout("slow"))
        assert not is_retryable(ValueError("app"))
        assert is_retryable(urllib.error.HTTPError("u", 503, "x", {}, None))
        assert not is_retryable(urllib.error.HTTPError("u", 500, "x", {},
                                                       None))
        assert not is_retryable(urllib.error.HTTPError("u", 404, "x", {},
                                                       None))

    def test_worker_fault(self):
        # 4xx = healthy worker refusing an application request
        assert not is_worker_fault(RpcStatusError("u", 415))
        assert not is_worker_fault(urllib.error.HTTPError("u", 404, "x",
                                                          {}, None))
        # timeouts and 5xx DO indict the worker (unlike retryability)
        assert is_worker_fault(socket.timeout("hung"))
        assert is_worker_fault(RpcStatusError("u", 500))
        assert is_worker_fault(ConnectionRefusedError())


# ---------------------------------------------------------------------------
# CircuitBreaker
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def _breaker(self, threshold=3, reset=5.0):
        self.now = [0.0]
        return CircuitBreaker(failure_threshold=threshold, reset_s=reset,
                              clock=lambda: self.now[0], name="w")

    def test_full_lifecycle(self):
        b = self._breaker()
        for _ in range(2):          # below threshold: stays closed
            b.acquire()
            b.record_failure()
        assert b.state == "closed"
        b.acquire()
        b.record_failure()          # third consecutive: trips
        assert b.state == "open"
        with pytest.raises(CircuitOpenError):
            b.acquire()
        self.now[0] = 5.1           # past reset: half-open probe
        assert b.state == "half_open"
        b.acquire()                 # the probe
        with pytest.raises(CircuitOpenError):
            b.acquire()             # only ONE probe at a time
        b.record_success()
        assert b.state == "closed"
        b.acquire()                 # healthy again
        assert b.transitions == ["closed", "open", "half_open", "closed"]

    def test_probe_failure_reopens(self):
        b = self._breaker(threshold=1, reset=2.0)
        b.acquire()
        b.record_failure()
        assert b.state == "open"
        self.now[0] = 2.5
        b.acquire()                 # half-open probe
        b.record_failure()
        assert b.state == "open"    # re-opened, reset timer restarted
        with pytest.raises(CircuitOpenError):
            b.acquire()
        self.now[0] = 4.0           # 2.5 + 2.0 > 4.0: still open
        with pytest.raises(CircuitOpenError):
            b.acquire()
        self.now[0] = 4.6
        b.acquire()
        b.record_success()
        assert b.state == "closed"

    def test_success_resets_consecutive_count(self):
        b = self._breaker(threshold=2)
        b.record_failure()
        b.record_success()
        b.record_failure()          # 1 consecutive, not 2
        assert b.state == "closed"

    def test_is_open_is_non_consuming(self):
        b = self._breaker(threshold=1, reset=1.0)
        b.record_failure()
        self.now[0] = 1.5
        assert not b.is_open()      # would admit a probe...
        assert not b.is_open()      # ...and did not consume it
        b.acquire()
        assert b.is_open()          # probe slot taken now

    def test_board_prunes_departed_workers(self):
        board = BreakerBoard(failure_threshold=1, reset_s=60.0)
        board.breaker("http://a:1").record_failure()
        board.breaker("http://b:2")
        assert board.is_open("http://a:1")
        assert board.open_count() == 1
        board.prune({"http://b:2"})
        # the rejoining worker starts with a clean breaker
        assert not board.is_open("http://a:1")
        assert board.snapshot() == {"http://b:2": "closed"}

    def test_trip_fault_point_counts_but_never_raises(self):
        global_injector.arm("resilience.breaker_trip", action="raise")
        b = self._breaker(threshold=1)
        b.record_failure()          # must not propagate FaultInjected
        assert b.state == "open"
        assert global_injector.fired.get("resilience.breaker_trip") == 1


# ---------------------------------------------------------------------------
# Fault-point tooling (satellite: chaos configs can't go stale)
# ---------------------------------------------------------------------------

class TestFaultTooling:
    def test_wildcard_rules_match_prefix(self):
        inj = FaultInjector()
        inj.arm("coord.heartbeat.*", action="raise")
        with pytest.raises(FaultInjected):
            inj.check("coord.heartbeat.7")
        inj.check("coord.other")   # no match, no fire
        assert inj.fired == {"coord.heartbeat.*": 1}

    # The PR 1 grep-based anti-stale test lived here; it is superseded
    # by the graftcheck registry-drift pass (tools/graftcheck), which
    # checks BOTH directions — every call site registered AND every
    # registry entry backed by a call site — and also sees the
    # CircuitBreaker._observe indirection the grep missed. Enforced by
    # tests/test_graftcheck.py::TestRealTree::test_registry_drift_fault_points
    # and the CI graftcheck job.

    def test_faults_list_cli(self, capsys):
        from tfidf_tpu.cli import main

        assert main(["faults", "list"]) == 0
        out = capsys.readouterr().out
        for name in KNOWN_FAULT_POINTS:
            assert name in out


# ---------------------------------------------------------------------------
# Cluster fixtures
# ---------------------------------------------------------------------------

@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


DOCS = {f"rz{i}.txt": f"common token{i} word{i % 3}" for i in range(12)}

_RESILIENCE_CFG = dict(
    top_k=32, min_doc_capacity=64, min_nnz_capacity=1 << 12,
    min_vocab_capacity=1 << 10, query_batch=8, max_query_terms=8,
    rpc_max_attempts=1,           # deterministic: no hidden retries
    breaker_failure_threshold=2, breaker_reset_s=0.4,
    reconcile_sweep_interval_s=0.2,
    # single-copy placement: this suite pins the PRE-replication
    # degraded/recovery semantics (R-way failover has its own suite,
    # tests/test_replication.py)
    replication_factor=1,
    # no result cache: these tests re-issue identical queries around
    # armed faults and count the resulting scatter RPCs/breaker fires
    # — a cache hit would (correctly) skip the fan-out and mask them
    # (the cache has its own suite, tests/test_admission.py)
    result_cache_entries=0)


def _node(core, tmp_path, i, port=0, **kw):
    cfg_kw = dict(_RESILIENCE_CFG)
    cfg_kw.update(kw)
    cfg = Config(
        documents_path=str(tmp_path / f"rz{i}" / "documents"),
        index_path=str(tmp_path / f"rz{i}" / "index"),
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
    http_post(leader.url + "/leader/upload-batch",
              json.dumps(batch).encode())


def _search(leader, q):
    return json.loads(http_post(
        leader.url + "/leader/start", json.dumps({"query": q}).encode()))


# ---------------------------------------------------------------------------
# Honest failure propagation
# ---------------------------------------------------------------------------

class TestHonestFailurePropagation:
    def test_process_batch_failure_is_non_2xx(self, core, tmp_path):
        """ADVICE r5: an engine failure must surface as a 5xx, never as
        an HTTP 200 all-empty reply the leader merges as a valid
        zero-hit result."""
        nodes = _mk_cluster(core, tmp_path, n=2)
        try:
            leader, worker = nodes
            _upload_docs(leader)
            assert _search(leader, "common")   # sanity: healthy path

            def broken(queries, k=None, unbounded=False):
                raise ValueError("engine exploded")

            # break BOTH batch entrypoints: the wire fast path serves
            # from search_batch_arrays, the fallback from search_batch
            worker.engine.search_batch = broken
            worker.engine.search_batch_arrays = broken
            with pytest.raises(urllib.error.HTTPError) as ei:
                http_post(worker.url + "/worker/process-batch",
                          json.dumps({"queries": ["common"],
                                      "k": 10}).encode())
            assert ei.value.code == 500
            assert global_metrics.get("worker_batch_failures") >= 1
        finally:
            _stop_all(nodes)

    def test_leader_counts_failed_batch_not_empty_merge(self, core,
                                                        tmp_path):
        """The failed worker's shard drops out AND is counted: the merge
        keeps the healthy worker's hits, scatter_failures increments,
        and the reply carries the degraded marker."""
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader, w1, w2 = nodes
            _upload_docs(leader)
            full = set(_search(leader, "common"))
            assert full == set(DOCS)
            victim = w1
            victim_names = {n for n, ws in leader._placement.items()
                            if victim.url in ws}
            assert victim_names and victim_names != set(DOCS)

            def broken(queries, k=None, unbounded=False):
                raise ValueError("engine exploded")

            victim.engine.search_batch = broken
            victim.engine.search_batch_arrays = broken
            before = global_metrics.get("scatter_failures")
            req = urllib.request.Request(
                leader.url + "/leader/start",
                data=json.dumps({"query": "common"}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                marker = resp.headers.get("X-Scatter-Degraded")
                res = json.loads(resp.read())
            # healthy shard answered; failed shard is absent, not empty
            assert set(res) == full - victim_names
            assert global_metrics.get("scatter_failures") > before
            assert marker is not None and "attempted=2" in marker
            assert global_metrics.get("scatter_degraded") == 1
            snap = json.loads(http_get(leader.url + "/api/metrics"))
            assert snap["scatter_last_responded"] == 1
        finally:
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# Circuit breaker end to end (acceptance c, deterministic variant)
# ---------------------------------------------------------------------------

class TestBreakerEndToEnd:
    def test_open_halfopen_close_with_bounded_fires(self, core, tmp_path):
        # reset_s wide enough that a suite-load-slowed search cannot
        # reach the half-open window mid-test and admit a probe RPC —
        # the exact fire-count asserts below depend on it
        nodes = _mk_cluster(core, tmp_path, n=3, breaker_reset_s=2.0)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            workers = leader.registry.get_all_service_addresses()
            full = set(_search(leader, "common"))

            global_injector.arm("leader.worker_rpc", action="raise")
            # threshold=2, attempts=1: two failed queries trip BOTH
            # workers' breakers...
            for _ in range(2):
                assert _search(leader, "common") == {}
            fired = global_injector.fired["leader.worker_rpc"]
            assert fired == 2 * len(workers)   # one per (query, worker)
            assert all(leader.resilience.board.is_open(w)
                       for w in workers)
            assert global_metrics.get("breaker_opened") >= 2
            # ...and the NEXT query fast-fails without any RPC attempt:
            # the fire counter must not move (bounded retries)
            assert _search(leader, "common") == {}
            assert global_injector.fired["leader.worker_rpc"] == fired
            assert global_metrics.get("scatter_circuit_open") >= 2
            assert global_metrics.get("scatter_degraded") == 1

            # fault heals; after reset_s the half-open probes succeed
            # and the breakers close: full results again
            global_injector.disarm("leader.worker_rpc")
            assert wait_until(
                lambda: set(_search(leader, "common")) == full,
                timeout=5.0)
            assert global_metrics.get("breaker_closed") >= 2
            assert global_metrics.get("breaker_probes") >= 2
            for w in workers:
                b = leader.resilience.board.breaker(w)
                assert b.transitions[-3:] == ["open", "half_open",
                                              "closed"]
            assert global_metrics.get("scatter_degraded") == 0
        finally:
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# Reconciliation sweep (tentpole + satellite regression test)
# ---------------------------------------------------------------------------

class TestReconcileSweep:
    def test_failed_reconcile_retried_by_sweep_no_double_count(
            self, core, tmp_path):
        """Regression for ADVICE r5 medium (node.py:692): kill the
        /worker/delete RPC at the rejoin, assert (1) merged scores never
        double-count the moved documents even while the reconcile is
        pending (merge-time exclusion), and (2) the periodic sweep —
        not a membership event — converges the cluster back to
        single-copy."""
        nodes = _mk_cluster(core, tmp_path)
        leader = nodes[0]
        try:
            _upload_docs(leader)
            assert set(_search(leader, "common")) == set(DOCS)

            victim = nodes[1]
            victim_port = victim.port
            victim_names = {n for n, ws in leader._placement.items()
                            if victim.url in ws}
            assert victim_names
            # kill the victim; recovery re-places its shard
            victim.httpd.shutdown()
            victim.httpd.server_close()
            core.expire_session(victim.coord.sid)
            assert wait_until(
                lambda: set(_search(leader, "common")) == set(DOCS)
                and {w for ws in leader._placement.values()
                     for w in ws} == {nodes[2].url}, timeout=10.0)
            want = _search(leader, "common")

            # arm: EVERY /worker/delete dies (covers the join-event
            # reconcile and any sweep pass while armed)
            global_injector.arm("leader.reconcile_rpc", action="raise")
            revived = _node(core, tmp_path, 1, port=victim_port)
            nodes.append(revived)
            assert wait_until(lambda: sorted(
                leader.registry.get_all_service_addresses())
                == sorted([nodes[2].url, revived.url]), timeout=5.0)
            # the join-event reconcile has failed by the time a sweep
            # retry fires; _moved still pending either way
            assert wait_until(
                lambda: global_injector.fired.get(
                    "leader.reconcile_rpc", 0) >= 1, timeout=5.0)
            with leader._placement_lock:
                assert leader._moved.get(revived.url) == victim_names

            # double-count window CLOSED while pending: the rejoiner's
            # boot re-walk serves the moved docs, but the merge excludes
            # them until the reconcile lands. EVERY search's scores must
            # be exact; the exclusion counter ticks only once the
            # revived worker's hits actually flow (its predecessor's
            # half-open breaker at the same URL may eat the first
            # scatter or two under load — wait for the real signal
            # instead of assuming a fixed number of searches).
            def exclusion_observed():
                scores = _search(leader, "common")
                assert scores.keys() == want.keys()
                for n in want:
                    assert scores[n] == pytest.approx(want[n], rel=1e-6)
                return global_metrics.get("scatter_hits_excluded") > 0
            assert wait_until(exclusion_observed, timeout=8.0)
            assert global_metrics.get("reconcile_failures") >= 1

            # heal the RPC: the SWEEP (timer, no membership event left
            # to fire) must converge the reconcile
            global_injector.disarm("leader.reconcile_rpc")

            def converged():
                with leader._placement_lock:
                    if leader._moved.get(revived.url):
                        return False
                return True
            assert wait_until(converged, timeout=5.0)
            assert global_metrics.get("reconcile_sweep_retries") >= 1
            assert global_metrics.get("reconciles_completed") >= 1
            # the moved docs are really deleted from the rejoiner, and
            # the merged scores still match (single copy, no exclusion
            # needed anymore)
            deleted = json.loads(http_post(
                revived.url + "/worker/delete",
                json.dumps({"names": sorted(victim_names)}).encode()))
            assert deleted["deleted"] == 0   # already gone
            scores = _search(leader, "common")
            for n in want:
                assert scores[n] == pytest.approx(want[n], rel=1e-6)
        finally:
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# Compile-flake retry gate (satellite)
# ---------------------------------------------------------------------------

class TestCompileRetryGate:
    def _node(self, core, tmp_path):
        return _node(core, tmp_path, 0, compile_retry_per_bucket=1)

    def test_unrelated_compile_substring_not_retried(self, core,
                                                     tmp_path):
        """The old gate retried ANY error whose repr contains 'compile';
        the narrowed gate requires the known transient signature."""
        node = self._node(core, tmp_path)
        try:
            node.engine.ingest_text("a.txt", "needle body")
            node.engine.commit()
            calls = {"n": 0}

            def broken(queries, k=None, unbounded=False):
                calls["n"] += 1
                raise ValueError("cannot compile the scoring plan")

            node.engine.search_batch = broken
            with pytest.raises(ValueError):
                node.worker_search_batch(["needle"])
            assert calls["n"] == 1   # no blind retry
        finally:
            node.stop()

    def test_per_bucket_budget_stops_deterministic_retries(self, core,
                                                           tmp_path):
        node = self._node(core, tmp_path)
        try:
            node.engine.ingest_text("a.txt", "needle body")
            node.engine.commit()
            calls = {"n": 0}

            def always_fails(queries, k=None, unbounded=False):
                calls["n"] += 1
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: Ran out of memory in memory "
                    "space vmem while allocating on stack. Scoped "
                    "allocation with size 18.20M and limit 16.00M "
                    "exceeded scoped vmem limit by 2.20M.")

            orig = node.engine.search_batch
            node.engine.search_batch = always_fails
            # first batch at this bucket: one retry (budget -> 0)
            with pytest.raises(RuntimeError):
                node.worker_search_batch(["needle"])
            assert calls["n"] == 2
            # deterministic failure: budget spent, NO further retries
            with pytest.raises(RuntimeError):
                node.worker_search_batch(["needle"])
            assert calls["n"] == 3
            # a different bucket size has its own budget
            with pytest.raises(RuntimeError):
                node.worker_search_batch(["needle", "x", "y"])
            assert calls["n"] == 5
            # success refills: a later transient at the bucket retries
            node.engine.search_batch = orig
            assert node.worker_search_batch(["needle"])
            node.engine.search_batch = always_fails
            calls["n"] = 0
            with pytest.raises(RuntimeError):
                node.worker_search_batch(["needle"])
            assert calls["n"] == 2
        finally:
            node.stop()


# ---------------------------------------------------------------------------
# Coordination loops
# ---------------------------------------------------------------------------

class TestCoordinationResilience:
    def test_heartbeat_send_retried_within_interval(self, core):
        """Two consecutive send failures must not cost the session two
        whole heartbeat intervals of its timeout budget: the retry
        policy resends within the same cycle and the session lives."""
        client = LocalCoordination(core, 0.05)
        try:
            global_injector.arm("coord.heartbeat_send", action="raise",
                                times=2)
            assert wait_until(
                lambda: global_injector.fired.get(
                    "coord.heartbeat_send", 0) >= 2, timeout=3.0)
            import time as _t
            _t.sleep(2 * core.session_timeout_s)
            # session survived: still listed, no expiry event
            assert client.sid in core._sessions
            assert global_metrics.get("coord_heartbeat_retries") >= 2
        finally:
            client.close()


# ---------------------------------------------------------------------------
# Chaos jobs (slow): probabilistic fault injection across the plane
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestChaos:
    def test_chaos_scatter_heartbeats_and_reconciles(self, core,
                                                     tmp_path):
        """Acceptance: probabilistic faults on worker RPCs, heartbeats,
        and reconciles. The leader must (a) count every failed batch
        instead of merging empties, (b) keep merged scores single-copy
        at all times, (c) bound retries (injector fire counters) and
        recover to full, non-degraded results once the chaos stops."""
        nodes = _mk_cluster(core, tmp_path,
                            rpc_max_attempts=2, breaker_reset_s=0.3)
        leader = nodes[0]
        try:
            _upload_docs(leader)
            full = _search(leader, "common")
            assert set(full) == set(DOCS)
            workers = leader.registry.get_all_service_addresses()

            global_injector.arm("leader.worker_rpc", action="raise",
                                probability=0.3)
            global_injector.arm("coord.heartbeat_send", action="raise",
                                probability=0.3)
            global_injector.arm("leader.reconcile_rpc", action="raise",
                                probability=0.5)
            global_injector.arm("resilience.backoff", action="delay",
                                delay_s=0.0)

            n_queries = 40
            for i in range(n_queries):
                res = _search(leader, "common")
                # honesty: partial/empty results only ever co-occur with
                # counted failures or open breakers
                if set(res) != set(full):
                    assert (global_metrics.get("scatter_failures") > 0
                            or global_metrics.get(
                                "scatter_circuit_open") > 0)
                # single-copy invariant: no score ever EXCEEDS the
                # healthy value (double-count would inflate it)
                for n, s in res.items():
                    assert s <= full[n] * (1 + 1e-6)

            # bounded retries: each logical RPC fires the fault point at
            # most rpc_max_attempts times
            max_rpcs = n_queries * len(workers)
            fired = global_injector.fired.get("leader.worker_rpc", 0)
            assert fired <= max_rpcs * 2
            # every backoff sleep follows SOME injected failure (the
            # heartbeat retry loop shares the backoff fault point)
            backoffs = global_injector.fired.get("resilience.backoff", 0)
            all_failures = sum(
                global_injector.fired.get(p, 0)
                for p in ("leader.worker_rpc", "coord.heartbeat_send",
                          "leader.reconcile_rpc"))
            assert backoffs <= all_failures

            # chaos off: cluster converges to healthy, non-degraded
            global_injector.disarm()

            def healthy():
                res = _search(leader, "common")
                return (set(res) == set(full)
                        and global_metrics.get("scatter_degraded") == 0)
            assert wait_until(healthy, timeout=10.0)
            for n, s in _search(leader, "common").items():
                assert s == pytest.approx(full[n], rel=1e-6)
        finally:
            _stop_all(nodes)

    def test_chaos_rejoin_sweep_converges(self, core, tmp_path):
        """Worker death + rejoin under a flaky /worker/delete: the sweep
        must converge to single-copy despite 70%-lossy reconciles, and
        scores must never double-count at any observation point."""
        nodes = _mk_cluster(core, tmp_path)
        leader = nodes[0]
        try:
            _upload_docs(leader)
            victim = nodes[1]
            victim_port = victim.port
            victim.httpd.shutdown()
            victim.httpd.server_close()
            core.expire_session(victim.coord.sid)
            assert wait_until(
                lambda: set(_search(leader, "common")) == set(DOCS)
                and {w for ws in leader._placement.values()
                     for w in ws} == {nodes[2].url}, timeout=10.0)
            want = _search(leader, "common")

            global_injector.arm("leader.reconcile_rpc", action="raise",
                                probability=0.7)
            revived = _node(core, tmp_path, 1, port=victim_port)
            nodes.append(revived)

            def converged():
                scores = _search(leader, "common")
                assert scores.keys() == want.keys()
                for n in want:   # never double-counted, converged or not
                    assert scores[n] == pytest.approx(want[n], rel=1e-6)
                with leader._placement_lock:
                    return not leader._moved.get(revived.url)
            assert wait_until(converged, timeout=20.0, interval=0.1)
            # a reconcile really completed (the fault is probabilistic,
            # so it may or may not have fired first — the deterministic
            # retry-through-failure path is pinned by TestReconcileSweep)
            assert global_metrics.get("reconciles_completed") >= 1
        finally:
            _stop_all(nodes)
