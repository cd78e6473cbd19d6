"""Scale-out query plane: placement follower views, stateless routers,
any-node reads, and honest staleness (ISSUE 12).

The acceptance story: ANY node — or a dedicated stateless router
process — serves ``/leader/start`` reads with exact owner-merge
semantics (never the legacy sum-merge's replica double-count), every
reply stamped with the (epoch, generation) placement world it routed
under, while all mutations stay on the elected leader. A router whose
placement view is deliberately staled (partitioned from the
coordinator by the nemesis, or frozen by the deterministic hook)
degrades HONESTLY — ``X-Scatter-Degraded … stale_view=1``, result
cache bypassed — and self-heals on the next successful refresh.

Tier-1 (deterministic): follower load/watch-refresh/re-arm mechanics,
router exact parity + route stamps, per-router cache invalidation on
observed flushes, unmapped-hit dropping (never summing), write
forwarding, worker-death failover through a router, any-node reads,
the frozen/partitioned staleness contract, and CLI surfaces.

Slow (``make chaos-router``): kill -9 a router AND the leader
mid-workload under 2x zipfian load through two routers — the
surviving router keeps serving, every admitted read is exact
single-node-oracle parity or honestly degraded, and the tier heals.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from tfidf_tpu.cluster.coordination import (CoordinationClient,
                                            CoordinationCore,
                                            CoordinationServer,
                                            LocalCoordination)
from tfidf_tpu.cluster.nemesis import global_nemesis
from tfidf_tpu.cluster.node import SearchNode, http_get, http_post
from tfidf_tpu.cluster.placement import PlacementFollower, PlacementMap
from tfidf_tpu.cluster.router import QueryRouter, list_routers
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

from tests.test_admission import _assert_parity, _oracle
from tests.test_cluster import wait_until


@pytest.fixture(autouse=True)
def _heal_nemesis():
    yield
    global_nemesis.heal()


@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


RDOCS = {f"rt{i}.txt": f"common token{i} word{i % 3} extra{i % 5}"
         for i in range(12)}
RQUERIES = ["common", "token3 word0", "word1 extra2", "common token7"]

_CFG = dict(
    top_k=32, min_doc_capacity=64, min_nnz_capacity=1 << 12,
    min_vocab_capacity=1 << 10, query_batch=8, max_query_terms=8,
    rpc_max_attempts=1,            # deterministic: no hidden retries
    breaker_failure_threshold=2, breaker_reset_s=0.4,
    reconcile_sweep_interval_s=0.2, placement_flush_ms=10.0,
    replication_factor=2,
    # fast follower cadence so tests never wait on the 1s default;
    # staleness threshold small enough to exercise in-band
    router_refresh_ms=50.0, router_stale_ms=800.0,
    # node-side caches off: scatter mechanics are under test on the
    # nodes; ROUTER caches are exercised explicitly via the router's
    # own knob
    result_cache_entries=0,
    admission_rate_qps=0.0, admission_queue_high_water=10_000,
    admission_queue_critical=100_000)


def _node(core, tmp_path, i, **kw):
    cfg_kw = dict(_CFG)
    cfg_kw.update(kw)
    cfg = Config(
        documents_path=str(tmp_path / f"rr{i}" / "documents"),
        index_path=str(tmp_path / f"rr{i}" / "index"),
        port=0, **cfg_kw)
    return SearchNode(cfg, coord=LocalCoordination(core, 0.1)).start()


def _mk_cluster(core, tmp_path, n=3, **kw):
    nodes = [_node(core, tmp_path, i, **kw) for i in range(n)]
    wait_until(lambda: len(
        nodes[0].registry.get_all_service_addresses()) == n - 1)
    return nodes


def _mk_router(core, **kw):
    cfg_kw = dict(_CFG)
    cfg_kw.setdefault("router_cache_entries", 0)
    cfg_kw.update(kw)
    cfg = Config(port=0, **cfg_kw)
    return QueryRouter(cfg, coord=LocalCoordination(core, 0.1)).start()


def _stop_all(nodes):
    for nd in nodes:
        try:
            nd.stop()
        except Exception:
            pass


def _upload(leader, docs=RDOCS):
    batch = [{"name": n, "text": t} for n, t in docs.items()]
    return json.loads(http_post(leader.url + "/leader/upload-batch",
                                json.dumps(batch).encode()))


def _post_full(base, path, data, headers=None, timeout=30.0):
    """(status, headers, body) — the honesty headers are the subject
    here, so the plain-bytes helpers are not enough."""
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    req = urllib.request.Request(base + path, data=data, headers=h)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _search_full(base, q, headers=None):
    st, hd, body = _post_full(base, "/leader/start",
                              json.dumps({"query": q}).encode(),
                              headers=headers)
    assert st == 200, (st, body[:200])
    return json.loads(body), hd


def _wait_view(router, n_docs, timeout=10.0):
    assert wait_until(
        lambda: router.placement.loaded
        and len(router.placement.replicas) == n_docs, timeout=timeout), \
        router.placement.view_snapshot()


# ---------------------------------------------------------------------------
# placement follower mechanics
# ---------------------------------------------------------------------------

class TestPlacementFollower:
    def _authoritative(self, coord):
        pm = PlacementMap(flush_ms=0.0, name="auth")
        pm.bind_store(lambda: coord)
        pm.set_persist_enabled(True)
        pm.epoch = 7
        return pm

    def _place(self, pm, name, workers):
        with pm.lock:
            pm.route_locked(name, list(workers),
                            {w: 0 for w in workers}, None, len(workers))
        for w in workers:
            pm.leg_success(name, w)

    def test_load_replaces_and_reports_lineage(self, core):
        ca, cb = LocalCoordination(core, 0.1), LocalCoordination(core, 0.1)
        pm = self._authoritative(ca)
        self._place(pm, "a", ["http://w1", "http://w2"])
        assert pm.flush()
        f = PlacementFollower(refresh_ms=60_000.0, stale_ms=0.0)
        f.bind_store(lambda: cb)
        assert f.refresh()
        assert f.loaded and f.version == 1
        assert set(f.replicas) == {"a"}
        assert sorted(f.replicas["a"]) == ["http://w1", "http://w2"]
        # the writing leader's lineage rides the payload
        assert f.loaded_epoch == 7
        assert f.loaded_gen == pm.gen
        # REPLACE semantics: a name that vanishes from the payload
        # vanishes from the view (never the new-leader merge)
        pm.forget(["a"])
        assert pm.flush()
        assert f.refresh()
        assert "a" not in f.replicas
        ca.close()
        cb.close()

    def test_watch_fires_refresh_and_rearms(self, core):
        ca, cb = LocalCoordination(core, 0.1), LocalCoordination(core, 0.1)
        pm = self._authoritative(ca)
        self._place(pm, "a", ["http://w1"])
        assert pm.flush()
        # refresh backstop parked FAR away: only the data watch can
        # deliver within the wait windows below
        f = PlacementFollower(refresh_ms=60_000.0, stale_ms=0.0)
        f.bind_store(lambda: cb)
        f.start()
        assert f.loaded and f.version == 1
        self._place(pm, "b", ["http://w1"])
        assert pm.flush()
        assert wait_until(lambda: f.version == 2), f.view_snapshot()
        assert "b" in f.replicas
        # one-shot watch re-armed: a SECOND flush propagates too
        self._place(pm, "c", ["http://w1"])
        assert pm.flush()
        assert wait_until(lambda: f.version == 3), f.view_snapshot()
        f.stop()
        ca.close()
        cb.close()

    def test_absent_znode_is_current_empty_not_failure(self, core):
        cb = LocalCoordination(core, 0.1)
        f = PlacementFollower(refresh_ms=60_000.0, stale_ms=500.0)
        f.bind_store(lambda: cb)
        f._started = True
        assert f.refresh()        # pre-first-flush cluster
        assert not f.suspect()    # confirmed current (empty IS a view)
        cb.close()

    def test_freeze_suspect_unfreeze_heals(self, core):
        ca, cb = LocalCoordination(core, 0.1), LocalCoordination(core, 0.1)
        pm = self._authoritative(ca)
        self._place(pm, "a", ["http://w1"])
        assert pm.flush()
        f = PlacementFollower(refresh_ms=30.0, stale_ms=200.0)
        f.bind_store(lambda: cb)
        f.start()
        # not a bare assert: a host stall over stale_ms between the
        # start's refresh and this line reads as suspect until the
        # 30 ms refresher runs again (2 of 3 loaded tier-1 runs, PR 29)
        assert wait_until(lambda: not f.suspect(), timeout=5.0)
        f.freeze()
        assert wait_until(lambda: f.suspect(), timeout=5.0)
        assert f.view_snapshot()["stale"]
        f.unfreeze()
        assert wait_until(lambda: not f.suspect(), timeout=5.0)
        f.stop()
        ca.close()
        cb.close()


# ---------------------------------------------------------------------------
# stateless router: exact reads, stamps, cache, failover, writes
# ---------------------------------------------------------------------------

class TestRouterReads:
    def test_exact_parity_and_route_stamp(self, core, tmp_path):
        """A router's reads are byte-equal to the leader's and to the
        single-node oracle (2 workers x R=2 = full replication, so
        per-shard stats match global stats), and every reply carries
        the (epoch, generation) placement world it routed under."""
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core)
            _wait_view(router, len(RDOCS))
            want = _oracle(tmp_path, docs=RDOCS, queries=RQUERIES,
                           tag="r_oracle")
            for q in RQUERIES:
                via_leader = json.loads(http_post(
                    leader.url + "/leader/start",
                    json.dumps({"query": q}).encode()))
                got, hd = _search_full(router.url, q)
                assert got == via_leader
                _assert_parity(got, want[q], ctx=q)
                assert "X-Scatter-Degraded" not in hd
                # the route stamp: which placement world answered
                assert int(hd["X-Route-Epoch"]) == leader.placement.epoch
                assert int(hd["X-Route-Generation"]) == \
                    router.placement.loaded_gen
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)

    def test_cache_hit_then_flush_invalidates(self, core, tmp_path):
        """The router cache token is (membership epoch, view version):
        repeats answer router-side without a scatter; an upload the
        leader flushes advances the observed version and the next read
        sees the new document."""
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core, router_cache_entries=64)
            _wait_view(router, len(RDOCS))
            got1, _ = _search_full(router.url, "common")
            h0 = global_metrics.get("cache_hits", 0)
            got2, _ = _search_full(router.url, "common")
            assert got2 == got1
            assert global_metrics.get("cache_hits", 0) == h0 + 1
            v0 = router.placement.version
            http_post(leader.url + "/leader/upload-batch", json.dumps(
                [{"name": "fresh.txt", "text": "common fresh"}]).encode())
            assert wait_until(
                lambda: router.placement.version > v0
                and "fresh.txt" in router.placement.replicas)
            got3, hd3 = _search_full(router.url, "common")
            assert "fresh.txt" in got3, got3
            assert "X-Scatter-Degraded" not in hd3
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)

    def test_worker_death_fails_over_exact(self, core, tmp_path):
        """The router runs the full PR-5 resilience stack: a dead
        worker's ownership slice fails over to the surviving replica
        within the request — full replication keeps results exact."""
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core)
            _wait_view(router, len(RDOCS))
            want = _oracle(tmp_path, docs=RDOCS, queries=RQUERIES,
                           tag="r_oracle2")
            victim = next(n for n in nodes if not n.is_leader())
            victim.stop()
            assert wait_until(lambda: len(
                router.registry.get_all_service_addresses()) == 1)

            def parity():
                try:
                    got, _hd = _search_full(router.url, "common")
                    _assert_parity(got, want["common"], "post-death")
                    return True
                except AssertionError:
                    return False
            assert wait_until(parity, timeout=10.0)
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)

    def test_unmapped_hits_dropped_never_summed(self, core, tmp_path):
        """A name OUTSIDE the follower view (here: written directly to
        both workers behind the leader's back) is dropped from
        router-routed merges and the reply is marked degraded — the
        legacy sum-merge would have silently double-counted the R
        copies. The leader's own results are its own business; the
        router must never fabricate a doubled score."""
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core)
            _wait_view(router, len(RDOCS))
            for w in leader.registry.get_all_service_addresses():
                http_post(w + "/worker/upload?name=ghost.txt",
                          b"common ghost",
                          content_type="application/octet-stream")
            got, hd = _search_full(router.url, "common")
            assert "ghost.txt" not in got
            marker = hd.get("X-Scatter-Degraded", "")
            assert "dropped=" in marker and "dropped=0" not in marker, \
                (marker, got)
            assert global_metrics.get(
                "router_unmapped_hits_dropped", 0) > 0
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)

    def test_writes_forward_to_leader(self, core, tmp_path):
        """Mutations stay on the elected leader: an upload and a
        delete POSTed at the router land through the leader's
        placement machinery (mapped, replicated, invalidated) and the
        read plane converges on the result."""
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core)
            _wait_view(router, len(RDOCS))
            st, _hd, body = _post_full(
                router.url, "/leader/upload-batch", json.dumps(
                    [{"name": "viaRouter.txt",
                      "text": "common viarouter"}]).encode())
            assert st == 200, body
            # the LEADER's map owns the placement (not the router's)
            assert wait_until(
                lambda: leader.placement.holders_of("viaRouter.txt"))
            assert wait_until(
                lambda: "viaRouter.txt" in router.placement.replicas)
            got, _ = _search_full(router.url, "viarouter")
            assert "viaRouter.txt" in got
            st, _hd, body = _post_full(
                router.url, "/leader/delete",
                json.dumps({"names": ["viaRouter.txt"]}).encode())
            assert st == 200, body
            assert not leader.placement.holders_of("viaRouter.txt")
            assert wait_until(
                lambda: "viaRouter.txt" not in router.placement.replicas)

            def gone():
                got, _hd = _search_full(router.url, "viarouter")
                return "viaRouter.txt" not in got
            assert wait_until(gone, timeout=10.0)
            assert global_metrics.get("router_writes_proxied", 0) >= 2
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)

    def test_download_probes_workers(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core)
            _wait_view(router, len(RDOCS))
            got = http_get(router.url + "/leader/download?path=rt0.txt")
            assert got == RDOCS["rt0.txt"].encode()
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)

    def test_operator_surface(self, core, tmp_path):
        """/api/router + /api/routers + /api/status + /api/health: the
        tier is enumerable from any node and each router reports the
        placement world it routes under."""
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core)
            _wait_view(router, len(RDOCS))
            assert http_get(router.url + "/api/status").decode() == \
                "I am a router"
            # registered under /router_registry, visible from any node
            assert json.loads(http_get(
                leader.url + "/api/routers")) == [router.url]
            assert list_routers(leader.coord) == [router.url]
            snap = json.loads(http_get(router.url + "/api/router"))
            assert snap["role"] == "router"
            assert snap["placement"]["docs"] == len(RDOCS)
            assert snap["placement"]["epoch"] == leader.placement.epoch
            # the leader's /api/router is the lag reference
            ref = json.loads(http_get(leader.url + "/api/router"))
            assert ref["placement"]["authoritative"] is True
            assert snap["placement"]["gen"] <= ref["placement"]["gen"]
            health = json.loads(http_get(router.url + "/api/health"))
            assert health["role"] == "router"
            assert health["admission"]["front_door"] == "router"
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# any-node reads: the role split on SearchNode itself
# ---------------------------------------------------------------------------

class TestAnyNodeReads:
    def test_worker_served_reads_exact_parity(self, core, tmp_path):
        """THE role-split pin: a NON-leader node answers /leader/start
        through its placement follower view with exact owner-merge
        parity. Before the split, a worker's empty post-demotion map
        sent every hit through the legacy sum-merge — R=2 replication
        silently DOUBLED every score."""
        nodes = _mk_cluster(core, tmp_path)
        try:
            leader = nodes[0]
            _upload(leader)
            worker = next(n for n in nodes if not n.is_leader())
            assert wait_until(
                lambda: worker._follower_active()
                and len(worker.placement_follower.replicas)
                == len(RDOCS))
            want = _oracle(tmp_path, docs=RDOCS, queries=RQUERIES,
                           tag="r_oracle3")
            for q in RQUERIES:
                got, hd = _search_full(worker.url, q)
                _assert_parity(got, want[q], ctx=f"worker-served {q}")
                assert "X-Scatter-Degraded" not in hd
                assert "X-Route-Epoch" in hd
        finally:
            _stop_all(nodes)

    def test_worker_follower_watch_survives_session_rejoin(
            self, core, tmp_path):
        """A session expiry kills the follower's armed data watch with
        the session; the rejoin must re-arm it on the NEW client —
        otherwise any-node reads silently degrade to poll latency
        forever. The refresh backstop is parked far away, so only a
        working watch can deliver the post-rejoin flush in time."""
        cfg = Config(
            documents_path=str(tmp_path / "rj" / "documents"),
            index_path=str(tmp_path / "rj" / "index"), port=0,
            **dict(_CFG, router_refresh_ms=60_000.0))
        nodes = _mk_cluster(core, tmp_path, n=2)
        worker = SearchNode(
            cfg, coord_factory=lambda: LocalCoordination(core, 0.1)
        ).start()
        try:
            leader = nodes[0]
            wait_until(lambda: len(
                leader.registry.get_all_service_addresses()) == 2)
            _upload(leader)
            assert wait_until(lambda: worker._follower_active())
            rejoins0 = global_metrics.get("session_rejoins", 0)
            core.expire_session(worker.coord.sid)
            assert wait_until(lambda: global_metrics.get(
                "session_rejoins", 0) > rejoins0, timeout=15.0)
            v0 = worker.placement_follower.version
            http_post(leader.url + "/leader/upload-batch", json.dumps(
                [{"name": "postRejoin.txt",
                  "text": "common postrejoin"}]).encode())
            # watch latency, not the 60s backstop
            assert wait_until(
                lambda: worker.placement_follower.version > v0,
                timeout=10.0)
        finally:
            worker.stop()
            _stop_all(nodes)

    def test_worker_forwards_writes_to_leader(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path)
        try:
            leader = nodes[0]
            _upload(leader)
            worker = next(n for n in nodes if not n.is_leader())
            st, _hd, body = _post_full(
                worker.url, "/leader/upload-batch", json.dumps(
                    [{"name": "viaWorker.txt",
                      "text": "common viaworker"}]).encode())
            assert st == 200, body
            # the LEADER placed it (the worker's own map stays empty —
            # it holds no authority)
            assert wait_until(
                lambda: leader.placement.holders_of("viaWorker.txt"))
            assert not worker.placement.holders_of("viaWorker.txt")
        finally:
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# honest staleness: frozen + nemesis-partitioned router views
# ---------------------------------------------------------------------------

class TestStaleRouterHonesty:
    def test_frozen_view_degrades_and_bypasses_cache(self, core,
                                                     tmp_path):
        """A view that cannot be confirmed fresh marks EVERY response
        degraded (stale_view=1) and stops serving from the result
        cache — a pre-partition cache entry would be silently wrong in
        exactly the window the marker exists for. Un-freezing
        self-heals."""
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core, router_cache_entries=64,
                                router_stale_ms=300.0)
            _wait_view(router, len(RDOCS))
            got1, hd1 = _search_full(router.url, "common")
            assert "X-Scatter-Degraded" not in hd1
            _search_full(router.url, "common")   # now cached
            router.placement.freeze()
            assert wait_until(lambda: router.placement.suspect(),
                              timeout=5.0)
            stale0 = global_metrics.get("router_stale_responses", 0)
            got2, hd2 = _search_full(router.url, "common")
            marker = hd2.get("X-Scatter-Degraded", "")
            assert "stale_view=1" in marker, marker
            # the cache was bypassed: a real scatter ran (attempted>0
            # shows in the stale-response counter, not a cache hit)
            assert global_metrics.get(
                "router_stale_responses", 0) > stale0
            assert got2 == got1   # data unchanged: still exact
            router.placement.unfreeze()
            assert wait_until(lambda: not router.placement.suspect())
            _got3, hd3 = _search_full(router.url, "common")
            assert "X-Scatter-Degraded" not in hd3
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)

    @pytest.mark.timeout(180)
    def test_nemesis_partitioned_router_is_exact_or_degraded(
            self, tmp_path):
        """ISSUE 12 satellite: partition a router from the coordinator
        with the network nemesis, mutate placement behind its back (a
        rebalance flip AND a cluster-wide delete), and pin that every
        read through the stale router is exact or HONESTLY degraded —
        never silently double-counted, never a silently resurrected
        deleted document. Heal; the router converges to fresh-oracle
        parity with the marker gone."""
        srv = CoordinationServer(host="127.0.0.1", port=0).start()
        nodes, router = [], None
        try:
            def factory():
                return CoordinationClient(srv.address,
                                          heartbeat_interval_s=0.1)

            for i in range(3):
                cfg = Config(
                    documents_path=str(tmp_path / f"nm{i}" / "docs"),
                    index_path=str(tmp_path / f"nm{i}" / "idx"),
                    port=0, **_CFG)
                nodes.append(SearchNode(
                    cfg, coord_factory=factory).start())
            wait_until(lambda: len(
                nodes[0].registry.get_all_service_addresses()) == 2)
            leader = nodes[0]
            assert leader.is_leader()
            _upload(leader)
            rcfg = dict(_CFG)
            rcfg.update(router_stale_ms=400.0, router_refresh_ms=50.0)
            router = QueryRouter(Config(port=0, **rcfg),
                                 coord_factory=factory).start()
            _wait_view(router, len(RDOCS))
            want = _oracle(tmp_path, docs=RDOCS, queries=RQUERIES,
                           tag="nm_oracle")
            got0, hd0 = _search_full(router.url, "common")
            _assert_parity(got0, want["common"], "pre-partition")

            # cut the router's control plane only (data plane intact)
            global_nemesis.partition([router.url], [srv.address])
            assert wait_until(lambda: router.placement.suspect(),
                              timeout=10.0)

            # mutate placement behind the stale view: flip a doc range
            # off one worker and delete a doc cluster-wide
            victim = leader.registry.get_all_service_addresses()[0]
            names = leader.placement.names_on(victim)[:3]
            assert names
            leader.rebalancer.migrate(victim, names)
            deleted = "rt0.txt"
            json.loads(http_post(
                leader.url + "/leader/delete",
                json.dumps({"names": [deleted]}).encode()))

            fresh = _oracle(tmp_path,
                            docs={k: v for k, v in RDOCS.items()
                                  if k != deleted},
                            queries=RQUERIES, tag="nm_oracle2")
            # reads through the STALE router: never silently wrong —
            # every response carries the honest marker (so a deleted
            # doc can only ever appear in a MARKED reply), and no doc
            # is ever double-counted (a replica-summed score would be
            # ~2x either world's; per-shard stats drifting through the
            # mid-reconcile windows stay far below that)
            ceilings = {
                n: 1.9 * max(want["common"].get(n, 0.0),
                             fresh["common"].get(n, 0.0))
                for n in want["common"]}
            for _ in range(5):
                got, hd = _search_full(router.url, "common")
                marker = hd.get("X-Scatter-Degraded", "")
                assert "stale_view=1" in marker, marker
                for n, s in got.items():
                    assert n in want["common"], f"unknown doc {n}"
                    assert s < ceilings[n], \
                        f"score for {n} looks replica-doubled: {s}"
                time.sleep(0.2)

            # heal: the view refreshes, the marker clears, results
            # converge to the fresh oracle exactly
            global_nemesis.heal()
            assert wait_until(lambda: not router.placement.suspect(),
                              timeout=15.0)

            def healed():
                got, hd = _search_full(router.url, "common")
                if "X-Scatter-Degraded" in hd:
                    return False
                if deleted in got:
                    return False
                try:
                    _assert_parity(got, fresh["common"], "healed")
                    return True
                except AssertionError:
                    return False
            assert wait_until(healed, timeout=30.0)
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)
            srv.close()


class TestWriteForwardingEdges:
    def test_dead_published_leader_forwards_503_with_retry_after(
            self, core, tmp_path):
        """A leader that is published (ephemeral not yet expired) but
        DEAD must surface to the writing client as 503 + Retry-After —
        an honest try-again — never a bare 500 with no backoff hint."""
        from tfidf_tpu.cluster.registry import publish_leader_info

        coord = LocalCoordination(core, 0.1)
        publish_leader_info(coord, "http://127.0.0.1:9")  # discard port
        router = _mk_router(core)
        try:
            st, hd, body = _post_full(
                router.url, "/leader/upload-batch",
                json.dumps([{"name": "x.txt", "text": "x"}]).encode())
            assert st == 503, (st, body)
            assert hd.get("Retry-After") == "1"
            assert json.loads(body)["error"] == "leader unavailable"
        finally:
            router.stop()
            coord.close()

    def test_forwarded_writes_pass_local_admission_first(self, core,
                                                         tmp_path):
        """The admit-before-body-read discipline holds on the proxy
        path: a router under backpressure sheds a forwarded mutation
        LOCALLY (429 + shed headers) before buffering or contacting
        the leader."""
        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core, admission_queue_high_water=1,
                                admission_queue_critical=10)
            _wait_view(router, len(RDOCS))
            proxied0 = global_metrics.get("router_writes_proxied", 0)
            # saturate the backpressure signal the router's depth_fn
            # reads (the gauge side of the max)
            global_metrics.set_gauge(
                "last_router_scatter_queue_depth", 999)
            try:
                st, hd, body = _post_full(
                    router.url, "/leader/upload-batch", json.dumps(
                        [{"name": "x.txt", "text": "x"}]).encode())
            finally:
                global_metrics.set_gauge(
                    "last_router_scatter_queue_depth", 0)
            assert st == 429, (st, body)
            assert hd.get("X-Shed-Reason") == "backpressure"
            assert "Retry-After" in hd
            # the leader was never contacted — shed before forwarding
            assert global_metrics.get(
                "router_writes_proxied", 0) == proxied0
            assert not leader.placement.holders_of("x.txt")
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)

    def test_cli_via_router_shed_exits_tempfail(self, core, tmp_path):
        """A shedding router turns the CLI query into the polite-shed
        exit (EX_TEMPFAIL 75 + message), never a raw HTTPError
        traceback — same contract as the --leader path."""
        from tfidf_tpu.cli import main as cli_main

        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core, admission_queue_critical=10,
                                admission_retry_after_s=0.05)
            _wait_view(router, len(RDOCS))
            global_metrics.set_gauge(
                "last_router_scatter_queue_depth", 999)
            try:
                with pytest.raises(SystemExit) as exc:
                    cli_main(["query", "common", "--via-router",
                              router.url])
                assert exc.value.code == 75
            finally:
                global_metrics.set_gauge(
                    "last_router_scatter_queue_depth", 0)
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------

class TestRouterCli:
    def test_query_via_router_and_status_block(self, core, tmp_path,
                                               capsys):
        from tfidf_tpu.cli import main as cli_main

        nodes = _mk_cluster(core, tmp_path)
        router = None
        try:
            leader = nodes[0]
            _upload(leader)
            router = _mk_router(core)
            _wait_view(router, len(RDOCS))
            rc = cli_main(["query", "common", "--via-router",
                           router.url])
            assert rc == 0
            out = capsys.readouterr()
            got = json.loads(out.out)
            assert len(got) == min(12, _CFG["top_k"])
            assert "X-Route-Epoch" in out.err

            # let in-flight leg confirmations settle so the lag
            # comparison sees one quiescent generation on both sides
            assert wait_until(
                lambda: router.placement.loaded_gen
                == leader.placement.gen, timeout=10.0)
            rc = cli_main(["status", "--leader", leader.url])
            assert rc == 0
            st = json.loads(capsys.readouterr().out)
            rb = st["routers"]
            assert rb["count"] == 1
            entry = rb["routers"][0]
            assert entry["url"] == router.url
            assert entry["reachable"] is True
            assert entry["stale"] is False
            assert entry["gen_lag"] == 0
            assert entry["epoch_lag"] == 0
        finally:
            if router is not None:
                router.stop()
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# chaos (slow): kill -9 a router and the leader mid-workload
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestChaosRouter:
    @pytest.mark.timeout(300)
    def test_router_and_leader_kill9_survivors_exact(self, tmp_path):
        """``make chaos-router``: 2x zipfian-ish closed-loop load
        through two stateless routers; mid-workload a router AND the
        node leader are killed -9. The surviving router keeps serving
        — every 200 it returns is exact single-node-oracle parity or
        honestly degraded — and after the new leader settles, reads
        through it converge to exact parity with no marker."""
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
            "TFIDF_TOP_K": "32",
            "TFIDF_SESSION_TIMEOUT_S": "1.0",
            "TFIDF_HEARTBEAT_INTERVAL_S": "0.2",
            "TFIDF_RECONCILE_SWEEP_INTERVAL_S": "0.5",
            "TFIDF_MIN_DOC_CAPACITY": "64",
            "TFIDF_MIN_NNZ_CAPACITY": "4096",
            "TFIDF_MIN_VOCAB_CAPACITY": "1024",
            "TFIDF_QUERY_BATCH": "8",
            "TFIDF_MAX_QUERY_TERMS": "8",
            "TFIDF_ROUTER_REFRESH_MS": "200",
            "TFIDF_ROUTER_STALE_MS": "3000",
            "TFIDF_ROUTER_CACHE_ENTRIES": "64",
        })
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

        coord_port = free_port()
        try:
            spawn("coord", ["coordinator", "--listen",
                            f"127.0.0.1:{coord_port}"])
            wait_pred(lambda: socket.create_connection(
                ("127.0.0.1", coord_port), timeout=1.0).close() or True)
            nports = [free_port() for _ in range(3)]
            nurls = [f"http://127.0.0.1:{p}" for p in nports]
            for i, p in enumerate(nports):
                spawn(f"n{i}", [
                    "serve", "--port", str(p), "--host", "127.0.0.1",
                    "--coordinator-address", f"127.0.0.1:{coord_port}",
                    "--documents-path", str(tmp_path / f"cr{i}/docs"),
                    "--index-path", str(tmp_path / f"cr{i}/idx")])
                wait_pred(lambda u=nurls[i]: http_get(
                    u + "/api/status", timeout=5.0), timeout=120)
            leader = nurls[0]
            wait_pred(lambda: len(json.loads(http_get(
                leader + "/api/services"))) == 2)
            _docs = {f"cr{i}.txt":
                     f"common token{i} word{i % 3} extra{i % 5}"
                     for i in range(24)}
            http_post(leader + "/leader/upload-batch", json.dumps(
                [{"name": n, "text": t}
                 for n, t in _docs.items()]).encode())

            rports = [free_port() for _ in range(2)]
            rurls = [f"http://127.0.0.1:{p}" for p in rports]
            for i, p in enumerate(rports):
                spawn(f"r{i}", [
                    "router", "--coordinator",
                    f"127.0.0.1:{coord_port}",
                    "--host", "127.0.0.1", "--port", str(p)])
                wait_pred(lambda u=rurls[i]: json.loads(http_get(
                    u + "/api/router"))["placement"]["docs"]
                    == len(_docs), timeout=120)

            qpool = ["common"] + [f"token{i} word{i % 3}"
                                  for i in range(24)] + \
                    [f"extra{k} common" for k in range(5)]
            want = _oracle(tmp_path, docs=_docs, queries=qpool,
                           tag="cr_oracle")

            def check_200(base, q):
                """One read: 200 ⇒ exact parity OR degraded marker."""
                st, hd, body = _post_full(
                    base, "/leader/start",
                    json.dumps({"query": q}).encode(), timeout=30.0)
                if st != 200:
                    return None
                got = json.loads(body)
                if "X-Scatter-Degraded" in hd:
                    return "degraded"
                _assert_parity(got, want[q], ctx=f"{base} {q}")
                return "exact"

            # sanity: both routers exact pre-chaos
            for u in rurls:
                wait_pred(lambda u=u: check_200(u, "common") == "exact",
                          timeout=60)

            stop_flag = threading.Event()
            outcomes = {"exact": 0, "degraded": 0, "failed": 0}
            olock = threading.Lock()
            errors = []

            def client(cid):
                import random
                rng = random.Random(cid)
                i = 0
                while not stop_flag.is_set():
                    base = rurls[i % 2] if cid % 2 else rurls[1]
                    q = qpool[int(rng.random() ** 2 * len(qpool))]
                    i += 1
                    try:
                        verdict = check_200(base, q)
                    except AssertionError as e:
                        errors.append(str(e)[:300])
                        return
                    except Exception:
                        verdict = None   # killed router / transient
                    with olock:
                        outcomes[verdict or "failed"] = \
                            outcomes.get(verdict or "failed", 0) + 1

            threads = [threading.Thread(target=client, args=(c,),
                                        daemon=True) for c in range(6)]
            for t in threads:
                t.start()
            time.sleep(3.0)
            # kill -9 a router AND the leader mid-workload
            os.kill(procs["r0"].pid, signal.SIGKILL)
            os.kill(procs["n0"].pid, signal.SIGKILL)
            time.sleep(12.0)
            stop_flag.set()
            for t in threads:
                t.join(timeout=30)
            assert not errors, errors[:3]
            # the surviving router kept ADMITTING exact reads
            assert outcomes["exact"] > 20, outcomes

            # post-chaos: the survivor converges to exact, unmarked
            # parity (the dead worker-leader's docs survive on the
            # replica; a new leader re-publishes the placement map)
            def settled():
                return check_200(rurls[1], "common") == "exact"
            wait_pred(settled, timeout=120, interval=1.0)
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
