"""Cluster tests: election, registry, and the full multi-node HTTP system.

The multi-node behavior the reference only ever validated manually
(SURVEY.md §4: run several instances + curl) is automated here: a 3-node
in-process cluster with a real HTTP data plane, exercising scatter-gather
search, least-loaded upload placement, download probing, leader failover,
and partial-result tolerance.
"""

import json
import time
import urllib.error
import urllib.request

import pytest

from tfidf_tpu.cluster.coordination import CoordinationCore, LocalCoordination
from tfidf_tpu.cluster.election import LeaderElection
from tfidf_tpu.cluster.node import SearchNode, http_get, http_post
from tfidf_tpu.cluster.registry import (ServiceRegistry, read_leader_info)
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.faults import global_injector


def wait_until(pred, timeout=5.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


class Recorder:
    """OnElectionCallback that records role transitions."""

    def __init__(self):
        self.roles = []

    def on_elected_to_be_leader(self):
        self.roles.append("leader")

    def on_worker(self):
        self.roles.append("worker")


class TestElection:
    def test_smallest_wins_and_failover(self, core):
        clients = [LocalCoordination(core, 0.1) for _ in range(3)]
        recs = [Recorder() for _ in range(3)]
        elections = []
        try:
            for c, r in zip(clients, recs):
                e = LeaderElection(c, r)
                e.volunteer_for_leadership()
                e.reelect_leader()
                elections.append(e)
            assert elections[0].is_leader()
            assert not elections[1].is_leader()
            assert recs[0].roles == ["leader"]
            assert recs[1].roles == ["worker"]

            # leader dies → successor (smallest remaining) is promoted
            core.expire_session(clients[0].sid)
            assert wait_until(lambda: recs[1].roles[-1] == "leader")
            assert elections[1].is_leader()
            assert recs[2].roles == ["worker"]   # non-successor undisturbed
        finally:
            for c in clients:
                c.close()

    def test_middle_death_rewires_watch_chain(self, core):
        """When a non-leader dies, its successor re-watches the new
        predecessor without a leadership change (LeaderElection.java:57-86:
        each node watches only its immediate predecessor)."""
        clients = [LocalCoordination(core, 0.1) for _ in range(3)]
        recs = [Recorder() for _ in range(3)]
        elections = []
        try:
            for c, r in zip(clients, recs):
                e = LeaderElection(c, r)
                e.volunteer_for_leadership()
                e.reelect_leader()
                elections.append(e)
            core.expire_session(clients[1].sid)   # middle node dies
            # node 2 re-elects, stays a worker
            assert wait_until(lambda: len(recs[2].roles) == 2)
            assert recs[2].roles == ["worker", "worker"]
            assert elections[0].is_leader()
            # now the old leader dies → node 2 must be promoted (proves the
            # watch was correctly rewired to node 0)
            core.expire_session(clients[0].sid)
            assert wait_until(lambda: recs[2].roles[-1] == "leader")
        finally:
            for c in clients:
                c.close()


class TestRejoin:
    def test_worker_rejoins_after_session_expiry(self, core, tmp_path):
        """A node whose coordination session expires reconnects with a
        fresh session and re-enters the cluster — a capability the
        reference lacks (an expired pod stays out until restarted)."""
        def factory():
            return LocalCoordination(core, 0.1)

        nodes = []
        try:
            for i in range(2):
                cfg = Config(
                    documents_path=str(tmp_path / f"rj{i}" / "docs"),
                    index_path=str(tmp_path / f"rj{i}" / "index"),
                    port=0, min_doc_capacity=64,
                    min_nnz_capacity=1 << 12, min_vocab_capacity=1 << 10,
                    query_batch=4, max_query_terms=8)
                nodes.append(SearchNode(cfg, coord_factory=factory).start())
            leader, worker = nodes
            assert wait_until(lambda: leader.registry
                              .get_all_service_addresses() == [worker.url])
            old_sid = worker.coord.sid
            core.expire_session(old_sid)
            # the worker must come back on a FRESH session and re-register
            assert wait_until(lambda: worker.coord.sid != old_sid,
                              timeout=8.0)
            assert wait_until(lambda: leader.registry
                              .get_all_service_addresses() == [worker.url],
                              timeout=8.0)
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

    def test_leader_info_survives_old_session_expiry(self, core):
        """publish_leader_info must re-own /leader_info: if the new leader
        merely setData'd the old leader's ephemeral node, the address would
        vanish when the old session expires."""
        from tfidf_tpu.cluster.registry import publish_leader_info
        old = LocalCoordination(core, 0.1)
        new = LocalCoordination(core, 0.1)
        try:
            publish_leader_info(old, "http://old")
            publish_leader_info(new, "http://new")
            assert read_leader_info(new) == "http://new"
            core.expire_session(old.sid)
            time.sleep(0.3)   # old session's ephemerals reaped
            assert read_leader_info(new) == "http://new"
        finally:
            old.close()
            new.close()


class TestRegistry:
    def test_register_discover_unregister(self, core):
        a, b = LocalCoordination(core, 0.1), LocalCoordination(core, 0.1)
        try:
            ra, rb = ServiceRegistry(a), ServiceRegistry(b)
            ra.register_to_cluster("http://w0:1")
            rb.register_for_updates()
            assert wait_until(
                lambda: rb.get_all_service_addresses() == ["http://w0:1"])
            ra.unregister_from_cluster()
            assert wait_until(lambda: rb.get_all_service_addresses() == [])
        finally:
            a.close()
            b.close()

    def test_dead_worker_disappears(self, core):
        a, b = LocalCoordination(core, 0.1), LocalCoordination(core, 0.1)
        try:
            ra, rb = ServiceRegistry(a), ServiceRegistry(b)
            ra.register_to_cluster("http://w0:1")
            rb.register_for_updates()
            assert wait_until(
                lambda: rb.get_all_service_addresses() == ["http://w0:1"])
            core.expire_session(a.sid)   # worker crash
            assert wait_until(lambda: rb.get_all_service_addresses() == [])
        finally:
            a.close()
            b.close()


@pytest.fixture
def cluster(core, tmp_path):
    """A 3-node cluster on localhost with a real HTTP data plane."""
    nodes = []
    for i in range(3):
        cfg = Config(
            documents_path=str(tmp_path / f"node{i}" / "documents"),
            index_path=str(tmp_path / f"node{i}" / "index"),
            port=0, result_order="name",
            # single-copy placement: this suite pins the reference's
            # one-copy-per-doc semantics (spread, upsert routing,
            # partial tolerance); R-way placement has its own suite
            replication_factor=1,
            min_doc_capacity=64, min_nnz_capacity=1 << 12,
            min_vocab_capacity=1 << 10, query_batch=4, max_query_terms=8)
        node = SearchNode(cfg, coord=LocalCoordination(core, 0.1))
        node.start()
        nodes.append(node)
    # node 0 is leader (smallest sequence number); 1 and 2 are workers
    wait_until(lambda: len(
        nodes[0].registry.get_all_service_addresses()) == 2)
    yield nodes
    for n in nodes:
        try:
            n.stop()
        except Exception:
            pass


class TestClusterEndToEnd:
    def test_roles_and_status(self, cluster, core):
        leader = cluster[0]
        assert leader.is_leader()
        assert http_get(leader.url + "/api/status") == b"I am the leader"
        assert http_get(cluster[1].url +
                        "/api/status") == b"I am a worker node"
        # leader is not in the worker pool (OnElectionAction.java:30)
        addrs = json.loads(http_get(leader.url + "/api/services"))
        assert sorted(addrs) == sorted([cluster[1].url, cluster[2].url])
        assert read_leader_info(leader.coord) == leader.url

    def test_upload_search_download_cycle(self, cluster):
        leader = cluster[0]
        docs = {
            "a.txt": b"the quick brown fox jumps over the lazy dog",
            "b.txt": b"a fast brown fox and a quick red fox",
            "c.txt": b"lorem ipsum dolor sit amet",
            "d.txt": b"the dog sleeps all day long",
        }
        for name, data in docs.items():
            resp = http_post(leader.url + f"/leader/upload?name={name}",
                             data, content_type="application/octet-stream")
            assert b"uploaded successfully" in resp

        # scatter-gather search, sum-merged, name-ordered (parity mode)
        result = json.loads(http_post(leader.url + "/leader/start",
                                      json.dumps({"query": "fox"}).encode()))
        assert set(result) == {"a.txt", "b.txt"}
        assert list(result) == sorted(result)   # reference TreeMap order
        assert all(v > 0 for v in result.values())
        # b.txt mentions fox twice → higher score
        assert result["b.txt"] > result["a.txt"]

        # download: leader probes workers for the document (Leader.java:127)
        got = http_get(leader.url + "/leader/download?path=c.txt")
        assert got == docs["c.txt"]

        # load balancing spread documents over both workers
        sizes = [int(http_get(w + "/worker/index-size"))
                 for w in json.loads(http_get(leader.url + "/api/services"))]
        assert all(s > 0 for s in sizes)

    def test_concurrent_same_name_uploads_place_once(self, cluster):
        """ADVICE r3 #1: concurrent uploads of the same NEW name must all
        route to ONE worker (tentative claim under the placement lock) —
        without it two handlers both miss the map and place twin copies
        that double-count in the scatter-gather sum-merge."""
        from concurrent.futures import ThreadPoolExecutor

        leader = cluster[0]

        def up(i):
            return http_post(
                leader.url + "/leader/upload?name=same.txt",
                f"unique pelican document copy {i}".encode(),
                content_type="application/octet-stream").decode()

        with ThreadPoolExecutor(8) as ex:
            res = list(ex.map(up, range(16)))
        assert all("uploaded successfully" in r for r in res)
        assert len({r.rsplit(": ", 1)[-1] for r in res}) == 1
        result = json.loads(http_post(
            leader.url + "/leader/start",
            json.dumps({"query": "pelican"}).encode()))
        assert list(result) == ["same.txt"]

    def test_bulk_upload_batch_and_nrt_visibility(self, cluster):
        """Framework addition: /leader/upload-batch places a whole batch
        with one request per worker; deferred (NRT) commits are flushed
        by the next search, so read-your-writes holds end to end."""
        leader = cluster[0]
        docs = [{"name": f"bulk{i}.txt",
                 "text": f"zebra stripe number {i} " + ("grass " * (i % 3))}
                for i in range(20)]
        resp = json.loads(http_post(leader.url + "/leader/upload-batch",
                                    json.dumps(docs).encode()))
        assert sum(resp["placed"].values()) == 20
        assert len(resp["placed"]) == 2          # spread over both workers
        result = json.loads(http_post(leader.url + "/leader/start",
                                      b"zebra"))
        assert len(result) > 0                   # visible without explicit
        names = set(result)                      # commit (NRT flush)
        assert names <= {d["name"] for d in docs}
        # re-upload an existing name: routes to the SAME worker (upsert,
        # not duplicate) — placement map, ADVICE r2
        orig = leader._placement["bulk0.txt"][0]
        one = [{"name": "bulk0.txt", "text": "entirely new content"}]
        resp2 = json.loads(http_post(leader.url + "/leader/upload-batch",
                                     json.dumps(one).encode()))
        assert list(resp2["placed"]) == [orig]
        # a doc the worker refuses (binary-looking text) is reported as
        # skipped, excluded from placed counts and the placement map
        bad = [{"name": "bad.pdf", "text": "%PDF-1.4 but no streams"},
               {"name": "good.txt", "text": "perfectly fine words"}]
        resp3 = json.loads(http_post(leader.url + "/leader/upload-batch",
                                     json.dumps(bad).encode()))
        assert sum(resp3["placed"].values()) == 1
        assert [s["name"] for s in resp3["skipped"]] == ["bad.pdf"]
        assert "bad.pdf" not in leader._placement
        assert "good.txt" in leader._placement

    def test_malformed_batch_rejected_without_state_leak(self, cluster):
        """A doc missing 'name' must 400 BEFORE any routing state is
        touched: a mid-planning KeyError would leak inflight counts and
        claims for already-routed docs, pinning those names to
        never-confirmed placements (code-review r4)."""
        leader = cluster[0]
        bad = [{"name": "leaky.txt", "text": "fine"}, {"text": "no name"}]
        with pytest.raises(urllib.error.HTTPError) as ei:
            http_post(leader.url + "/leader/upload-batch",
                      json.dumps(bad).encode())
        assert ei.value.code == 400
        assert "leaky.txt" not in leader._placement
        assert not any(n == "leaky.txt"
                       for n, _w in leader.placement._inflight)
        # the name is still placeable afterwards
        ok = [{"name": "leaky.txt", "text": "quokka sighting report"}]
        resp = json.loads(http_post(leader.url + "/leader/upload-batch",
                                    json.dumps(ok).encode()))
        assert sum(resp["placed"].values()) == 1
        result = json.loads(http_post(leader.url + "/leader/start",
                                      b"quokka"))
        assert list(result) == ["leaky.txt"]

    def test_settle_failure_cleans_phantom_placement(self, cluster):
        """When EVERY upload leg of a new name fails, the tentative
        placement must not survive: the last failing leg of a
        never-confirmed replica drops the phantom entry, so retries can
        re-place the name anywhere (code-review r4, generalized to
        R-way legs in cluster/placement.py)."""
        leader = cluster[0]
        # registry read BEFORE taking the placement lock: production
        # never nests these, and the lockdep witness holds tests to the
        # same ordering discipline as the code under test
        w = leader.registry.get_all_service_addresses()[0]
        pm = leader.placement
        with leader._placement_lock:
            reps, new = pm.route_locked("ghost.txt", [w], {w: 0},
                                        None, 1)
            assert reps == (w,) and new
            pm._track_leg("ghost.txt", w)   # concurrent sibling leg
        # first leg fails: the sibling is still in flight, keep state
        pm.leg_failure("ghost.txt", w)
        assert "ghost.txt" in leader._placement
        # sibling leg fails last: no leg ever confirmed — drop the
        # phantom placement entirely
        pm.leg_failure("ghost.txt", w)
        assert "ghost.txt" not in leader._placement
        assert not any(n == "ghost.txt" for n, _w in pm._inflight)

    def test_large_download_streams_with_bounded_reads(self, cluster):
        """A big document flows worker -> leader -> client in bounded
        chunks (Leader.java:95-151 FileSystemResource parity): no hop
        buffers the whole file, and the bytes survive the two-hop
        chunked proxy exactly."""
        import hashlib
        import os as _os

        leader, worker = cluster[0], cluster[1]
        # place a ~9MB file directly in a worker's documents dir (upload
        # paths are text-oriented; download must serve any bytes)
        blob = _os.urandom(1 << 20) * 9
        path = worker.engine._safe_doc_path("big.bin")
        _os.makedirs(_os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(blob)

        reads = []
        orig = worker.engine.open_document_stream

        def spying(rel):
            got = orig(rel)
            if got is None:
                return None
            stream, size = got

            class Spy:
                def read(self, n=-1):
                    buf = stream.read(n)
                    reads.append(len(buf))
                    return buf

                def close(self):
                    stream.close()
            return Spy(), size

        worker.engine.open_document_stream = spying
        try:
            got = http_get(leader.url + "/leader/download?path=big.bin",
                           timeout=60.0)
        finally:
            worker.engine.open_document_stream = orig
        assert hashlib.sha256(got).hexdigest() == \
            hashlib.sha256(blob).hexdigest()
        # the worker handler pulled bounded chunks, never the whole file
        assert reads and max(reads) <= (1 << 16)

    def test_pdf_upload_extracts_binary_upload_415(self, cluster):
        """Tika-parity contract over HTTP (Worker.java:198-212): a PDF
        becomes searchable text; a raw binary is refused with 415."""
        import urllib.error

        leader = cluster[0]
        pdf_stream = b"BT (uniquepdftoken inside document) Tj ET"
        pdf = (b"%PDF-1.4\nstream\n" + pdf_stream + b"endstream\n%%EOF")
        http_post(leader.url + "/leader/upload?name=doc.pdf", pdf,
                  content_type="application/octet-stream")
        res = json.loads(http_post(leader.url + "/leader/start",
                                   b"uniquepdftoken"))
        assert set(res) == {"doc.pdf"}
        elf = b"\x7fELF\x02\x01\x01" + bytes(64)
        with pytest.raises(urllib.error.HTTPError) as ei:
            http_post(leader.url + "/leader/upload?name=prog.bin", elf,
                      content_type="application/octet-stream")
        assert ei.value.code == 415

    def test_multipart_upload(self, cluster):
        leader = cluster[0]
        boundary = "XbOuNdArYX"
        body = (
            f"--{boundary}\r\n"
            'Content-Disposition: form-data; name="file"; '
            'filename="multi.txt"\r\n'
            "Content-Type: text/plain\r\n\r\n"
            "zebra stripes pattern\r\n"
            f"--{boundary}--\r\n").encode()
        resp = http_post(
            leader.url + "/leader/upload", body,
            content_type=f"multipart/form-data; boundary={boundary}")
        assert b"uploaded successfully" in resp
        result = json.loads(http_post(
            leader.url + "/leader/start",
            json.dumps({"query": "zebra"}).encode()))
        assert "multi.txt" in result

    def test_download_traversal_rejected(self, cluster):
        worker = cluster[1]
        req = urllib.request.Request(
            worker.url + "/worker/download?path=..%2F..%2Fetc%2Fpasswd")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 400

    def test_partial_results_on_worker_failure(self, cluster):
        """Per-worker failure tolerance (Leader.java:67-69): killing one
        worker must not break search; the other shard still answers."""
        leader = cluster[0]
        for name, text in [("x.txt", b"alpha beta"), ("y.txt", b"alpha gq")]:
            http_post(leader.url + f"/leader/upload?name={name}", text,
                      content_type="application/octet-stream")
        cluster[2].httpd.shutdown()   # data plane down, session still alive
        cluster[2].httpd.server_close()   # refuse new connections promptly
        result = json.loads(http_post(
            leader.url + "/leader/start",
            json.dumps({"query": "alpha"}).encode()))
        # at least the surviving worker's shard answered
        assert len(result) >= 1

    def test_leader_failover_end_to_end(self, cluster, core):
        """Kill the leader: a worker is promoted, publishes /leader_info,
        leaves the worker pool, and serves searches."""
        old_leader, w1 = cluster[0], cluster[1]
        http_post(old_leader.url + "/leader/upload?name=z.txt",
                  b"gamma delta", content_type="application/octet-stream")
        core.expire_session(old_leader.coord.sid)
        assert wait_until(lambda: w1.is_leader(), timeout=5.0)
        assert wait_until(
            lambda: read_leader_info(w1.coord) == w1.url, timeout=5.0)
        # new leader left the worker pool; only w2 remains registered
        assert wait_until(lambda: w1.registry.get_all_service_addresses()
                          == [cluster[2].url], timeout=5.0)
        result = json.loads(http_post(
            w1.url + "/leader/start",
            json.dumps({"query": "gamma"}).encode()))
        assert isinstance(result, dict)

    def test_fault_injection_on_scatter(self, cluster):
        """Armed fault point drops every worker RPC → empty results, no
        error (the reference's swallow-and-continue semantics)."""
        leader = cluster[0]
        http_post(leader.url + "/leader/upload?name=f.txt", b"epsilon zeta",
                  content_type="application/octet-stream")
        global_injector.arm("leader.worker_rpc", action="raise")
        try:
            result = json.loads(http_post(
                leader.url + "/leader/start",
                json.dumps({"query": "epsilon"}).encode()))
            assert result == {}
        finally:
            global_injector.disarm("leader.worker_rpc")

    def test_leader_download_traversal_rejected(self, cluster):
        req = urllib.request.Request(
            cluster[0].url + "/leader/download?path=..%2F..%2Fetc%2Fpasswd")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 400

    def test_metrics_exposed(self, cluster):
        leader = cluster[0]
        http_post(leader.url + "/leader/upload?name=m.txt", b"metric text",
                  content_type="application/octet-stream")
        snap = json.loads(http_get(leader.url + "/api/metrics"))
        assert snap.get("uploads_placed", 0) >= 1

    def test_topk_chunk_counters_exposed(self, cluster):
        """Every dispatched chunk adds its snapshot's padded top-k chunk
        count to ``topk_chunks`` (the dead ones among them to
        ``topk_chunks_skipped``, those ranked by group maxima to
        ``topk_chunks_grouped``): read from a worker's /api/metrics."""
        from tfidf_tpu.ops.topk import topk_chunk_counts
        leader, workers = cluster[0], cluster[1:]
        for i in range(12):     # three widths: several blocks a worker
            words = " ".join(f"w{i}x{j}" for j in range((2, 10, 20)[i % 3]))
            http_post(leader.url + f"/leader/upload?name=t{i:02d}.txt",
                      f"chunky {words}".encode(),
                      content_type="application/octet-stream")
        http_post(leader.url + "/leader/start", b"chunky")   # commits
        before = json.loads(http_get(workers[0].url + "/api/metrics"))
        hits = json.loads(http_post(leader.url + "/leader/start",
                                    b"chunky w0x0"))   # not the cached one
        after = json.loads(http_get(workers[0].url + "/api/metrics"))
        assert len(hits) == 10
        want = [0, 0, 0]
        for w in workers:       # one process: the workers share counters
            snap = w.engine.index.snapshot
            assert len(snap.ell_impacts) >= 2
            counts = topk_chunk_counts(
                [imp.shape[1] for imp in snap.ell_impacts],
                snap.ell_live_host, k=10)
            want = [a + b for a, b in zip(want, counts)]
        assert after["dispatch_chunks"] - before["dispatch_chunks"] == 2
        assert after["topk_chunks"] - before["topk_chunks"] == want[0]
        assert after["topk_chunks_skipped"] \
            - before["topk_chunks_skipped"] == want[1]
        # these blocks are narrower than eighty groups: none is grouped,
        # and the counter is there all the same
        assert after["topk_chunks_grouped"] \
            - before.get("topk_chunks_grouped", 0) == want[2] == 0


class TestBoundedClusterSearch:
    """r2: /worker/process serves exact top-k by default; the reference's
    unbounded ranking (Worker.java:230) is opt-in parity behavior."""

    def _fill(self, leader, n=25):
        for i in range(n):
            http_post(leader.url + f"/leader/upload?name=bulk{i:02d}.txt",
                      b"shared common token plus unique" +
                      str(i).encode() * 2,
                      content_type="application/octet-stream")

    def test_default_returns_top_k(self, cluster):
        leader = cluster[0]
        self._fill(leader)
        res = json.loads(http_post(leader.url + "/leader/start",
                                   b"shared common token"))
        assert 0 < len(res) <= leader.config.top_k

    def test_worker_response_is_bounded(self, cluster):
        leader = cluster[0]
        self._fill(leader)
        for w in leader.registry.get_all_service_addresses():
            hits = json.loads(http_post(w + "/worker/process", b"common"))
            assert len(hits) <= leader.config.top_k

    def test_unbounded_parity_flag(self, core, tmp_path):
        nodes = []
        try:
            for i in range(2):
                cfg = Config(
                    documents_path=str(tmp_path / f"ub{i}" / "documents"),
                    index_path=str(tmp_path / f"ub{i}" / "index"),
                    port=0, unbounded_results=True, top_k=2,
                    min_doc_capacity=64, min_nnz_capacity=1 << 12,
                    min_vocab_capacity=1 << 10, query_batch=4,
                    max_query_terms=8)
                node = SearchNode(cfg, coord=LocalCoordination(core, 0.1))
                node.start()
                nodes.append(node)
            leader = nodes[0]
            wait_until(lambda: len(
                leader.registry.get_all_service_addresses()) == 1)
            for i in range(6):
                http_post(
                    leader.url + f"/leader/upload?name=d{i}.txt",
                    b"same term everywhere",
                    content_type="application/octet-stream")
            res = json.loads(http_post(leader.url + "/leader/start",
                                       b"term"))
            assert len(res) == 6   # all matches, despite top_k=2
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass


class TestMeshCluster:
    """End-to-end: cluster nodes serving from the MESH engine — uploads
    commit into ShardedArrays and /leader/start answers through the
    shard_map psum/all_gather step (VERDICT r1 #1 'done' criterion)."""

    def test_leader_search_answers_through_mesh(self, core, tmp_path):
        from tfidf_tpu.parallel.mesh_index import MeshIndex
        nodes = []
        try:
            for i in range(2):
                cfg = Config(
                    documents_path=str(tmp_path / f"mesh{i}" / "documents"),
                    index_path=str(tmp_path / f"mesh{i}" / "index"),
                    port=0, engine_mode="mesh",
                    min_doc_capacity=64, min_nnz_capacity=1 << 12,
                    min_vocab_capacity=1 << 10, query_batch=4,
                    max_query_terms=8)
                node = SearchNode(cfg, coord=LocalCoordination(core, 0.1))
                node.start()
                nodes.append(node)
            leader, worker = nodes
            assert leader.is_leader()
            wait_until(lambda: len(
                leader.registry.get_all_service_addresses()) == 1)
            # the worker's engine really is mesh-backed
            assert isinstance(worker.engine.index, MeshIndex)
            assert worker.engine.index.mesh.devices.size == 8

            docs = {
                "a.txt": b"the quick brown fox jumps over the lazy dog",
                "b.txt": b"a fast brown fox and a quick red fox",
                "c.txt": b"lorem ipsum dolor sit amet",
                "d.txt": b"red dogs chase brown foxes at dawn",
            }
            for name, data in docs.items():
                http_post(leader.url + f"/leader/upload?name={name}", data,
                          content_type="application/octet-stream")
            # NRT commit policy: uploads defer the commit; the next
            # search flushes it (read-your-writes via commit_if_dirty)
            worker.commit_if_dirty()
            # committed into sharded device arrays, spread over the mesh
            snap = worker.engine.index.snapshot
            assert snap is not None and snap.total_live == 4
            counts = [sum(1 for d in sd if d.live)
                      for sd in worker.engine.index._shard_docs]
            assert sum(counts) == 4
            assert sum(1 for c in counts if c > 0) >= 2

            res = json.loads(http_post(leader.url + "/leader/start",
                                       b"brown fox"))
            assert set(res) == {"a.txt", "b.txt", "d.txt"}
            assert res["b.txt"] > res["a.txt"]   # two foxes beat one

            # delete-equivalent: upsert then search through the mesh again
            http_post(leader.url + "/leader/upload?name=a.txt",
                      b"totally different content now",
                      content_type="application/octet-stream")
            res = json.loads(http_post(leader.url + "/leader/start",
                                       b"brown fox"))
            assert set(res) == {"b.txt", "d.txt"}
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass


class TestScatterClient:
    """_ScatterClient retry/pruning semantics (code-review r4)."""

    def test_retries_stale_connection_not_timeout(self):
        import http.server
        import socket
        import threading

        from tfidf_tpu.cluster.node import _ScatterClient

        hits = []

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                hits.append(self.path)
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                body = b"[]"
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        c = _ScatterClient()
        try:
            assert c.post(base, "/worker/process", b"{}") == b"[]"
            # server restarts: the cached keep-alive connection is stale;
            # ONE transparent retry on a fresh connection must succeed
            srv.shutdown()
            srv.server_close()
            srv2 = http.server.ThreadingHTTPServer(
                ("127.0.0.1", srv.server_address[1]), H)
            threading.Thread(target=srv2.serve_forever,
                             daemon=True).start()
            assert c.post(base, "/worker/process", b"{}") == b"[]"
            srv2.shutdown()
            srv2.server_close()
        finally:
            pass
        # a connection-refused endpoint exhausts the single retry and
        # raises (never loops)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            dead = f"http://127.0.0.1:{s.getsockname()[1]}"
        with pytest.raises(Exception):
            c.post(dead, "/worker/process", b"{}")

    def test_prunes_departed_workers(self):
        from tfidf_tpu.cluster.node import _ScatterClient

        c = _ScatterClient()
        c._tls.conns = {"http://old:1": _FakeConn(),
                        "http://live:2": _FakeConn()}
        try:
            c.post("http://live:2", "/x", b"", live={"http://live:2"})
        except Exception:
            pass   # the fake conn fails the request; pruning is the point
        assert "http://old:1" not in c._tls.conns


class _FakeConn:
    closed = False

    def close(self):
        self.closed = True

    def request(self, *a, **kw):
        raise ConnectionResetError("fake")


class TestSizeCacheEviction:
    def test_stale_poll_cannot_resurrect_evicted_worker(self, cluster):
        """A worker evicted from the size cache during a poll must not
        re-enter it from that poll's pre-failure data (code-review r4)."""
        import time as _time

        leader = cluster[0]
        workers = leader.registry.get_all_service_addresses()
        w = workers[0]
        with leader._placement_lock:
            leader._size_cache = (0.0, {})   # force a fresh poll
        leader._ensure_sizes_fresh(workers)
        assert w in leader._size_cache[1]
        # simulate a failure-eviction racing a poll that started earlier
        with leader._placement_lock:
            leader._size_cache[1].pop(w, None)
            leader._evicted[w] = _time.monotonic() + 60.0   # "future"
            leader._size_cache = (0.0, leader._size_cache[1])
        leader._ensure_sizes_fresh(workers)
        assert w not in leader._size_cache[1]
        # once the eviction is old news, the next poll restores it
        with leader._placement_lock:
            leader._evicted[w] = _time.monotonic() - 1.0
            leader._size_cache = (0.0, leader._size_cache[1])
        leader._ensure_sizes_fresh(workers)
        assert w in leader._size_cache[1]
