"""Batched scatter-gather serving path (round-5 serving-gap work).

The leader coalesces concurrent ``/leader/start`` queries into one
``/worker/process-batch`` RPC per worker with a packed binary reply
(``cluster/wire.py``); these tests pin the wire format, the endpoint, and
the equivalence of the batched path with the per-query JSON path the
reference defines (``Leader.java:39-92``).
"""

import json
import threading

import pytest
from jax._src.pallas.mosaic.error_handling import MosaicError

from tfidf_tpu.cluster.coordination import CoordinationCore, LocalCoordination
from tfidf_tpu.cluster.node import SearchNode, http_post
from tfidf_tpu.cluster.wire import pack_hit_lists, unpack_hit_lists
from tfidf_tpu.engine.searcher import SearchHit
from tfidf_tpu.utils.config import Config

from tests.test_cluster import wait_until


class TestWireFormat:
    def test_roundtrip(self):
        lists = [
            [SearchHit("a.txt", 1.5), SearchHit("dir/b.txt", 0.25)],
            [],
            [SearchHit("unicode-ßø𝄞.txt", 3.75)],
            [SearchHit("", 0.0)],
        ]
        got = unpack_hit_lists(pack_hit_lists(lists))
        assert len(got) == len(lists)
        for want, have in zip(lists, got):
            assert [h.name for h in want] == [n for n, _ in have]
            for h, (_, s) in zip(want, have):
                assert s == pytest.approx(h.score, rel=1e-6)

    def test_empty_batch(self):
        assert unpack_hit_lists(pack_hit_lists([])) == []

    def test_corrupt_magic_rejected(self):
        data = bytearray(pack_hit_lists([[SearchHit("x", 1.0)]]))
        data[0] ^= 0xFF
        with pytest.raises(ValueError):
            unpack_hit_lists(bytes(data))

    def test_truncated_rejected(self):
        data = pack_hit_lists([[SearchHit("name.txt", 1.0)]])
        with pytest.raises(ValueError):
            unpack_hit_lists(data[:-3])

    def test_short_buffer_rejected_with_valueerror(self):
        """Buffers shorter than the 8-byte header (or the counts region
        the header promises) must raise ValueError per the wire
        contract — not struct.error (ADVICE r5)."""
        data = pack_hit_lists([[SearchHit("name.txt", 1.0)]])
        for cut in (b"", b"\x31", data[:4], data[:7]):
            with pytest.raises(ValueError):
                unpack_hit_lists(cut)
        # header intact but counts region missing
        with pytest.raises(ValueError):
            unpack_hit_lists(data[:8])


@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


def _mk_cluster(core, tmp_path, n=3, **cfg_kw):
    nodes = []
    for i in range(n):
        cfg = Config(
            documents_path=str(tmp_path / f"sc{i}" / "documents"),
            index_path=str(tmp_path / f"sc{i}" / "index"),
            port=0, min_doc_capacity=64, min_nnz_capacity=1 << 12,
            min_vocab_capacity=1 << 10, query_batch=8, max_query_terms=8,
            # single-copy placement: this suite pins the scatter layer's
            # per-shard tolerance; R-way failover has its own suite
            **{"replication_factor": 1, **cfg_kw})
        node = SearchNode(cfg, coord=LocalCoordination(core, 0.1))
        node.start()
        nodes.append(node)
    wait_until(lambda: len(
        nodes[0].registry.get_all_service_addresses()) == n - 1)
    return nodes


def _stop_all(nodes):
    for nd in nodes:
        try:
            nd.stop()
        except Exception:
            pass


DOCS = {
    "a.txt": b"apple banana cherry apple",
    "b.txt": b"banana date elderberry",
    "c.txt": b"apple fig grape banana banana",
    "d.txt": b"cherry date apple apple apple",
    "e.txt": b"solo unique token here",
}


class TestProcessBatchEndpoint:
    def test_packed_reply_matches_per_query_json(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path)
        try:
            leader = nodes[0]
            for name, data in DOCS.items():
                http_post(leader.url + f"/leader/upload?name={name}", data,
                          content_type="application/octet-stream")
            queries = ["apple", "banana date", "nosuchterm", "cherry"]
            for w in leader.registry.get_all_service_addresses():
                packed = http_post(
                    w + "/worker/process-batch",
                    json.dumps({"queries": queries, "k": 10}).encode())
                batch = unpack_hit_lists(packed)
                assert len(batch) == len(queries)
                for q, hits in zip(queries, batch):
                    singles = json.loads(http_post(
                        w + "/worker/process",
                        json.dumps({"query": q}).encode()))
                    assert [(h["document"]["name"],
                             pytest.approx(h["score"], rel=1e-5))
                            for h in singles] == hits
        finally:
            _stop_all(nodes)


class TestScatterBatchedLeader:
    def test_batched_equals_per_query_path(self, core, tmp_path):
        """The coalesced scatter must return exactly what the reference's
        per-query fan-out shape returns, for every query."""
        nodes = _mk_cluster(core, tmp_path, result_order="name")
        try:
            leader = nodes[0]
            for name, data in DOCS.items():
                http_post(leader.url + f"/leader/upload?name={name}", data,
                          content_type="application/octet-stream")
            queries = ["apple", "banana", "apple banana", "date",
                       "nosuchterm", "solo unique"]
            assert leader.scatter_batcher is not None
            batched = {}
            threads = []

            def run(q):
                batched[q] = json.loads(http_post(
                    leader.url + "/leader/start",
                    json.dumps({"query": q}).encode()))

            for q in queries:   # concurrent: exercises real coalescing
                t = threading.Thread(target=run, args=(q,))
                t.start()
                threads.append(t)
            for t in threads:
                t.join()

            # reference-shaped per-query fan-out on the same cluster
            sb, leader.scatter_batcher = leader.scatter_batcher, None
            try:
                for q in queries:
                    want = leader.leader_search(q)
                    have = batched[q]
                    assert list(have) == list(want), q
                    for n in want:
                        assert have[n] == pytest.approx(want[n], rel=1e-5)
            finally:
                leader.scatter_batcher = sb
        finally:
            _stop_all(nodes)

    def test_partial_results_on_worker_death(self, core, tmp_path):
        """A dead worker's shard drops out of the batched scatter
        (partial results, Leader.java:67-69 / ServiceRegistry watch
        semantics), never an error. Session expiry shrinks the registry,
        and the scatter client prunes its idle keep-alive socket.
        Recovery is disabled to isolate the scatter layer's tolerance
        (tests/test_shard_recovery.py covers the re-placement path)."""
        nodes = _mk_cluster(core, tmp_path, shard_recovery=False)
        try:
            leader = nodes[0]
            for name, data in DOCS.items():
                http_post(leader.url + f"/leader/upload?name={name}", data,
                          content_type="application/octet-stream")
            full = json.loads(http_post(leader.url + "/leader/start",
                                        b"apple banana"))
            assert full
            victim = nodes[1]
            victim_names = [n for n, ws in leader._placement.items()
                            if victim.url in ws]
            assert victim_names   # placement spread both workers
            core.expire_session(victim.coord.sid)
            assert wait_until(lambda: leader.registry
                              .get_all_service_addresses()
                              == [nodes[2].url])
            res = json.loads(http_post(leader.url + "/leader/start",
                                       b"apple banana"))
            assert set(res).isdisjoint(victim_names)
            assert set(res) == set(full) - set(victim_names)
        finally:
            _stop_all(nodes)

    def test_unbounded_config_uses_per_query_path(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=2, unbounded_results=True)
        try:
            assert nodes[0].scatter_batcher is None
        finally:
            _stop_all(nodes)


class TestNrtCommitBarrier:
    def test_search_waits_for_inflight_commit(self, core, tmp_path):
        """Read-your-writes under concurrency: a search that finds the
        dirty flag already cleared by a sibling must WAIT for that
        sibling's in-flight commit, not serve the pre-upload snapshot
        (the race that surfaced as silently-partial batched scatters)."""
        import time

        cfg = Config(
            documents_path=str(tmp_path / "nrt" / "documents"),
            index_path=str(tmp_path / "nrt" / "index"),
            port=0, micro_batch=False, scatter_micro_batch=False,
            min_doc_capacity=64, min_nnz_capacity=1 << 12,
            min_vocab_capacity=1 << 10, query_batch=4, max_query_terms=8)
        node = SearchNode(cfg, coord=LocalCoordination(core, 0.1))
        node.start()
        try:
            node.engine.ingest_text("n.txt", "needle haystack")
            node.notify_write()
            orig = node.engine.commit
            started = threading.Event()

            def slow_commit():
                started.set()
                time.sleep(0.3)
                orig()

            node.engine.commit = slow_commit
            t = threading.Thread(target=node.worker_search,
                                 args=("needle",))
            t.start()
            assert started.wait(2.0)
            # this search arrives mid-commit with the flag already clear
            hits = node.worker_search("needle")
            t.join()
            assert any(h.name == "n.txt" for h in hits)
        finally:
            node.stop()


class TestCompileFlakeRetry:
    def test_batch_search_retries_once_on_compile_error(self, core,
                                                        tmp_path):
        """A compile failure must not degrade a batch to empty
        results: it classifies as a compute fault ("compile") and the
        pure search retries once within its per-bucket budget."""
        cfg = Config(
            documents_path=str(tmp_path / "cf" / "documents"),
            index_path=str(tmp_path / "cf" / "index"),
            port=0, min_doc_capacity=64, min_nnz_capacity=1 << 12,
            min_vocab_capacity=1 << 10, query_batch=4, max_query_terms=8)
        node = SearchNode(cfg, coord=LocalCoordination(core, 0.1)).start()
        try:
            node.engine.ingest_text("a.txt", "needle body")
            node.engine.commit()
            orig = node.engine.search_batch
            calls = {"n": 0}

            def flaky(queries, k=None, unbounded=False):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise MosaicError(
                        "INTERNAL: Mosaic failed to compile TPU "
                        "kernel: cannot statically prove that index "
                        "in dimension 0 is a multiple of 8")
                return orig(queries, k=k, unbounded=unbounded)

            node.engine.search_batch = flaky
            hits = node.worker_search_batch(["needle"])
            assert calls["n"] == 2
            assert [h.name for h in hits[0]] == ["a.txt"]

            # non-compile errors propagate immediately (no blind retry)
            calls["n"] = 0

            def broken(queries, k=None, unbounded=False):
                calls["n"] += 1
                raise ValueError("scoring exploded")

            node.engine.search_batch = broken
            with pytest.raises(ValueError):
                node.worker_search_batch(["needle"])
            assert calls["n"] == 1
        finally:
            node.stop()
