"""Hybrid retrieval (ISSUE 17): dense embedding scoring beside sparse
TF-IDF, fused top-k with exact oracle gates.

The acceptance story, layer by layer:

- the dense top-k kernel (``ops/dense.py``) matches a numpy brute-force
  oracle on every shape edge — dim not a multiple of 128, one live doc,
  empty column, k > live docs, chunked scan vs one-shot;
- the fusion algebra (``cluster/fusion.py``) matches an INDEPENDENT
  pure-python re-derivation of RRF and weighted-sum in this file;
- the embedding column rides the checkpoint storage seam: bit-exact
  round-trip, re-embed fallback on a signature change, and the
  corruption matrix (a torn ``embeddings.npz`` quarantines the version
  and falls back to an older intact one);
- the two-stage cluster plan matches a single-node hybrid oracle
  EXACTLY — including through a worker killed mid-fleet (failover
  slices re-issue BOTH stages) and through a rebalance drain flip;
- the ``mode`` field is an additive wire-v3 surface: absent means
  sparse (a v2 request is untouched), a staged reply carries 2n lists,
  and a misaligned reply degrades honestly via the slot-count check.

The slow chaos job (``make chaos-hybrid``) kills a worker's data plane
mid-hybrid-scatter under zipfian load: every reply must be exact or
honestly degraded, never silently partial.
"""

import json
import random
import threading
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from tests.test_cluster import wait_until
from tests.test_replication import (_CFG, _mk_cluster, _node, _stop_all,
                                    _upload_docs)
from tfidf_tpu.cluster import fusion
from tfidf_tpu.cluster.node import http_get, http_post
from tfidf_tpu.cluster.wire import pack_hit_lists, unpack_hit_lists
from tfidf_tpu.engine.checkpoint import (load_checkpoint,
                                         restore_checkpoint,
                                         save_checkpoint)
from tfidf_tpu.engine.dense import EmbeddingColumn
from tfidf_tpu.engine.embedder import HashEmbedder, get_embedder
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics


@pytest.fixture
def core():
    from tfidf_tpu.cluster.coordination import CoordinationCore
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


DOCS = {f"hy{i}.txt": f"common token{i} word{i % 3} extra{i % 5}"
        for i in range(12)}
QUERIES = ["common", "token3 word0", "word1 extra2", "common token7"]

_ENGINE_KEYS = ("top_k", "min_doc_capacity", "min_nnz_capacity",
                "min_vocab_capacity", "query_batch", "max_query_terms")


def _engine(tmp_path, tag, **kw):
    cfg_kw = {k: v for k, v in _CFG.items() if k in _ENGINE_KEYS}
    cfg_kw.update(kw)
    cfg = Config(documents_path=str(tmp_path / tag / "documents"),
                 index_path=str(tmp_path / tag / "index"), **cfg_kw)
    e = Engine(cfg)
    for n, t in DOCS.items():
        e.ingest_text(n, t)
    e.commit()
    return e


def _order(merged, k):
    return dict(sorted(merged.items(), key=lambda kv: (-kv[1], kv[0]))[:k])


def _hybrid_oracle(tmp_path, tag, mode, method, queries=QUERIES):
    """Single-node staged oracle: full-corpus engine, both stages run
    locally, fused with the SAME fusion module the leader uses (the
    fusion algebra itself is gated against an independent re-derivation
    in TestFusionOracle below)."""
    eng = _engine(tmp_path, tag)
    c = eng.config
    out = {}
    for q in queries:
        sparse = {h.name: float(h.score) for h in eng.search(q, k=c.top_k)}
        dense = dict(eng.search_dense_batch([q], k=c.top_k)[0])
        if mode == "dense":
            out[q] = _order(dense, c.top_k)
        else:
            out[q] = _order(fusion.fuse(
                sparse, dense, method=method, k=c.top_k,
                rrf_k=c.fusion_rrf_k, w_sparse=c.fusion_weight_sparse,
                w_dense=c.fusion_weight_dense), c.top_k)
    return out


def _post_search(leader, q, mode=None, method=None):
    """POST /leader/start returning (body, reply headers)."""
    body = {"query": q}
    if mode is not None:
        body["mode"] = mode
    if method is not None:
        body["fusion"] = method
    req = urllib.request.Request(
        leader.url + "/leader/start", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30.0) as r:
        return json.loads(r.read()), dict(r.headers)


def _kill_data_plane(victim):
    """HTTP down, session alive (the in-process stand-in for kill -9's
    RST — same idiom as tests/test_replication.py): the registry still
    lists the worker, so only WITHIN-REQUEST failover keeps results
    complete."""
    victim.httpd.shutdown()
    victim.httpd.server_close()
    cls = victim.httpd.RequestHandlerClass

    def dead(handler):
        raise ConnectionResetError("worker killed (test)")
    cls.do_POST = dead
    cls.do_GET = dead


def _assert_parity(got, want, ctx=""):
    assert set(got) == set(want), \
        f"{ctx}: missing={set(want) - set(got)} extra={set(got) - set(want)}"
    for n, s in want.items():
        assert got[n] == pytest.approx(s, rel=1e-5), (ctx, n, got[n], s)


# ---------------------------------------------------------------------------
# Dense kernel vs numpy brute force — every shape edge
# ---------------------------------------------------------------------------

def _mk_column(num_docs, dim, chunk=1 << 14, min_cap=8):
    col = EmbeddingColumn(HashEmbedder(dim), min_doc_capacity=min_cap,
                          chunk=chunk)
    for i in range(num_docs):
        col.upsert(f"d{i:04d}", {f"tok{i}": 1.0, f"shared{i % 4}": 2.0,
                                 "common": 0.5})
    col.commit()
    return col


def _numpy_oracle(col, counts, k):
    """Brute-force cosine top-k over the column's host vectors, ranked
    (-score, name) — fully independent of the jit kernel."""
    names = sorted(col._vecs)
    if not names:
        return []
    rows = np.stack([col._vecs[n] for n in names]).astype(np.float64)
    q = col.embedder.embed_query(counts).astype(np.float64)
    scores = rows @ q
    ranked = sorted(zip(names, scores), key=lambda kv: (-kv[1], kv[0]))
    return [(n, float(s)) for n, s in ranked[:k]]


class TestDenseKernelOracle:
    @pytest.mark.parametrize("num_docs,dim,k,chunk", [
        (1, 40, 5, 1 << 14),      # one live doc, dim far from %128
        (7, 64, 3, 1 << 14),      # sub-lane dim, k < docs
        (12, 96, 32, 1 << 14),    # k > live docs
        (200, 130, 10, 64),       # chunked scan, dim just over one lane
        (300, 128, 7, 4),         # chunk < k: clamped to k rows
    ])
    def test_matches_numpy_bruteforce(self, num_docs, dim, k, chunk):
        col = _mk_column(num_docs, dim, chunk=chunk)
        queries = [{"common": 1.0, "tok3": 2.0}, {"shared1": 1.0}]
        got = col.search_batch(queries, k)
        for qi, counts in enumerate(queries):
            want = _numpy_oracle(col, counts, k)
            assert [n for n, _ in got[qi]] == [n for n, _ in want], \
                (num_docs, dim, k, chunk, qi)
            for (gn, gs), (wn, ws) in zip(got[qi], want):
                assert gs == pytest.approx(ws, rel=1e-5, abs=1e-6)

    def test_empty_column(self):
        col = EmbeddingColumn(HashEmbedder(64), min_doc_capacity=8)
        col.commit()
        assert col.search_batch([{"a": 1.0}, {"b": 2.0}], 5) == [[], []]

    def test_chunked_equals_oneshot(self):
        """What ``ops/dense.py`` promises of the chunk scan: the same
        winners in the same order, whatever the chunking. Scores are
        held to an ULP, not to the bit — a [B, 32] and a [B, 512]
        matmul may accumulate a row's dot in different orders."""
        one = _mk_column(257, 64, chunk=1 << 14)
        chk = _mk_column(257, 64, chunk=32)
        q = [{"common": 1.0, "tok17": 3.0}]
        (a,), (b,) = one.search_batch(q, 11), chk.search_batch(q, 11)
        assert [n for n, _ in a] == [n for n, _ in b]
        for (_, sa), (_, sb) in zip(a, b):
            assert sa == pytest.approx(sb, rel=2e-7, abs=1e-9)

    def test_negative_cosines_survive_the_wire(self):
        """Signed-hash cosines are legitimately negative; the packed
        hit-list wire must carry them (the arrays fast path would drop
        scores <= 0 — dense never rides it)."""
        col = _mk_column(30, 32)
        rows = np.stack([col._vecs[n] for n in sorted(col._vecs)])
        token = next(t for t in (f"neg{i}" for i in range(500))
                     if (rows @ col.embedder.embed_counts({t: 1.0})
                         ).min() < -1e-3)
        hits = col.search_batch([{token: 1.0}], 30)[0]
        lists = unpack_hit_lists(pack_hit_lists([hits]))
        assert lists[0] == [(n, pytest.approx(s, rel=1e-6))
                            for n, s in hits]
        assert any(s < 0 for _, s in hits)   # the edge is actually hit

    def test_delete_then_commit_drops_doc(self):
        col = _mk_column(10, 64)
        assert col.delete("d0003")
        col.commit()
        names = [n for n, _ in col.search_batch([{"common": 1.0}], 10)[0]]
        assert "d0003" not in names and len(names) == 9


# ---------------------------------------------------------------------------
# Fusion algebra vs an independent pure-python re-derivation
# ---------------------------------------------------------------------------

def _ref_rrf(sparse, dense, rrf_k, ws, wd, k):
    """Independent RRF reference (re-derived from the paper's formula,
    not from cluster/fusion.py)."""
    s_ranked = sorted(sparse.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    d_ranked = sorted(dense.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    out = {}
    for i, (n, _) in enumerate(s_ranked):
        out[n] = out.get(n, 0.0) + ws * (1.0 / (rrf_k + i + 1))
    for i, (n, _) in enumerate(d_ranked):
        out[n] = out.get(n, 0.0) + wd * (1.0 / (rrf_k + i + 1))
    return out


def _ref_wsum(sparse, dense, ws, wd, k):
    out = {}
    for weight, stage in ((ws, sparse), (wd, dense)):
        ranked = sorted(stage.items(),
                        key=lambda kv: (-kv[1], kv[0]))[:k]
        if not ranked:
            continue
        vals = [s for _, s in ranked]
        lo, hi = min(vals), max(vals)
        for n, s in ranked:
            norm = 1.0 if hi <= lo else (s - lo) / (hi - lo)
            out[n] = out.get(n, 0.0) + weight * norm
    return out


class TestFusionOracle:
    def _stages(self, seed, n_s=20, n_d=20, overlap=8):
        rng = random.Random(seed)
        names = [f"doc{i:03d}" for i in range(40)]
        sparse = {n: rng.uniform(0.0, 12.0)
                  for n in rng.sample(names, n_s)}
        dense = {n: rng.uniform(-1.0, 1.0)
                 for n in rng.sample(names[:overlap] + names[20:], n_d)}
        return sparse, dense

    @pytest.mark.parametrize("seed", range(5))
    def test_rrf_matches_reference(self, seed):
        sparse, dense = self._stages(seed)
        got = fusion.fuse(sparse, dense, method="rrf", k=10,
                          rrf_k=60.0, w_sparse=0.7, w_dense=0.3)
        want = _ref_rrf(sparse, dense, 60.0, 0.7, 0.3, 10)
        assert set(got) == set(want)
        for n in want:
            assert got[n] == pytest.approx(want[n], rel=1e-12)

    @pytest.mark.parametrize("seed", range(5))
    def test_wsum_matches_reference(self, seed):
        sparse, dense = self._stages(seed)
        got = fusion.fuse(sparse, dense, method="wsum", k=10,
                          w_sparse=0.4, w_dense=0.6)
        want = _ref_wsum(sparse, dense, 0.4, 0.6, 10)
        assert set(got) == set(want)
        for n in want:
            assert got[n] == pytest.approx(want[n], rel=1e-12)

    def test_wsum_all_tied_stage_gets_full_credit(self):
        got = fusion.fuse({"a": 2.0, "b": 2.0}, {}, method="wsum",
                          k=5, w_sparse=0.5, w_dense=0.5)
        assert got == {"a": 0.5, "b": 0.5}

    def test_empty_stages(self):
        assert fusion.fuse({}, {}, method="rrf", k=5) == {}
        got = fusion.fuse({}, {"a": 0.3}, method="wsum", k=5)
        assert got == {"a": pytest.approx(0.5)}

    def test_unknown_method_raises(self):
        with pytest.raises(ValueError, match="unknown fusion method"):
            fusion.fuse({}, {}, method="borda", k=5)


# ---------------------------------------------------------------------------
# Engine integration + checkpoint seam
# ---------------------------------------------------------------------------

class TestEngineDense:
    def test_dense_search_through_engine(self, tmp_path):
        eng = _engine(tmp_path, "eng")
        hits = eng.search_dense_batch(["common token3"], k=5)[0]
        assert hits and hits == sorted(hits,
                                       key=lambda kv: (-kv[1], kv[0]))
        stats = eng.dense_stats()
        assert stats["model"] == "hash" and stats["docs"] == len(DOCS)
        assert stats["dim"] == eng.config.embedding_dim
        assert stats["bytes"] > 0

    def test_disabled_plane_is_loud(self, tmp_path):
        eng = _engine(tmp_path, "off", embedding_enabled=False)
        assert eng.dense_stats() is None
        with pytest.raises(RuntimeError, match="dense plane disabled"):
            eng.search_dense_batch(["common"], k=5)

    def test_delete_reaches_dense_plane(self, tmp_path):
        eng = _engine(tmp_path, "del")
        victim = next(iter(DOCS))
        assert eng.delete(victim)
        eng.commit()
        names = {n for n, _ in
                 eng.search_dense_batch(["common"], k=50)[0]}
        assert victim not in names


class TestCheckpointDense:
    def test_roundtrip_bit_exact(self, tmp_path):
        eng = _engine(tmp_path, "ck")
        ckpt = str(tmp_path / "ckpt")
        save_checkpoint(eng, ckpt)
        before = global_metrics.get("checkpoint_dense_reembeds")
        e2 = load_checkpoint(ckpt, eng.config)
        assert global_metrics.get("checkpoint_dense_reembeds") == before
        r1, n1 = eng.dense.export_arrays()
        r2, n2 = e2.dense.export_arrays()
        assert n1 == n2 and np.array_equal(r1, r2)
        assert eng.search_dense_batch(QUERIES, k=8) == \
            e2.search_dense_batch(QUERIES, k=8)

    def test_signature_change_reembeds(self, tmp_path):
        eng = _engine(tmp_path, "sig", embedding_dim=64)
        ckpt = str(tmp_path / "ckpt")
        save_checkpoint(eng, ckpt)
        before = global_metrics.get("checkpoint_dense_reembeds")
        cfg32 = eng.config.replace(embedding_dim=32)
        e2 = load_checkpoint(ckpt, cfg32)
        assert global_metrics.get("checkpoint_dense_reembeds") \
            == before + 1
        # the re-embedded column equals a fresh dim-32 ingest exactly
        fresh = _engine(tmp_path, "sig32", embedding_dim=32)
        r1, n1 = e2.dense.export_arrays()
        r2, n2 = fresh.dense.export_arrays()
        assert n1 == n2 and np.allclose(r1, r2, rtol=1e-6)

    def test_corrupt_embeddings_falls_back_to_intact_version(
            self, tmp_path):
        import os
        eng = _engine(tmp_path, "corr", storage_keep_versions=3)
        ckpt = str(tmp_path / "ckpt")
        save_checkpoint(eng, ckpt)          # .v1 — intact fallback
        eng.ingest_text("late.txt", "late arrival pelican")
        eng.commit()
        save_checkpoint(eng, ckpt)          # .v2 — to be corrupted
        with open(str(tmp_path / "ckpt.v2" / "embeddings.npz"),
                  "r+b") as f:
            f.seek(12)
            f.write(b"\xde\xad\xbe\xef")
        before = global_metrics.get("checkpoint_fallbacks")
        e2, meta = restore_checkpoint(ckpt, eng.config)
        assert global_metrics.get("checkpoint_fallbacks") == before + 1
        # fell back to .v1: pre-corruption corpus, dense plane intact
        assert e2.index.num_live_docs == len(DOCS)
        assert any(os.path.isdir(str(tmp_path / d))
                   for d in os.listdir(str(tmp_path))
                   if d.startswith("ckpt.v2.quarantine"))
        hits = e2.search_dense_batch(["common"], k=5)[0]
        assert hits


# ---------------------------------------------------------------------------
# Cluster: two-stage plan vs single-node oracle, wire surfaces
# ---------------------------------------------------------------------------

class TestHybridCluster:
    def test_hybrid_matches_single_node_oracle(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader, DOCS)
            for method in fusion.FUSION_METHODS:
                want = _hybrid_oracle(tmp_path, f"ho-{method}",
                                      "hybrid", method)
                for q in QUERIES:
                    got, hdrs = _post_search(leader, q, mode="hybrid",
                                             method=method)
                    _assert_parity(got, want[q], ctx=f"{method}:{q}")
                    assert hdrs.get("X-Search-Stages", "").startswith(
                        f"sparse,dense; fusion={method}")
                    assert hdrs.get("X-Proto-Version") == "4"
        finally:
            _stop_all(nodes)

    def test_dense_mode_matches_oracle(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader, DOCS)
            want = _hybrid_oracle(tmp_path, "do", "dense", "rrf")
            for q in QUERIES:
                got, hdrs = _post_search(leader, q, mode="dense")
                _assert_parity(got, want[q], ctx=f"dense:{q}")
                assert hdrs.get("X-Search-Stages") == "dense"
        finally:
            _stop_all(nodes)

    def test_sparse_requests_are_unstamped_and_unchanged(self, core,
                                                         tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader, DOCS)
            got, hdrs = _post_search(leader, "common")   # no mode field
            assert "X-Search-Stages" not in hdrs
            assert got
        finally:
            _stop_all(nodes)

    def test_bad_mode_and_fusion_reject_400(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=2)
        try:
            leader = nodes[0]
            for body in ({"query": "x", "mode": "ann"},
                         {"query": "x", "mode": "hybrid",
                          "fusion": "borda"}):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    http_post(leader.url + "/leader/start",
                              json.dumps(body).encode())
                assert ei.value.code == 400
        finally:
            _stop_all(nodes)

    def test_disabled_dense_plane_rejects_staged_modes(self, core,
                                                       tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=2,
                            embedding_enabled=False)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                http_post(nodes[0].url + "/leader/start",
                          json.dumps({"query": "x",
                                      "mode": "hybrid"}).encode())
            assert ei.value.code == 400
        finally:
            _stop_all(nodes)

    def test_worker_staged_wire_is_2n_lists(self, core, tmp_path):
        """The wire-v3 staged reply layout, asserted at the worker RPC
        itself: n sparse lists then n dense lists; mode absent -> the
        v2 reply (n lists) byte-layout."""
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader, DOCS)
            worker = leader.registry.get_all_service_addresses()[0]
            staged = unpack_hit_lists(http_post(
                worker + "/worker/process-batch",
                json.dumps({"queries": QUERIES[:2], "k": 5,
                            "mode": "hybrid"}).encode()))
            assert len(staged) == 4
            legacy = unpack_hit_lists(http_post(
                worker + "/worker/process-batch",
                json.dumps({"queries": QUERIES[:2], "k": 5}).encode()))
            assert len(legacy) == 2
            # sparse slots of the staged reply == the legacy reply
            assert staged[:2] == legacy
            # dense-mode reply keeps the slot layout: n EMPTY sparse
            # lists ahead of the dense stage
            dense = unpack_hit_lists(http_post(
                worker + "/worker/process-batch",
                json.dumps({"queries": QUERIES[:2], "k": 5,
                            "mode": "dense"}).encode()))
            assert len(dense) == 4 and dense[0] == [] and dense[1] == []
            assert dense[2] and dense[3]
        finally:
            _stop_all(nodes)

    def test_health_reports_embedding_column(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=2)
        try:
            _upload_docs(nodes[0], DOCS)
            for nd in nodes:
                h = json.loads(http_get(nd.url + "/api/health"))
                emb = h["embedding"]
                assert emb["model"] == "hash"
                assert emb["dim"] == nd.config.embedding_dim
        finally:
            _stop_all(nodes)


class TestHybridFailover:
    def test_hybrid_exact_through_worker_death(self, core, tmp_path):
        """A worker killed mid-fleet: failover slices re-issue BOTH
        stages (the slice request carries ``mode``), so hybrid results
        stay in exact oracle parity with zero degraded replies."""
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader, DOCS)
            want = _hybrid_oracle(tmp_path, "fo", "hybrid", "rrf")
            for q in QUERIES:
                got, _ = _post_search(leader, q, mode="hybrid",
                                      method="rrf")
                _assert_parity(got, want[q], ctx=f"pre:{q}")

            _kill_data_plane(nodes[1])
            before = global_metrics.get("scatter_failovers")
            for _ in range(3):
                for q in QUERIES:
                    got, hdrs = _post_search(leader, q, mode="hybrid",
                                             method="rrf")
                    _assert_parity(got, want[q], ctx=f"post:{q}")
                    assert "X-Scatter-Degraded" not in hdrs
            # the death was really exercised: either within-request
            # failover re-issued slices, or the dead worker's breaker
            # opened first (background sweeps race the first query) and
            # owner assignment routed around it pre-dispatch
            assert (global_metrics.get("scatter_failovers") > before
                    or global_metrics.get("scatter_last_circuit_open")
                    > 0)
        finally:
            _stop_all(nodes)

    def test_misaligned_staged_reply_fails_over(self, core, tmp_path):
        """A v2-style worker that ignores ``mode`` replies n lists where
        the leader expects 2n: the slot-count check must treat it as a
        failed worker (failover covers it) — never merge a misaligned
        reply as if the dense stage were empty."""
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader, DOCS)
            want = _hybrid_oracle(tmp_path, "mis", "hybrid", "rrf")
            victim = nodes[1]

            def v2_reply(queries, k=None, mode="hybrid", deadline=None):
                return victim.worker_search_batch_wire(
                    queries, k=k, deadline=deadline)
            victim.worker_search_staged_wire = v2_reply
            before = global_metrics.get("scatter_failures")
            for q in QUERIES:
                got, _ = _post_search(leader, q, mode="hybrid",
                                      method="rrf")
                _assert_parity(got, want[q], ctx=f"v2:{q}")
            assert global_metrics.get("scatter_failures") > before
        finally:
            _stop_all(nodes)

    def test_hybrid_exact_through_rebalance_flip(self, core, tmp_path):
        """Drain a full-corpus worker onto a freshly joined one: the
        flip changes ownership mid-fleet and hybrid parity must hold at
        every step (the drain target receives the whole corpus before
        any flip, so post-flip owners are full-corpus shards too)."""
        nodes = _mk_cluster(core, tmp_path, n=3, replication_factor=2)
        try:
            leader = nodes[0]
            victim = nodes[1]
            _upload_docs(leader, DOCS)
            want = _hybrid_oracle(tmp_path, "rb", "hybrid", "rrf")
            joined = _node(core, tmp_path, 9, replication_factor=2)
            nodes.append(joined)
            wait_until(lambda: len(
                leader.registry.get_all_service_addresses()) == 3)
            resp = json.loads(http_post(
                leader.url + "/api/drain",
                json.dumps({"worker": victim.url}).encode()))
            assert resp["draining"] is True

            # Known (ROADMAP D12): on a loaded host about one run in
            # five fails a ``during:`` check below, documents or one
            # stage's hits missing — a search planned before the
            # ownership flip reaches the drained worker after the
            # post-flip /worker/delete. The product's race, not this
            # wait's: the checks stay as they are.
            def drained():
                for q in QUERIES:   # exact parity DURING the drain
                    got, _ = _post_search(leader, q, mode="hybrid",
                                          method="rrf")
                    _assert_parity(got, want[q], ctx=f"during:{q}")
                st = json.loads(http_get(
                    leader.url + "/api/drain?worker="
                    + urllib.parse.quote(victim.url)))
                return st["drained"]
            assert wait_until(drained, timeout=30.0)
            for q in QUERIES:
                got, _ = _post_search(leader, q, mode="hybrid",
                                      method="rrf")
                _assert_parity(got, want[q], ctx=f"post:{q}")
        finally:
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# Chaos (slow): kill -9 the owner mid-hybrid-scatter under zipfian load
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestHybridChaos:
    def test_owner_killed_mid_scatter_under_zipfian_load(self, core,
                                                         tmp_path):
        """``make chaos-hybrid``: hybrid queries under a zipfian query
        distribution while a worker's data plane dies mid-flight. The
        contract is exact-or-honestly-degraded: every 200 either
        matches the oracle or carries ``X-Scatter-Degraded``."""
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader, DOCS)
            want = _hybrid_oracle(tmp_path, "chaos", "hybrid", "rrf")
            rng = random.Random(17)
            weights = [1.0 / (i + 1) for i in range(len(QUERIES))]
            stop = threading.Event()
            bad: list = []
            done = [0]

            def client():
                while not stop.is_set():
                    q = rng.choices(QUERIES, weights=weights)[0]
                    try:
                        got, hdrs = _post_search(leader, q,
                                                 mode="hybrid",
                                                 method="rrf")
                    except urllib.error.URLError:
                        continue   # shed/refused is honest too
                    if "X-Scatter-Degraded" not in hdrs:
                        try:
                            _assert_parity(got, want[q], ctx=q)
                        except AssertionError as e:
                            bad.append(e)
                    done[0] += 1

            threads = [threading.Thread(target=client)
                       for _ in range(4)]
            for t in threads:
                t.start()
            try:
                wait_until(lambda: done[0] > 20, timeout=20.0)
                _kill_data_plane(nodes[1])   # mid-flight
                wait_until(lambda: done[0] > 120, timeout=30.0)
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=10.0)
            assert not bad, bad[0]
            assert done[0] > 120
        finally:
            _stop_all(nodes)


class TestEmbedderContract:
    def test_hash_embedder_is_process_stable(self):
        """blake2b of the token STRING — replica-identical regardless of
        per-worker vocab insertion order (the invariant failover
        exactness rests on)."""
        a, b = HashEmbedder(64), HashEmbedder(64)
        counts = {"pelican": 2.0, "common": 1.0, "zebra": 0.5}
        assert np.array_equal(a.embed_counts(counts),
                              b.embed_counts(dict(reversed(
                                  list(counts.items())))))
        v = a.embed_counts(counts)
        assert np.linalg.norm(v) == pytest.approx(1.0, rel=1e-6)
        assert np.array_equal(a.embed_counts({}),
                              np.zeros(64, np.float32))

    def test_registry(self):
        emb = get_embedder("hash", 48)
        assert emb.signature() == {"model": "hash", "dim": 48}
        with pytest.raises(ValueError, match="unknown embedding model"):
            get_embedder("bert", 64)

    def test_register_embedder_plugs_in(self):
        """The pluggability seam: a registered factory is selectable by
        name (Config-style), and a dim mismatch is refused loudly."""
        from tfidf_tpu.engine.embedder import (_REGISTRY, Embedder,
                                               register_embedder)

        class _Stub(Embedder):
            name = "stub-encoder"

            def __init__(self, dim):
                self.dim = dim

            def embed_counts(self, counts):
                v = np.zeros(self.dim, np.float32)
                v[0] = 1.0
                return v

        register_embedder("stub-encoder", _Stub)
        try:
            emb = get_embedder("stub-encoder", 16)
            assert isinstance(emb, _Stub)
            assert emb.signature() == {"model": "stub-encoder",
                                       "dim": 16}
            assert emb.embed_query({"x": 1.0})[0] == 1.0
            bad = type("_Lying", (_Stub,), {})
            bad.__init__ = lambda self, dim: setattr(self, "dim", 8)
            register_embedder("stub-encoder", bad)
            with pytest.raises(ValueError, match="built dim 8"):
                get_embedder("stub-encoder", 16)
        finally:
            _REGISTRY.pop("stub-encoder", None)


# ---------------------------------------------------------------------------
# Mesh-sharded dense search (parallel/mesh_dense.py) vs the same oracle
# ---------------------------------------------------------------------------

class TestMeshDense:
    def test_sharded_matches_bruteforce(self):
        """Embedding rows sharded over a 4-wide docs axis (uneven
        shards, so padding + ``base`` offsets are both exercised) must
        reproduce the single-host numpy oracle exactly: global top-k is
        contained in the union of per-shard top-ks."""
        from tfidf_tpu.ops.topk import unpack_topk
        from tfidf_tpu.parallel.mesh import make_mesh
        from tfidf_tpu.parallel.mesh_dense import (make_mesh_dense_search,
                                                   shard_dense_column)

        dim, k = 72, 6
        col = _mk_column(22, dim)
        names = sorted(col._vecs)
        rows = np.stack([col._vecs[n] for n in names]).astype(np.float32)

        mesh = make_mesh((4, 2))
        dim_pad = -(-dim // 128) * 128
        # uneven split: 7 / 7 / 7 / 1 rows — shard-major order is the
        # name-table order ids map back through
        cuts = [0, 7, 14, 21, len(names)]
        shards = [rows[cuts[i]:cuts[i + 1]] for i in range(4)]
        emb, live, base = shard_dense_column(mesh, shards, dim_pad)
        search = make_mesh_dense_search(mesh, k=k)

        queries = [{"common": 1.0, "tok3": 2.0}, {"shared1": 1.0},
                   {"tok21": 1.0}]
        q = np.zeros((len(queries), dim_pad), np.float32)
        for i, counts in enumerate(queries):
            q[i, :dim] = col.embedder.embed_query(counts)
        packed = search(q, emb, live, base)
        vals, ids = unpack_topk(packed)
        for qi, counts in enumerate(queries):
            want = _numpy_oracle(col, counts, k)
            got = [(names[int(d)], float(v))
                   for v, d in zip(vals[qi], ids[qi])]
            assert [n for n, _ in got] == [n for n, _ in want], qi
            for (_, gs), (_, ws) in zip(got, want):
                assert gs == pytest.approx(ws, rel=1e-5)

    def test_shard_count_mismatch_refused(self):
        from tfidf_tpu.parallel.mesh import make_mesh
        from tfidf_tpu.parallel.mesh_dense import shard_dense_column

        mesh = make_mesh((4, 2))
        with pytest.raises(ValueError, match="3 shards"):
            shard_dense_column(
                mesh, [np.zeros((2, 8), np.float32)] * 3, 128)
