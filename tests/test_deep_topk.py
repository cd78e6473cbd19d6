"""The 1,000-deep run file, held to its plain reference on the CPU.

``msmarco2m-top1000`` asks every query for 1,000 passages where the other
configurations ask for 10, and the depth takes other routes through the
same code: ``ops/topk.py _block_topk`` pads a block narrower than the
depth back to it with ``-inf`` lanes whose ids are 0, every chunk goes
straight through ``lax.top_k``, ``merge_topk`` ranks thousands of
candidates a query, ``merge_packed`` does so again over stretches and the
mesh over shards, and a query of rare terms fills only part of the depth.
Every path is compared with ``tests/bm25_reference.py`` (dense, float64,
a full ``np.lexsort``) on one seeded corpus of 2,540 documents in which
EQUAL scores are the rule: every tf is 1 and a document has one of three
lengths, so a query's ranking is a few plateaus of hundreds of documents
and the cut at 1,000 falls inside one. A tie goes to the lower document:
the lower row of the index (on the mesh: shard, then row).

Documents compare with ``==`` and scores with ``rel=1e-5``: the system
sums a query's float32 impacts (through three exact bfloat16 passes on
the kernel) where the reference sums float64, which differ by a few
units of 2**-24 = 6e-8 a term (read here: 8e-8 at most); 1e-5 is a
hundred times that and still a hundredth of the gap between two
plateaus.
"""

import jax
import numpy as np
import pytest

from tests.bm25_reference import Bm25Reference
from tfidf_tpu.cluster.coordination import (CoordinationCore,
                                            LocalCoordination)
from tfidf_tpu.cluster.node import SearchNode
from tfidf_tpu.cluster.wire import pack_hit_lists, unpack_hit_lists
from tfidf_tpu.engine import searcher as searcher_mod
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.ops.topk import (TOPK_CHUNK, TOPK_SUBGROUP,
                                packed_topk_chunked, topk_chunk_counts,
                                topk_widths, unpack_topk)
from tfidf_tpu.parallel.mesh import make_mesh
from tfidf_tpu.utils.config import Config

VOCAB = 64
K1, B_ = 0.9, 0.4               # the configuration's
DEPTH = 1000
# (documents, distinct terms each), in the commit's row order: blocks of
# 2048 and 1024 rows (as wide as the depth or wider) and of 512 and 256
# (narrower: the pad path), the last one mostly dead tail
CROWDS = [(1500, 20), (700, 14), (300, 10), (40, 5)]
BLOCK_ROWS = [2048, 1024, 512, 256]
LENGTHS = (24.0, 30.0, 36.0)
# t60-t63 are drawn by no document; t60 is given to the two documents on
# each side of every block boundary (and so of every stretch boundary)
# with the same length: one plateau of twelve across all four blocks
EDGE_TERM, NO_DOC_TERM = 60, 63
EDGES = [1498, 1499, 1500, 1501, 2198, 2199, 2200, 2201, 2498, 2499,
         2500, 2501]

QUERIES = {
    # >= 1,000 matches, two plateaus: the cut falls inside the second
    "deep_one_term": "t0",
    "deep_two_terms": "t0 t1",
    "deep_repeated_term": "t5 t9 t9",
    "deep_five_terms": "t2 t3 t4 t6 t7",
    # fewer matches than the depth: exactly those come back
    "short_393": "t40",
    "short_two_rare_terms": "t59 t58",
    "edge_plateau_across_blocks": f"t{EDGE_TERM}",
    # none: a term no document holds, a token outside the vocabulary
    "none_in_corpus": f"t{NO_DOC_TERM}",
    "none_in_vocabulary": "zzz",
}
NAMES = list(QUERIES)


def _make_docs(crowds=CROWDS, edges=EDGES):
    rng = np.random.default_rng(38)
    p = 1.0 / np.arange(1, VOCAB - 3) ** 0.9
    docs, lengths = [], []
    for count, n_terms in crowds:
        for _ in range(count):
            ids = rng.choice(VOCAB - 4, size=n_terms, replace=False,
                             p=p / p.sum())
            docs.append({int(t): 1.0 for t in ids})
            lengths.append(LENGTHS[int(rng.integers(len(LENGTHS)))])
    for d in edges:
        # one term swapped for the edge term: the document's distinct
        # count, and so its block, stays
        del docs[d][max(docs[d])]
        docs[d][EDGE_TERM] = 1.0
        lengths[d] = LENGTHS[1]
    return docs, lengths


def _load(engine, docs, lengths):
    for t in range(VOCAB):
        engine.vocab.add(f"t{t}")
    for i, d in enumerate(docs):
        ids = np.asarray(sorted(d), np.int32)
        engine.index.add_document_arrays(
            f"d{i}", ids, np.asarray([d[t] for t in ids], np.float32),
            lengths[i])
    engine.commit()


def _config(tmp, **extra) -> Config:
    return Config(documents_path=str(tmp / "documents"),
                  index_path=str(tmp / "index"), min_doc_capacity=256,
                  min_nnz_capacity=1 << 15, min_vocab_capacity=128,
                  query_batch=16, embedding_enabled=False, bm25_k1=K1,
                  bm25_b=B_, port=0, **extra)


def _places(names) -> np.ndarray:
    """``position`` for the reference: document -> its place in the
    index's own order, from the index's list of names in that order."""
    live = [int(n[1:]) for n in names if n is not None]
    place = np.empty(len(live), np.int64)
    place[live] = np.arange(len(live))
    return place


class Deep:
    """The corpus, its reference, and one engine a deployment: one chip
    (``local``) and one mesh worker over four (``mesh``)."""

    def __init__(self, tmp) -> None:
        self.docs, self.lengths = _make_docs()
        self.ref = Bm25Reference(self.docs, self.lengths, vocab=VOCAB,
                                 k1=K1, b=B_)
        self.local = Engine(_config(tmp / "local"))
        _load(self.local, self.docs, self.lengths)
        self.mesh = Engine(
            _config(tmp / "mesh", engine_mode="mesh", mesh_shape=(4, 1)),
            mesh=make_mesh((4, 1), devices=jax.devices()[:4]))
        _load(self.mesh, self.docs, self.lengths)
        snap = self.local.index.snapshot
        msnap = self.mesh.index.snapshot
        self.place = {
            "local": _places(snap.doc_names[:snap.num_names]),
            "mesh": _places(msnap.doc_names)}
        self._runs: dict = {}

    def want(self, name: str, k: int, order: str = "local"):
        return [(f"d{d}", s) for d, s in self.ref.run(
            QUERIES[name], k, self.place[order])]

    def runs(self, door: str, k: int) -> dict[str, list]:
        """Every query of ``QUERIES`` through ``door`` at depth ``k``,
        as ONE batch, once a module."""
        if (door, k) not in self._runs:
            self._runs[door, k] = dict(zip(
                NAMES, DOORS[door](self, list(QUERIES.values()), k)))
        return self._runs[door, k]


def _search_batch(deep, queries, k):
    return [[(h.name, h.score) for h in hits]
            for hits in deep.local.search_batch(queries, k=k)]


def _search_arrays(deep, queries, k):
    """``Engine.search_batch_arrays`` read as its docstring says: ``ids``
    index ``names``, an entry whose value is not finite or <= 0 is dead."""
    vals, ids, kk, names = deep.local.search_batch_arrays(queries, k=k)
    assert vals.shape == ids.shape == (len(queries), kk)
    return [[(names[i], float(v)) for v, i in zip(row_v, row_i)
             if np.isfinite(v) and v > 0]
            for row_v, row_i in zip(vals, ids)]


def _stretched(deep, queries, k):
    """``search_batch`` with a stretch budget under any block's scores:
    every block is a stretch of its own, and ``merge_packed`` joins four
    packed top-k lists of depth ``k``."""
    s = deep.local.searcher
    mp = pytest.MonkeyPatch()
    mp.setattr(searcher_mod, "stretch_budget", lambda *_a: 1)
    s._plans = {}
    try:
        plan = s._stretch_plan(deep.local.index.snapshot,
                               s._batch_cap(len(queries)))
        assert [(st.first, st.stop) for st in plan] == [
            (0, 1), (1, 2), (2, 3), (3, 4)]
        return _search_batch(deep, queries, k)
    finally:
        mp.undo()
        s._plans = {}


def _wire(deep, queries, k):
    """A served worker's ``/worker/process-batch`` reply body (the arrays
    packed straight into the wire layout), decoded as the leader does."""
    node = SearchNode(deep.local.config,
                      coord=LocalCoordination(
                          CoordinationCore(session_timeout_s=5.0), 0.1),
                      engine=deep.local)
    return unpack_hit_lists(node.worker_search_batch_wire(queries, k=k))


def _mesh_worker(deep, queries, k):
    """The mesh worker's reply: hit objects, then ``pack_hit_lists``."""
    return unpack_hit_lists(pack_hit_lists(
        deep.mesh.search_batch(queries, k=k)))


DOORS = {"search_batch": _search_batch, "search_arrays": _search_arrays,
         "stretched": _stretched, "wire": _wire,
         "mesh_worker": _mesh_worker}


@pytest.fixture(scope="module")
def deep(tmp_path_factory):
    return Deep(tmp_path_factory.mktemp("deep"))


def _same(got, want):
    assert [n for n, _s in got] == [n for n, _s in want]
    assert [s for _n, s in got] == pytest.approx(
        [s for _n, s in want], rel=1e-5)


def test_corpus_has_the_shape_the_cases_need(deep):
    snap = deep.local.index.snapshot
    assert [imp.shape[1] for imp in snap.ell_impacts] == BLOCK_ROWS
    assert snap.ell_live_host == tuple(c for c, _n in CROWDS)
    assert deep.local.compute_stats()["kernel_blocks"] == 4
    # rows lie in the order the documents went in, so the edge
    # documents sit on the block boundaries
    assert snap.doc_names[1499:1501] == ["d1499", "d1500"]
    msnap = deep.mesh.index.snapshot
    assert msnap.stride >= DEPTH      # a shard can hold the depth ...
    assert all(len(sd) < DEPTH for sd in msnap.shard_docs)   # ... unfilled
    # plateaus: the deep queries' 1,000 hits hold a handful of scores
    for name in NAMES[:2]:
        want = deep.want(name, DEPTH)
        assert len(want) == DEPTH
        assert len({s for _n, s in want}) <= 5
        # and the cut falls INSIDE a plateau: rank 1,001 scores the same
        assert deep.ref.run(QUERIES[name], DEPTH + 1,
                            deep.place["local"])[-1][1] == want[-1][1]
    assert len(deep.want("short_393", DEPTH)) == 393
    # the edge plateau: one score, one document each side of a boundary
    edge = deep.want("edge_plateau_across_blocks", DEPTH)
    assert [n for n, _s in edge] == [f"d{d}" for d in EDGES]
    assert len({s for _n, s in edge}) == 1


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("door", list(DOORS))
def test_depth_1000_equals_the_reference(deep, door, name):
    """Every door at the depth, query by query: the same documents in
    the same order, ties to the lower document across chunk, block,
    stretch and shard boundaries; a query with fewer matches returns
    exactly those, one with none returns none."""
    order = "mesh" if door == "mesh_worker" else "local"
    _same(deep.runs(door, DEPTH)[name], deep.want(name, DEPTH, order))


@pytest.mark.parametrize("door", list(DOORS))
@pytest.mark.parametrize("k", [10, 5000])
def test_other_depths_equal_the_reference(deep, door, k):
    """The control depth, and a depth past the corpus (2,540 documents;
    past a mesh shard's 1,280 rows too): every match comes back."""
    order = "mesh" if door == "mesh_worker" else "local"
    got = deep.runs(door, k)
    for name in NAMES:
        _same(got[name], deep.want(name, k, order))
    if k == 5000:
        assert len(got["deep_one_term"]) == 2478


def test_doors_agree_with_each_other(deep):
    """One chip's doors give the same lists to the bit; the mesh worker
    the same scores rank by rank and the same documents above the cut's
    plateau (its own document order breaks the ties inside it)."""
    base = deep.runs("search_batch", DEPTH)
    for door in ("search_arrays", "stretched", "wire"):
        assert deep.runs(door, DEPTH) == base, door
    mesh = deep.runs("mesh_worker", DEPTH)
    for name in NAMES:
        a, m = base[name], mesh[name]
        assert [s for _n, s in m] == pytest.approx([s for _n, s in a],
                                                   rel=1e-6)
        floor = a[-1][1] * (1 + 1e-6) if len(a) == DEPTH else 0.0
        assert {n for n, s in m if s > floor} \
            == {n for n, s in a if s > floor}


def test_wire_reply_round_trips_1000_hits(deep):
    got = deep.runs("wire", DEPTH)
    assert [len(got[n]) for n in NAMES[:4]] == [DEPTH] * 4
    # float32 scores survive the wire to the bit, names to the letter
    again = unpack_hit_lists(pack_hit_lists(
        [got[n] for n in NAMES]))
    assert again == [got[n] for n in NAMES]


@pytest.mark.parametrize("chunk", [256, 1024, TOPK_CHUNK])
def test_chunked_topk_pads_and_merges_at_depth(deep, chunk):
    """``packed_topk_chunked(k=1000)`` on the engine's own score blocks,
    the doc axis in chunks of 256 (EVERY chunk narrower than the depth:
    21 of them padded from 256 lanes to 1,000, and the plateaus straddle
    chunk boundaries), of 1,024 (the 2048-row block in two chunks of
    1,000 of 1,024) and whole. A pad lane is ``-inf`` with id 0: it
    displaces no live entry and reaches no reply as document 0."""
    s = deep.local.searcher
    snap = deep.local.index.snapshot
    queries = list(QUERIES.values())
    qb, _widest = s._vectorize(queries, s._batch_cap(len(queries)))
    blocks, live, _host = s._score_chunk(snap, qb)
    vals, ids = unpack_topk(np.asarray(packed_topk_chunked(
        blocks, live, k=DEPTH, chunk=chunk)))
    assert vals.shape == ids.shape == (16, DEPTH)
    n_docs = snap.num_names
    for row, name in enumerate(NAMES):
        # 2,540 live rows > the depth: every lane holds a live entry,
        # and a live entry's score is finite (a non-match scores 0.0)
        assert np.isfinite(vals[row]).all(), name
        assert (ids[row] < n_docs).all()
        assert len(set(ids[row].tolist())) == DEPTH     # no row twice
        got = [(snap.doc_names[i], float(v))
               for v, i in zip(vals[row], ids[row]) if v > 0]
        _same(got, deep.want(name, DEPTH))
        # document 0 is in a reply only where the reference has it
        assert ("d0" in [n for n, _s in got]) \
            == ("d0" in [n for n, _s in deep.want(name, DEPTH)])


def test_hit_counters_say_how_full_the_depth_is(deep):
    """``hit_slots`` moves by queries x depth and ``hits_built`` by the
    reference's hit count: four of these nine queries fill the depth,
    the rest 393, 533, 12, 0 and 0 of it."""
    from tfidf_tpu.utils.metrics import global_metrics
    before = global_metrics.snapshot()
    deep.local.search_batch(list(QUERIES.values()), k=DEPTH)
    after = global_metrics.snapshot()
    want = [len(deep.want(name, DEPTH)) for name in NAMES]
    assert want == [DEPTH] * 4 + [393, 533, 12, 0, 0]
    assert after["hit_slots"] - before.get("hit_slots", 0) \
        == len(NAMES) * DEPTH
    assert after["hits_built"] - before.get("hits_built", 0) == sum(want)


# ---- a corpus WIDE enough for the selection by candidates (PR 39): the
# blocks above are one small sort each at any depth; 40,000 documents of
# five terms fill a 65,536-row block whose 8,192 sub-groups of 8 rows
# outnumber the depth eight times, so its top-1,000 goes by sub-group
# maxima. Same vocabulary, same three lengths: plateaus of thousands.

WIDE_CROWDS = [(300, 14), (40000, 5)]
WIDE_EDGES = [298, 299, 300, 301]      # the edge plateau, across the blocks


class Wide:
    def __init__(self, tmp) -> None:
        self.docs, self.lengths = _make_docs(WIDE_CROWDS, WIDE_EDGES)
        self.ref = Bm25Reference(self.docs, self.lengths, vocab=VOCAB,
                                 k1=K1, b=B_)
        self.local = Engine(_config(tmp / "wide"))
        _load(self.local, self.docs, self.lengths)
        snap = self.local.index.snapshot
        self.place = _places(snap.doc_names[:snap.num_names])


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    return Wide(tmp_path_factory.mktemp("wide"))


@pytest.mark.parametrize("door", ["search_batch", "search_arrays", "wire"])
def test_depth_1000_by_candidates_equals_the_reference(wide, door):
    """The one-chip doors over a block that takes the deep route: the
    same 1,000 documents in the same order as the reference, the cut
    inside a plateau of thousands, ``topk_chunks_grouped`` counting the
    window."""
    from tfidf_tpu.utils.metrics import global_metrics
    snap = wide.local.index.snapshot
    caps = [imp.shape[1] for imp in snap.ell_impacts]
    assert caps == [512, 65536]
    assert topk_widths(65536, 65536, DEPTH) == (TOPK_SUBGROUP,)
    assert topk_chunk_counts(caps, snap.ell_live_host,
                             k=DEPTH) == (2, 0, 1)
    before = global_metrics.snapshot().get("topk_chunks_grouped", 0)
    got = DOORS[door](wide, list(QUERIES.values()), DEPTH)
    assert global_metrics.snapshot()["topk_chunks_grouped"] - before == 1
    for name, hits in zip(NAMES, got):
        _same(hits, [(f"d{d}", s) for d, s in wide.ref.run(
            QUERIES[name], DEPTH, wide.place)])
    assert [len(h) for h in got] == [DEPTH] * 6 + [4, 0, 0]
    # the cut falls inside a plateau: rank 1,001 scores as rank 1,000
    for name in NAMES[:2]:
        run = wide.ref.run(QUERIES[name], DEPTH + 1, wide.place)
        assert run[-1][1] == run[-2][1]
