"""The stretched step: a chunk's ELL blocks scored and ranked a STRETCH
at a time, so that the live score space is bounded by the device and not
by the corpus (``ops/ell.py`` ``ELL_BLOCK_ROWS_MAX`` / ``plan_stretches``
/ ``stretch_budget``, ``engine/searcher.py`` ``_dispatch_ell``).

One engine with the row ceiling at 256 rows and a stretch budget of
three blocks' scores at B = 1 (under one block's at B = 8), so that every search
here runs 3+ stretches over: a live COO residual (block 0, first stretch
only), rungs of several blocks whose last one has a dead tail, and
twelve documents of IDENTICAL postings laid across a block boundary, so
that one query's ten winners tie exactly and straddle two stretches.
Every block has 256 rows, so all of them ride the (interpreted) kernel.
"""

import json
import math
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfidf_tpu.cluster.coordination import (CoordinationCore,
                                            LocalCoordination)
from tfidf_tpu.cluster.node import SearchNode
from tfidf_tpu.engine import searcher as searcher_mod
from tfidf_tpu.engine.checkpoint import load_checkpoint, save_checkpoint
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.ops import ell
from tfidf_tpu.ops.ell import (build_ell_from_coo, ell_layout_gauges,
                               ell_scores_to_real, plan_stretches,
                               stretch_budget)
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 3000
CEILING = 256           # rows a block, for the engine of this file
WIDTH_CAP = 32          # so that a document of 40 terms spills
BUDGET = 4 * 3 * CEILING  # bytes: three blocks' [1, 256] f32 scores
K1, B_ = 1.2, 0.75
TIE_TERM = VOCAB - 1    # only the twelve tied documents hold it
# (distinct terms, documents) by rung, in row order: the two spilling
# ones first; rung 32: 300 rows = blocks of 256 + 44; rung 24: 600 rows =
# 256 + 256 + 88 (every row 20 terms wide, so its order is insertion
# order); rung 8: 400 = 256 + 144
CROWDS = [(45, 1), (40, 1), (30, 300), (20, 600), (6, 400)]
# the tied documents' places among the 600 of rung 24: across row 256
TIED = range(250, 262)


def _make_docs():
    rng = np.random.default_rng(32)
    p = 1.0 / np.arange(1, VOCAB) ** 0.7       # TIE_TERM is never drawn
    docs = []
    for n_terms, count in CROWDS:
        for j in range(count):
            if n_terms == 20 and j in TIED:
                ids = np.arange(100, 119).tolist() + [TIE_TERM]
                docs.append(dict.fromkeys(ids, 2.0))
                continue
            ids = np.sort(rng.choice(VOCAB - 1, size=n_terms,
                                     replace=False, p=p / p.sum()))
            tfs = rng.integers(1, 6, size=n_terms).astype(float)
            docs.append(dict(zip(ids.tolist(), tfs.tolist())))
    return docs


@pytest.fixture(scope="module")
def stretched(tmp_path_factory):
    """(engine, docs, lengths): the ceiling and the budget stay patched
    for the module (a later commit, a restore and every dispatch read
    them)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(ell, "ELL_BLOCK_ROWS_MAX", CEILING)
    mp.setattr(searcher_mod, "stretch_budget", lambda *_a: BUDGET)
    tmp = tmp_path_factory.mktemp("stretched")
    cfg = Config(documents_path=str(tmp / "documents"),
                 index_path=str(tmp / "index"), min_doc_capacity=256, min_nnz_capacity=1 << 15,
                 min_vocab_capacity=1 << 12, query_batch=32,
                 ell_width_cap=WIDTH_CAP, embedding_enabled=False,
                 bm25_k1=K1, bm25_b=B_)
    engine = Engine(cfg)
    for t in range(VOCAB):
        engine.vocab.add(f"t{t}")
    docs = _make_docs()
    lengths = [sum(d.values()) for d in docs]
    for i, d in enumerate(docs):
        ids = np.asarray(sorted(d), np.int32)
        engine.index.add_document_arrays(
            f"d{i}", ids, np.asarray([d[t] for t in ids], np.float32),
            lengths[i])
    engine.commit()
    yield engine, docs, lengths
    mp.undo()


def _queries(n: int, seed: int = 0) -> list[str]:
    """``n`` queries: the tie query first, then random ones of 1-6 terms
    with a repeated term now and then (a multiplicity of 2)."""
    rng = np.random.default_rng([32, seed])
    out = [f"t{TIE_TERM}"]
    while len(out) < n:
        terms = rng.integers(0, 400, size=rng.integers(1, 7)).tolist()
        if len(out) % 3 == 0:
            terms.append(terms[0])
        out.append(" ".join(f"t{t}" for t in terms))
    return out[:n]


def _whole_space_topk(engine, queries, k=10):
    """``lax.top_k`` of the materialised ``[B, doc_cap]`` matrix: every
    block scored by ONE program and gathered into document order."""
    s = engine.searcher
    snap = engine.index.snapshot
    qb, _widest = s._vectorize(queries, s._batch_cap(len(queries)))
    blocks, live, _ = s._score_chunk(snap, qb)
    real = ell_scores_to_real(blocks, live, snap.doc_len.shape[0])
    col = jnp.arange(real.shape[1])[None, :]
    vals, ids = jax.lax.top_k(
        jnp.where(col < snap.num_names, real, -jnp.inf), k)
    return np.asarray(vals)[:len(queries)], np.asarray(ids)[:len(queries)]


def test_layout_is_split_at_the_ceiling(stretched):
    engine, docs, _lengths = stretched
    snap = engine.index.snapshot
    shapes = [imp.shape for imp in snap.ell_impacts]
    # a block is held width-major: [width, rows_cap]
    assert [w for w, _r in shapes] == [32] * 2 + [24] * 3 + [8] * 2
    assert all(rows == CEILING for _w, rows in shapes)
    # the last block of every rung has a dead tail; 302 = the two
    # spilling rows and the 300 of their rung
    assert snap.ell_live_host == (256, 302 - 256, 256, 256, 88, 256, 144)
    assert snap.res_nnz == (45 - 32) + (40 - 32)
    stats = engine.compute_stats()
    assert stats["kernel_blocks"] == stats["posting_blocks"] == 7
    g = ell_layout_gauges(shapes, snap.ell_live_host,
                          np.asarray(snap.res_doc)[:snap.res_nnz])
    assert g["ell_blocks"] == 7 and g["ell_rows_padded"] == 7 * CEILING
    assert g["ell_entries_padded"] == CEILING * (2 * 32 + 3 * 24 + 2 * 8)
    assert g["ell_residual_nnz"] == 21 and g["ell_residual_docs"] == 2


# B=1: stretches of three blocks (3, 3, 1); B=8 and 32: a block each
@pytest.mark.parametrize("n_queries, stretches", [(1, 3), (8, 7), (32, 7)])
@pytest.mark.parametrize("mode", ("inline", "executor"))
def test_stretches_equal_the_whole_space(stretched, n_queries, stretches,
                                         mode):
    """(a) values bit-equal, ids equal, whatever the stretches — through
    the pipeline executor as through the inline loop."""
    engine, _docs, _lengths = stretched
    queries = _queries(n_queries, seed=n_queries)
    engine.searcher.pipeline_mode = mode
    try:
        before = global_metrics.snapshot()
        vals, ids, kk, _names = engine.searcher.search_arrays(queries)
        after = global_metrics.snapshot()
    finally:
        engine.searcher.pipeline_mode = "auto"
    assert kk == 10
    assert after["score_stretches"] - before.get("score_stretches", 0) \
        == stretches
    assert after["dispatch_chunks"] - before.get("dispatch_chunks", 0) == 1
    want_v, want_i = _whole_space_topk(engine, queries)
    assert np.array_equal(vals.view(np.uint32), want_v.view(np.uint32))
    assert np.array_equal(ids, want_i)


def test_tied_winners_straddle_a_stretch_boundary(stretched):
    engine, _docs, _lengths = stretched
    snap = engine.index.snapshot
    vals, ids, _kk, names = engine.searcher.search_arrays(_queries(8))
    # the twelve tied rows start 250 rows into rung 24, after the 302
    # rows of rung 32: six end one block (a stretch at B = 8), the rest
    # begin the next; the ten winners are the ten lowest rows
    first = 302 + TIED.start
    assert ids[0].tolist() == list(range(first, first + 10))
    bounds = np.cumsum(snap.ell_live_host)
    assert bounds[2] == first + 6
    assert len(set(vals[0].tolist())) == 1 and vals[0][0] > 0
    assert [names[i] for i in ids[0]] == [
        f"d{i}" for i in range(first, first + 10)]


def _bm25_reference(docs, lengths, query: dict, k1: float, b: float):
    """Textbook BM25 in float64 (what ``benchmarks/lib/oracle.py``
    states): nothing of the program is used here."""
    n = len(docs)
    avgdl = sum(lengths) / n
    out = np.zeros(n)
    for t, mult in query.items():
        df = sum(1 for d in docs if t in d)
        if not df:
            continue
        idf = math.log1p((n - df + 0.5) / (df + 0.5))
        for i, d in enumerate(docs):
            tf = d.get(t, 0.0)
            if tf:
                out[i] += mult * idf * tf / (
                    tf + k1 * (1 - b + b * lengths[i] / avgdl))
    return out


@pytest.mark.parametrize("n_queries", (1, 8, 32))
def test_stretches_against_float64_bm25(stretched, n_queries):
    """(b) the comparison and the limits of ``benchmarks/lib/oracle.py``:
    as many hits as the reference has positive scores in its top 10,
    every returned document's score within 9.0e-4 of the reference's
    score of THAT document, the sorted scores within 9.0e-4 of the
    reference's top 10 rank by rank."""
    engine, docs, lengths = stretched
    queries = _queries(n_queries, seed=100 + n_queries)
    got = engine.search_batch(queries)
    doc_err = rank_err = 0.0
    for q, hits in zip(queries, got):
        counts: dict = {}
        for tok in q.split():
            counts[int(tok[1:])] = counts.get(int(tok[1:]), 0) + 1
        ref = _bm25_reference(docs, lengths, counts, K1, B_)
        want = np.sort(ref)[::-1][:10]
        want = want[want > 0]
        have = np.asarray([h.score for h in hits])
        assert have.shape == want.shape, q
        if not have.size:
            continue
        of_doc = ref[[int(h.name[1:]) for h in hits]]
        assert (of_doc > 0).all(), q
        doc_err = max(doc_err, float(np.max(np.abs(have - of_doc) / of_doc)))
        rank_err = max(rank_err, float(np.max(
            np.abs(np.sort(have)[::-1] - want) / want)))
    assert doc_err <= 9.0e-4 and rank_err <= 9.0e-4, (doc_err, rank_err)
    assert any(got)


@pytest.mark.parametrize("ceiling", (64, 256, 1 << 20))
def test_every_posting_in_one_block_or_the_residual(stretched, ceiling):
    """(c) ``build_ell_from_coo`` under a ceiling: blocks no larger,
    rows in order, each posting exactly once."""
    engine, _docs, _lengths = stretched
    coo, _names, _raw = engine.index.to_coo(engine.index.snapshot
                                            .df.shape[0])
    built = build_ell_from_coo(coo, width_cap=WIDTH_CAP, min_rows=64,
                               max_rows=ceiling)
    got = []
    row0 = 0
    for blk in built.blocks:
        assert blk.tf.shape == blk.term.shape == (
            blk.width, blk.tf.shape[1])
        assert blk.tf.shape[1] <= ceiling and blk.row0 == row0
        assert not blk.tf[:, blk.n_rows:].any()
        c, r = np.nonzero(blk.tf)           # [width, rows_cap]
        got += zip((r + row0).tolist(), blk.term[c, r].tolist(),
                   blk.tf[c, r].tolist())
        row0 += blk.n_rows
    assert row0 == coo.num_docs
    n = built.res_nnz
    got += zip(built.res_doc[:n].tolist(), built.res_term[:n].tolist(),
               built.res_tf[:n].tolist())
    want = list(zip(coo.doc[:coo.nnz].tolist(),
                    coo.term[:coo.nnz].tolist(),
                    coo.tf[:coo.nnz].tolist()))
    assert len(got) == len(want) == coo.nnz and sorted(got) == sorted(want)
    if ceiling == 1 << 20:      # no rung reaches it: one block a rung
        assert [b.width for b in built.blocks] == [32, 24, 8]
    shapes = [b.tf.shape for b in built.blocks]
    g = ell_layout_gauges(shapes, [b.n_rows for b in built.blocks],
                          built.res_doc[:n])
    assert g["ell_blocks"] == len(shapes)
    assert g["ell_rows_padded"] == sum(r for _w, r in shapes)
    assert g["ell_entries_padded"] == sum(w * r for w, r in shapes)
    assert g["ell_residual_nnz"] == n and g["ell_residual_docs"] == 2


def test_checkpoint_round_trip_keeps_the_split_blocks(stretched, tmp_path):
    """(c) a restore installs the blocks as they were cut (gauges and
    answers agree, the gauges are published again); under ANOTHER
    ceiling the arrays are not installed: the restore commits anew, in
    blocks cut at that ceiling."""
    engine, _docs, _lengths = stretched
    queries = _queries(8, seed=7)
    want = engine.searcher.search_arrays(queries)[:2]
    shapes = [imp.shape for imp in engine.index.snapshot.ell_impacts]
    save_checkpoint(engine, str(tmp_path / "ck"))
    src = engine.index.snapshot
    res_doc = np.asarray(src.res_doc)[np.asarray(src.res_tf) > 0]
    gauges = ell_layout_gauges(shapes, src.ell_live_host, res_doc)
    for name in gauges:
        global_metrics.set_gauge(name, -1)
    restored = load_checkpoint(str(tmp_path / "ck"), engine.config)
    snap = restored.index.snapshot
    assert [imp.shape for imp in snap.ell_impacts] == shapes
    assert snap.ell_live_host == engine.index.snapshot.ell_live_host
    assert {k: v for k, v in global_metrics.snapshot().items()
            if k.startswith("ell_")} == gauges
    got = restored.searcher.search_arrays(queries)[:2]
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ell, "ELL_BLOCK_ROWS_MAX", 512)
        doubled = load_checkpoint(str(tmp_path / "ck"), engine.config)
    rows = [imp.shape[1] for imp in doubled.index.snapshot.ell_impacts]
    assert rows == [512, 512, 256, 512]     # 302, 512 + 88, 400 rows
    got = doubled.searcher.search_arrays(queries)[:2]
    assert all(np.array_equal(a, b) for a, b in zip(got, want))


# ---- (d) the benchmark's configurations, shapes only -------------------

# memory_stats()["bytes_limit"] of a 16 GB v5e chip (my chip run, PR 32)
V5E_BYTES_LIMIT = 16_909_336_064
IN_FLIGHT = 3                       # search_pipeline_depth 2, + 1

# (rows_cap, width) of every committed block, document capacity:
# tests/kernel_compile_worker.py CELL_STEPS
ACCEPTED = {
    "msmarco2m": (((4096, 64), (1048576, 48), (1048576, 32),
                   (131072, 24), (256, 16), (256, 12)), 1 << 21),
    "wiki1m": (((256, 128), (524288, 96), (1048576, 64), (32768, 48),
                (256, 32)), 1 << 20),
    "msmarco-doc": (((65536, 512), (524288, 384)), 1 << 19),
}
VOCAB_CAP = 1 << 19


def _index_bytes(blocks, doc_cap: int) -> int:
    """``Snapshot.size_bytes()`` of a committed ELL layout: impacts and
    terms, document lengths, df."""
    return sum(8 * r * w for r, w in blocks) + 4 * doc_cap + 4 * VOCAB_CAP


@pytest.mark.parametrize("name", sorted(ACCEPTED))
@pytest.mark.parametrize("limit", (V5E_BYTES_LIMIT, 16 << 30))
def test_accepted_cells_are_one_stretch(name, limit):
    """Their step stays the unstretched pair of programs: at B = 512 the
    whole score space fits three times beside the index and the eighth
    of the device the budget reserves (``msmarco2m`` by 2.7%: a chip
    that reported 16e9 bytes would stretch it)."""
    blocks, doc_cap = ACCEPTED[name]
    budget = stretch_budget(limit, _index_bytes(blocks, doc_cap), IN_FLIGHT)
    rows = [r for r, _w in blocks]
    for B in (512, 256, 128, 16):
        assert plan_stretches(rows, B, budget) == [(0, len(rows))], B


def _full_blocks():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "msmarco-full.json")) as f:
        layout = json.load(f)["layout"]["blocks"]
    return list(zip(layout["rows"], layout["widths"])), layout["doc_cap"]


# stretches a bucket, on a v5e and on a device of 16 GiB: the run's
# 6,700,000 passages (the configuration's ``layout.blocks``) and the
# whole collection's thirteen blocks (a full-scale commit, PR 32)
FULL_STRETCHES = {512: 6, 256: 2, 128: 1, 8: 1}
COLLECTION_ROWS = ([16384] + [1 << 20] * 4 + [1 << 19] + [1 << 20] * 4
                   + [1 << 19, 2048, 256])
COLLECTION_INDEX_BYTES = 3_064_225_792
COLLECTION_STRETCHES = {512: 8, 256: 3, 128: 2, 64: 1}


def _check_plan(rows, index, B, limit, stretches):
    plan = plan_stretches(rows, B, stretch_budget(limit, index, IN_FLIGHT))
    assert len(plan) == stretches
    assert plan[0][0] == 0 and plan[-1][1] == len(rows)
    assert all(a[1] == b[0] for a, b in zip(plan, plan[1:]))
    live = max(4 * B * sum(rows[lo:hi]) for lo, hi in plan) * IN_FLIGHT
    assert live + index <= limit - limit // 8


@pytest.mark.parametrize("B", sorted(FULL_STRETCHES))
@pytest.mark.parametrize("limit", (V5E_BYTES_LIMIT, 16 << 30))
def test_full_cell_is_stretched_under_the_budget(B, limit):
    """``msmarco-full``'s eleven blocks (its configuration's
    ``layout.blocks``: tests/test_mesh_block_capacities.py holds them to
    the generator): how many stretches a bucket takes, and that the
    stretches in flight and the index leave an eighth of the device."""
    blocks, doc_cap = _full_blocks()
    rows = [r for r, _w in blocks]
    assert len(rows) == 11 and sum(rows) * 512 * 4 > 15e9
    _check_plan(rows, _index_bytes(blocks, doc_cap), B, limit,
                FULL_STRETCHES[B])


@pytest.mark.parametrize("B", sorted(COLLECTION_STRETCHES))
def test_whole_collection_is_stretched_under_the_budget(B):
    """All 8,841,823 passages (what the deployment holds; the run's
    count is the harness's host memory's): a 19.4 GB score space at
    B = 512, a 1M-row block a stretch there."""
    assert sum(COLLECTION_ROWS) * 512 * 4 > 19e9
    _check_plan(COLLECTION_ROWS, COLLECTION_INDEX_BYTES, B,
                V5E_BYTES_LIMIT, COLLECTION_STRETCHES[B])


def test_plan_stretches_edges():
    assert plan_stretches([8, 8, 8], 4, None) == [(0, 3)]
    assert plan_stretches([8, 8, 8], 4, 4 * 4 * 16) == [(0, 2), (2, 3)]
    # a block over the budget alone is a stretch of its own
    assert plan_stretches([64, 8, 8], 4, 4 * 4 * 16) == [(0, 1), (1, 3)]
    assert plan_stretches([8], 4, 0) == [(0, 1)]
    assert stretch_budget(None, 10, 3) is None
    assert stretch_budget(1600, 100, 3) == (1600 - 200 - 100) // 3
    assert stretch_budget(160, 1000, 3) == 0


# ---- (e) counters and the wait's timing in /api/metrics ----------------

def test_stretch_counters_in_api_metrics(stretched):
    engine, _docs, _lengths = stretched
    core = CoordinationCore(session_timeout_s=5.0)
    node = SearchNode(engine.config.replace(port=0),
                      coord=LocalCoordination(core, 0.1), engine=engine)
    node.start(rebuild=False)
    try:
        def metrics() -> dict:
            with urllib.request.urlopen(node.url + "/api/metrics",
                                        timeout=10) as r:
                return json.loads(r.read())

        before = metrics()
        for seed in range(4):       # 4 chunks x 7 stretches: the fourth
            engine.search_batch(_queries(8, seed=200 + seed))  # waits
        after = metrics()
    finally:
        node.stop()
        core.close()

    def delta(key: str) -> float:
        return after.get(key, 0) - before.get(key, 0)

    assert delta("dispatch_chunks") == 4
    assert delta("score_stretches") == 4 * 7
    # three stretches in flight, one block's [8, 256] f32 scores each
    assert delta("score_space_bytes") == 4 * 3 * (4 * 8 * CEILING)
    assert delta("phase_stretch_wait_count") >= 4 * 7 - 3
    assert "phase_stretch_wait_sum_ms" in after
    # one `score` and one `topk` span a dispatched chunk
    assert delta("phase_score_count") == delta("phase_topk_count") == 4
