"""Whole documents: rows wider than 256 ride ELL blocks and the kernel.

One engine whose documents hold from 200 distinct terms to more than the
ladder's top rung, so that the committed snapshot has a block at 256, at
every rung past it AND a live COO residual (the postings of the two
documents past the top). ``Engine.search_batch`` is held to the plain
float64 BM25 of ``tests/oracle.py``: the same hits in the same order,
each score within rtol 1e-4, the float32 limit ``tests/test_engine.py``
uses against that oracle (float32 impacts and sums differ from float64
by ~1e-6 relative a term; a block scored in bfloat16 would miss by
~4e-3, a dropped residual by a whole term's impact). Every block has 256
rows here, so all of them ride the (interpreted) Pallas kernel.
"""

import numpy as np
import pytest

from tests.oracle import bm25_scores
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.ops.ell import ELL_WIDTH_LADDER
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

VOCAB = 9000
TOP = ELL_WIDTH_LADDER[-1]
WIDE_RUNGS = [w for w in ELL_WIDTH_LADDER if w >= 256]
# distinct terms a document: two in every rung from 256 up (one at the
# rung exactly), two past the top, and a crowd of short ones below
SIZES = ([200, 256] + [n for w in WIDE_RUNGS[1:] for n in (w - 40, w)]
         + [TOP + 200, TOP + 900] + list(range(20, 180, 4)))


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    rng = np.random.default_rng(30)
    cfg = Config(documents_path=str(tmp_path_factory.mktemp("wide")),
                 min_doc_capacity=256, min_nnz_capacity=1 << 16,
                 min_vocab_capacity=1 << 14, query_batch=16,
                 embedding_enabled=False)
    engine = Engine(cfg)
    for t in range(VOCAB):
        engine.vocab.add(f"t{t}")
    # common terms first, as a Zipf vocabulary has them
    p = 1.0 / np.arange(1, VOCAB + 1) ** 0.6
    docs, lengths = [], []
    for i, n in enumerate(SIZES):
        ids = np.sort(rng.choice(VOCAB, size=n, replace=False,
                                 p=p / p.sum())).astype(np.int32)
        tfs = rng.integers(1, 6, size=n).astype(np.float32)
        engine.index.add_document_arrays(f"d{i}", ids, tfs,
                                         float(tfs.sum()))
        docs.append(dict(zip(ids.tolist(), tfs.tolist())))
        lengths.append(float(tfs.sum()))
    engine.commit()
    return engine, docs, lengths


def test_layout_has_every_wide_rung_and_a_residual(wide):
    engine, docs, _lengths = wide
    snap = engine.index.snapshot
    widths = [imp.shape[0] for imp in snap.ell_impacts]    # [width, rows]
    assert widths[:len(WIDE_RUNGS)] == WIDE_RUNGS[::-1]
    assert all(imp.shape[1] == 256 for imp in snap.ell_impacts)
    stats = engine.compute_stats()
    assert stats["kernel_blocks"] == stats["posting_blocks"] == len(widths)
    spilled = [len(d) - TOP for d in docs if len(d) > TOP]
    assert snap.res_nnz == sum(spilled) == 1100
    g = global_metrics.snapshot()
    assert g["ell_blocks"] == len(widths)
    assert g["ell_width_max"] == TOP
    assert g["ell_residual_nnz"] == 1100 and g["ell_residual_docs"] == 2
    assert g["ell_entries_padded"] == 256 * sum(widths)
    # a 256-row block is ONE doc tile, streamed whole, but for the two
    # widest: their tile is 128 rows (``_pl_tiles``) and the second
    # tile holds no live row
    assert g["ell_entries_live_tiles"] \
        == g["ell_entries_padded"] - 128 * (4096 + 3072)
    assert g["phase_ell_build_count"] >= 1


def _queries(docs, rng):
    """Per document past 200 terms, a query of three of its terms —
    for the two past the top rung, two of them from the residual (a
    row's terms ascend, so those are its largest ids) — with one term
    repeated, so the batch carries a multiplicity of 2."""
    out = []
    for d in docs:
        if len(d) < 200:
            continue
        ids = sorted(d)
        tail = ids[TOP:] if len(ids) > TOP else ids
        picks = [int(rng.choice(ids)), int(rng.choice(tail)),
                 int(rng.choice(tail))]
        out.append(picks + picks[:1])
    return out


def test_search_batch_equals_float64_bm25(wide):
    engine, docs, lengths = wide
    queries = _queries(docs, np.random.default_rng(31))
    before = global_metrics.get("residual_entries_scored")
    got = engine.search_batch(
        [" ".join(f"t{t}" for t in q) for q in queries], k=10)
    # every dispatched chunk scored the residual's 1,100 live entries
    chunks = -(-len(queries) // engine.config.query_batch)
    assert global_metrics.get("residual_entries_scored") - before \
        == 1100 * chunks
    for q, hits in zip(queries, got):
        weights: dict[int, float] = {}
        for t in q:
            weights[t] = weights.get(t, 0.0) + 1.0
        want = np.asarray(bm25_scores(docs, lengths, weights))
        order = np.argsort(-want, kind="stable")[:10]
        order = order[want[order] > 0]
        assert [h.name for h in hits] == [f"d{i}" for i in order], q
        np.testing.assert_allclose([h.score for h in hits], want[order],
                                   rtol=1e-4)
