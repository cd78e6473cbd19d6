"""The top-k read from the ELL scorer's blocks, in place.

``packed_topk_chunked`` takes the per-block scores ``score_ell_batch``
returns and maps winners to real row ids by arithmetic; the serving path
never builds the ``[B, doc_cap]`` matrix. The contract is exactness: the
packed ``[B, 2k]`` reply is BIT-equal to the top-k of the matrix
``ell_scores_to_real`` gathers from the same blocks — dead tail rows
never win, ties resolve to the lower real row across block and chunk
boundaries, live zero-score rows still fill a short list.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_ell import build_ell_arrays
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.ops.csr import build_coo
from tfidf_tpu.ops.ell import ell_scores_to_real, score_ell_batch
from tfidf_tpu.ops.scoring import make_query_batch
from tfidf_tpu.ops.topk import (packed_topk, packed_topk_chunked,
                                topk_chunk_counts)
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

CHUNK = 16      # the production 131072, scaled to test-sized blocks
DEAD = 1e9      # written into dead tails: a missing mask would rank it


def synthetic_blocks(rng, caps, live, *, B=5, levels=None):
    """Random block scores; ``levels`` draws them from that many distinct
    values so exact ties span every boundary."""
    blocks = []
    for cap, n in zip(caps, live):
        x = (rng.integers(0, levels, (B, cap)).astype(np.float32)
             if levels else rng.random((B, cap), dtype=np.float32))
        x[:, n:] = DEAD
        blocks.append(jnp.asarray(x))
    return tuple(blocks), jnp.asarray(np.asarray(live, np.int32))


def corpus_blocks(rng, shape, *, model, width_cap, query_terms,
                  vocab=64, B=4, rare_terms=0):
    """Blocks scored from a seeded corpus: ``shape`` is ``[(documents,
    distinct terms each), ...]``, longest first (the commit's order), so
    the block structure is the case's choice and not the draw's. Terms
    ``vocab .. vocab + rare_terms`` go into the first document of every
    group and no other."""
    docs = [{int(t): int(rng.integers(1, 4))
             for t in rng.choice(vocab, size=n_terms, replace=False)}
            | ({vocab + r: 1 for r in range(rare_terms)} if i == 0 else {})
            for count, n_terms in shape for i in range(count)]
    lengths = [float(sum(d.values())) for d in docs]
    coo = build_coo(docs, vocab_cap=128, min_nnz_cap=1 << 10,
                    min_doc_cap=64)
    n_docs, avgdl = jnp.float32(len(docs)), jnp.float32(np.mean(lengths))
    ell, impacts, terms, live = build_ell_arrays(
        coo, model, n_docs, avgdl, width_cap=width_cap)
    q_terms = np.zeros((B, 6), np.int32)
    q_weights = np.zeros((B, 6), np.float32)
    for b in range(B):
        ts = query_terms(b, docs)
        q_terms[b, :len(ts)] = ts
        q_weights[b, :len(ts)] = 1.0
    qb = make_query_batch(q_terms, q_weights, min_slots=8)
    res = [None] * 3 if not ell.res_nnz else [
        jnp.asarray(a) for a in (ell.res_tf, ell.res_term, ell.res_doc)]
    blocks = score_ell_batch(
        impacts, terms, live, *res, jnp.asarray(coo.doc_len),
        jnp.asarray(coo.df), qb, n_docs, avgdl, model=model)
    # the scorer leaves zeros in its dead tails; make them rank first
    blocks = tuple(
        blk.at[:, int(n):].set(DEAD) for blk, n in zip(blocks, live))
    return blocks, live, ell


def case_dead_tails(rng):
    # (a) four blocks with dead tails; block 1's second chunk and block
    # 0's last hold no live row; the 8-row block is narrower than k
    caps, live = (64, 32, 16, 8), (40, 5, 16, 3)
    assert topk_chunk_counts(caps, live, CHUNK) == (8, 2)
    return synthetic_blocks(rng, caps, live) + (10,)


def case_ties(rng):
    # (b) three score levels over 100 live rows: every top-10 is one
    # long tie that runs across chunk 16|17 and block 40|41 boundaries
    blocks, live = synthetic_blocks(rng, (64, 32, 32), (40, 31, 29),
                                    levels=3)
    blocks = (blocks[0].at[0, :40].set(2.0),      # a row of ONE value
              blocks[1].at[0, :31].set(2.0),
              blocks[2].at[0, :29].set(2.0))
    return blocks, live, 10


def case_few_matches(rng):
    # (c) the query's one term is in 3 documents, one a block: 7 of the
    # 10 places go to live zero-score rows in real-row order, none to a
    # dead row
    blocks, live, _ = corpus_blocks(
        rng, [(20, 26), (9, 7), (5, 2)], model="bm25", width_cap=64,
        query_terms=lambda b, docs: [64 + b], rare_terms=4)
    assert len(blocks) == 3
    assert int((np.asarray(blocks[0])[:, :20] > 0).sum()) == 4
    return blocks, live, 10


def case_residual(rng):
    # (d) 12 documents wider than the cap spill into the COO residual,
    # which adds to block 0 alone
    blocks, live, ell = corpus_blocks(
        rng, [(12, 20), (30, 7)], model="bm25", width_cap=8,
        query_terms=lambda b, docs: sorted(docs[b])[5:11])
    assert ell.res_nnz > 0
    return blocks, live, 10


def case_small_and_skipped(rng):
    # (e) 70 rows in a 128-row block (chunks at 80, 96, 112 skipped)
    # beside two 8-row blocks, each smaller than a chunk; tfidf, so
    # documents of different blocks tie exactly on a shared term
    blocks, live, _ = corpus_blocks(
        rng, [(70, 20), (5, 10), (3, 5)], model="tfidf", width_cap=64,
        query_terms=lambda b, docs: [b, b + 1])
    caps = [blk.shape[1] for blk in blocks]
    assert caps == [128, 8, 8]
    assert topk_chunk_counts(caps, np.asarray(live), CHUNK) == (10, 3)
    return blocks, live, 10


@pytest.mark.parametrize("case", [
    case_dead_tails, case_ties, case_few_matches, case_residual,
    case_small_and_skipped])
def test_topk_from_blocks_is_bit_equal_to_topk_of_real_matrix(rng, case):
    blocks, live, k = case(rng)
    num_docs = jnp.int32(int(np.asarray(live).sum()))
    doc_cap = 256
    real = ell_scores_to_real(blocks, live, doc_cap)
    want = np.asarray(packed_topk(real, num_docs, k=k))
    # the matrix form is the one-block case of the same function
    assert np.array_equal(
        want, np.asarray(packed_topk_chunked(real, num_docs, k=k,
                                             chunk=CHUNK)))
    got = np.asarray(packed_topk_chunked(blocks, live, k=k, chunk=CHUNK))
    assert np.array_equal(got, want)
    assert not np.any(got[:, :k].view(np.float32) == DEAD)
    # whatever the chunking
    for chunk in (8, 24, 1 << 17):
        assert np.array_equal(want, np.asarray(packed_topk_chunked(
            blocks, live, k=k, chunk=chunk)))


def test_topk_chunk_counters_add_up_to_the_padded_space(tmp_path):
    """``topk_chunks`` / ``topk_chunks_skipped`` count, per dispatched
    chunk, the committed snapshot's padded chunk count and the dead ones
    among them — from shapes and the commit's host integers
    (``tests/test_cluster.py`` reads them from ``/api/metrics``)."""
    def counted(fn):
        before = global_metrics.snapshot()
        fn()
        after = global_metrics.snapshot()
        return [after.get(key, 0) - before.get(key, 0) for key in
                ("dispatch_chunks", "topk_chunks", "topk_chunks_skipped")]

    e = Engine(Config(documents_path=str(tmp_path), min_doc_capacity=8,
                      min_nnz_capacity=256, min_vocab_capacity=64,
                      query_batch=4, max_query_terms=8))
    for i in range(20):     # three widths -> three blocks
        e.ingest_text(f"d{i}", " ".join(
            f"w{i}x{j}" for j in range((3, 11, 20)[i % 3])) + " shared")
    e.commit()
    snap = e.index.snapshot
    caps = [imp.shape[0] for imp in snap.ell_impacts]
    assert len(caps) == 3
    assert snap.ell_live_host == tuple(np.asarray(snap.ell_live))
    # 3 dispatches of <= 4 queries; every block here is one live chunk
    assert counted(lambda: e.search_batch(["shared"] * 9)) == [3, 9, 0]

    # the msmarco2m cell's blocks (a commit of its corpus): 20 chunks of
    # 131072 columns, the third block's last one past its 870,316 rows
    assert topk_chunk_counts(
        (4096, 1048576, 1048576, 131072, 256, 256),
        (3310, 1047691, 870316, 78448, 233, 2)) == (20, 1)
