"""The top-k read from the ELL scorer's blocks, in place.

``packed_topk_chunked`` takes the per-block scores ``score_ell_batch``
returns and maps winners to real row ids by arithmetic; the serving path
never builds the ``[B, doc_cap]`` matrix. The contract is exactness: the
packed ``[B, 2k]`` reply is BIT-equal to the top-k of the matrix
``ell_scores_to_real`` gathers from the same blocks — dead tail rows
never win, ties resolve to the lower real row across block and chunk
boundaries, live zero-score rows still fill a short list.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_ell import build_ell_arrays
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.ops.csr import build_coo
from tfidf_tpu.ops.ell import ell_scores_to_real, score_ell_batch
from tfidf_tpu.ops.scoring import make_query_batch
from tfidf_tpu.ops.topk import (TOPK_CHUNK, TOPK_GROUP, TOPK_SORT_WIDTH,
                                TOPK_SUBGROUP,
                                exact_topk, merge_packed, packed_topk,
                                packed_topk_chunked, topk_chunk_counts,
                                topk_grouped, topk_widths, unpack_topk)
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
    assert topk_chunk_counts(caps, live, CHUNK, k=10) == (8, 2, 0)
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
    assert topk_chunk_counts(caps, np.asarray(live), CHUNK,
                             k=10) == (10, 3, 0)
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


# ---- the two-stage selection (PR 31): group maxima choose the k groups
# that can hold a winner, and only those are ranked. The contract is
# lax.top_k's own answer over the masked matrix, VALUES AND IDS: exact
# ties are the common case here (zeros, equal tf and length).

G = TOPK_GROUP
WIDE = 96 * G       # the narrowest window that is grouped at k = 10 is 80 G


def tied_scores(rng, B, cap, zeros=0.97):
    """Integer scores 1..3 over ``zeros`` exact zeros: ties everywhere."""
    x = rng.integers(1, 4, (B, cap)).astype(np.float32)
    x[rng.random((B, cap)) < zeros] = 0.0
    return x


def lax_topk_of_masked(x, lo, hi, k):
    """The oracle: ``lax.top_k`` of the matrix with the columns outside
    ``[lo, hi)`` at -inf."""
    col = np.arange(x.shape[1])[None, :]
    masked = jnp.where((col >= lo) & (col < hi), jnp.asarray(x), -jnp.inf)
    v, i = jax.lax.top_k(masked, k)
    return np.asarray(v), np.asarray(i)


# (B, cap, live, k, chunk, whether the block's chunks are grouped): a
# width the groups do not divide goes straight (the commit's capacities
# are powers of two: a pad would be a copy of the block)
GROUPED_CASES = {
    "ties_95pct_zeros": (16, WIDE, WIDE - 5, 10, WIDE, True),
    "fewer_than_k_positive": (2, WIDE, WIDE, 10, WIDE, True),
    "live_0": (2, WIDE, 0, 10, WIDE, True),
    "live_inside_a_group": (16, WIDE, 40 * G + 37, 10, WIDE, True),
    "live_on_a_group_edge": (16, WIDE, 40 * G, 10, WIDE, True),
    "live_is_cap": (16, WIDE, WIDE, 10, WIDE, True),
    "width_g_does_not_divide": (2, 87 * G + 1, 87 * G + 1, 10, 1 << 17,
                                False),
    "width_a_column_short": (2, 88 * G - 1, 87 * G + 3, 10, 1 << 17, False),
    "under_the_threshold": (16, 79 * G, 79 * G - 3, 10, 1 << 17, False),
    "k_1": (16, 8 * G, 5 * G + 1, 1, 8 * G, True),
    "k_100": (2, 1600 * G, 1500 * G + 1, 100, 800 * G, True),
    "k_over_g": (1, 8 * (G + 2) * G, 999 * G + 1, G + 2, 1 << 18, True),
    "B_1": (1, WIDE, WIDE - G - 1, 10, WIDE, True),
    "B_2": (2, WIDE, WIDE - G - 1, 10, WIDE, True),
    "B_12": (12, WIDE, WIDE - G - 1, 10, WIDE, True),
    "B_64": (64, WIDE, WIDE - G - 1, 10, WIDE, True),
    "clamped_last_chunk": (16, 2 * WIDE + 3 * G, 2 * WIDE + G + 1, 10,
                           WIDE, True),
    "clamped_last_chunk_skipped": (2, 2 * WIDE + 3 * G, 2 * WIDE, 10, WIDE,
                                   True),
    "clamped_last_chunk_odd_cap": (2, 2 * WIDE + 3 * G + 5, 2 * WIDE + G,
                                   10, WIDE, False),
    "all_minus_inf_rows": (16, WIDE, WIDE - 9, 10, WIDE, True),
}


@pytest.mark.parametrize("name", sorted(GROUPED_CASES))
def test_grouped_topk_is_bit_equal_to_lax_topk(rng, name):
    B, cap, live, k, chunk, grouped = GROUPED_CASES[name]
    c = min(chunk, cap)
    assert topk_grouped(cap, c, min(k, c)) == grouped
    x = tied_scores(rng, B, cap)
    if name == "fewer_than_k_positive":
        x[:] = 0.0
        x[0, [5, 40 * G, cap - 1]] = 2.0        # row 1: none at all
    if name == "all_minus_inf_rows":
        x[::2] = -np.inf
        x[1, :cap // 2] = -np.inf
    x[:, live:] = DEAD
    want_v, want_i = lax_topk_of_masked(x, 0, live, k)

    # the chunked form (a block with a traced live count) ...
    got_v, got_i = unpack_topk(np.asarray(packed_topk_chunked(
        (jnp.asarray(x),), jnp.asarray([live], jnp.int32), k=k,
        chunk=chunk)))
    assert np.array_equal(got_v, want_v)
    assert np.array_equal(got_i, want_i)
    # ... and the unchunked one the mesh's shards use (``lax.top_k``
    # itself: PERF.md section 7)
    v, i = exact_topk(jnp.asarray(x), jnp.int32(live), k=k)
    assert np.array_equal(np.asarray(v), want_v)
    assert np.array_equal(np.asarray(i), want_i)
    n_chunks, skipped, n_grouped = topk_chunk_counts([cap], [live], chunk,
                                                     k=k)
    assert n_grouped == (n_chunks - skipped if grouped else 0)


# ---- the deep selection (PR 39): the same step twice. A depth past 128
# takes the block for its window, ranks the k groups' 16 k sub-groups of
# 8 contiguous columns, then the 8 k columns of the k chosen ones; a
# window whose groups do not outnumber the depth enters at the second
# level. Shapes, not a knob, make each route: (B, cap, live, k, the
# window's width, ``topk_widths`` of it). The chunk is the production
# one throughout.

S = TOPK_SUBGROUP
TWO = (G, S)
DEEP_SHAPES = {
    # one 2^20-column block at the run file's depth: msmarco2m's
    "block_2e20_k_1000": (8, 1 << 20, (1 << 20) - 885, 1000, 1 << 20, TWO),
    # the narrowest block whose groups outnumber 130: 1,040 of them
    "k_130_two_levels": (4, 1040 * G, 1040 * G - 3, 130, 1040 * G, TWO),
    "k_200_live_inside_a_subgroup": (2, 1 << 18, 901 * G + 3 * S + 5, 200,
                                     1 << 18, TWO),
    "k_500_live_on_a_group_edge": (2, 1 << 19, 4001 * G, 500, 1 << 19,
                                   TWO),
    # a depth of whole lane tiles: each level keeps exactly k
    "k_256_whole_tiles": (2, 1 << 19, (1 << 19) - 300, 256, 1 << 19, TWO),
    "two_levels_fewer_live_than_k": (2, 1040 * G, 101, 130, 1040 * G, TWO),
    "two_levels_live_0": (2, 1040 * G, 0, 130, 1040 * G, TWO),
    "two_levels_B_1": (1, 1040 * G, 1040 * G, 130, 1040 * G, TWO),
    "two_levels_B_12": (12, 1040 * G, 1039 * G + 77, 130, 1040 * G, TWO),
    # a block of 2.5 windows: the last one's start is clamped, the
    # columns it shares with the one before are masked
    "two_levels_clamped_last_window": (1, 5 << 19, (5 << 19) - 2 * G - 1,
                                       130, 1 << 21, TWO),
    # groups do not outnumber the depth, sub-groups do: the second
    # level alone (msmarco2m's 131,072-column block at 1,000)
    "chunk_2e17_k_1000": (8, 1 << 17, 78448, 1000, 1 << 17, (S,)),
    "k_64_subgroups_alone": (4, 1 << 15, (1 << 15) - 9, 64, 1 << 15, (S,)),
    "k_40_live_inside_a_subgroup": (2, 1 << 15, 100 * G + 2 * S + 3, 40,
                                    1 << 15, (S,)),
    "subgroups_fewer_live_than_k": (2, 1 << 15, 37, 64, 1 << 15, (S,)),
    "subgroups_clamped_last_chunk": (2, 5 << 16, (5 << 16) - G - 5, 1000,
                                     1 << 17, (S,)),
    "k_2000_chunks_of_a_block": (2, 1 << 18, (1 << 18) - 70, 2000,
                                 1 << 17, (S,)),
    # no route by candidates: a sort of the window is small, or the
    # depth too deep for either level
    "window_of_one_sort": (2, 1 << 14, (1 << 14) - 3, 64, 1 << 14, ()),
    "k_5000": (1, 1 << 18, (1 << 18) - 3, 5000, 1 << 17, ()),
}
DEEP_DATA = ("random", "plateaus", "zeros")


def deep_scores(rng, data, B, cap, c, k):
    if data == "random":
        return rng.random((B, cap), dtype=np.float32)
    if data == "zeros":     # one tie: the first k live columns win
        return np.zeros((B, cap), np.float32)
    # three levels over 97% zeros, and plateaus of ONE higher value that
    # straddle a sub-group's, a group's and the window's edges, fewer
    # than k columns in all: each is in the answer, in column order
    x = tied_scores(rng, B, cap)
    for edge in (5 * S, 3 * G, 77 * G, c, cap - c, cap // 2):
        x[:, max(edge - 3, 0):edge + 4] = 7.0
    return x


@pytest.mark.parametrize("data", DEEP_DATA)
@pytest.mark.parametrize("name", sorted(DEEP_SHAPES))
def test_deep_topk_is_bit_equal_to_lax_topk(rng, name, data):
    B, cap, live, k, c, widths = DEEP_SHAPES[name]
    n_windows = -(-cap // c)
    assert topk_widths(cap, c, min(k, c)) == widths
    assert topk_chunk_counts([cap], [live], k=k)[0] == n_windows
    x = deep_scores(rng, data, B, cap, c, k)
    x[:, live:] = DEAD
    want_v, want_i = lax_topk_of_masked(x, 0, live, k)
    got_v, got_i = unpack_topk(np.asarray(packed_topk_chunked(
        (jnp.asarray(x),), jnp.asarray([live], jnp.int32), k=k)))
    assert np.array_equal(got_v, want_v)
    assert np.array_equal(got_i, want_i)
    if live < k:    # the -inf lanes too: the masked columns, in order
        assert np.all(np.isneginf(got_v[:, live:]))
    _n, skipped, grouped = topk_chunk_counts([cap], [live], k=k)
    assert grouped == (n_windows - skipped if widths else 0)


def test_deep_topk_between_blocks_and_stretches(rng):
    """A two-level block, a sub-group one, a straight one and one
    narrower than the depth, every score one of three values: the merged
    top-300 is the 300 lowest real rows of the highest value, whichever
    block holds them, plateaus running across every block's edge; and
    the same blocks as two stretches with ``base`` joined by
    ``merge_packed``."""
    k = 300
    caps, live = (1 << 15, 2400 * G, 4096, 256), (30000, 2400 * G - 77,
                                                  4000, 200)
    assert [topk_widths(c, c, min(k, c)) for c in caps] == [
        (S,), TWO, (), ()]
    assert topk_chunk_counts(caps, live, k=k) == (4, 0, 2)
    blocks, lives = synthetic_blocks(rng, caps, live, B=3, levels=3)
    total = sum(live)
    real = np.concatenate([np.asarray(b)[:, :n]
                           for b, n in zip(blocks, live)], axis=1)
    want_v, want_i = lax_topk_of_masked(real, 0, total, k)
    got = np.asarray(packed_topk_chunked(blocks, lives, k=k))
    got_v, got_i = unpack_topk(got)
    assert np.array_equal(got_v, want_v)
    assert np.array_equal(got_i, want_i)
    parts = (packed_topk_chunked(blocks[:1], lives[:1], jnp.int32(0), k=k),
             packed_topk_chunked(blocks[1:], lives[1:],
                                 jnp.int32(live[0]), k=k))
    assert np.array_equal(np.asarray(merge_packed(parts)), got)


# the msmarco2m cell's blocks (a commit of its corpus) and wiki1m's
MSMARCO2M = ((4096, 1048576, 1048576, 131072, 256, 256),
             (3310, 1047691, 870316, 78448, 233, 2))
ONE, SUB = (G,), (S,)
# k -> (chunks, skipped, grouped) of a dispatch, and the route of the
# 4096-, the 2^20- and the 131072-column block's window (the two
# 256-column ones are straight at every depth). A WINDOW is a chunk of
# 131,072 columns up to a depth of 128 and past 2,048; between them a
# 2^20-column block is ONE window (8,192 groups outnumber a depth up to
# 1,024) or, past that, eight chunks ranked by sub-groups alone.
ROUTES = {
    10: ((20, 1, 16), (), ONE, ONE),
    64: ((20, 1, 16), (), ONE, ONE),
    100: ((20, 1, 16), (), ONE, ONE),
    128: ((20, 1, 16), (), ONE, ONE),
    129: ((6, 0, 3), (), TWO, SUB),
    500: ((6, 0, 3), (), TWO, SUB),
    1000: ((6, 0, 3), (), TWO, SUB),
    1024: ((6, 0, 3), (), TWO, SUB),
    1025: ((20, 1, 16), (), SUB, SUB),
    2000: ((20, 1, 16), (), SUB, SUB),
    2048: ((20, 1, 16), (), SUB, SUB),
    2049: ((20, 1, 0), (), (), ()),
    5000: ((20, 1, 0), (), (), ()),
}


@pytest.mark.parametrize("k", sorted(ROUTES))
def test_route_of_every_depth_on_msmarco2m_blocks(k):
    """Host integers only: the route a depth takes over the cell's six
    blocks, so that no depth falls between two routes. Up to 128 it is
    the parent's program (the digests of ``tests/test_kernel_compile.py``
    hold k = 10)."""
    caps, live = MSMARCO2M
    counts, *routes = ROUTES[k]
    assert topk_chunk_counts(caps, live, k=k) == counts
    windows = [min(cap, TOPK_CHUNK) for cap in (4096, 1 << 20, 1 << 17)]
    if TWO in routes:
        windows[1] = 1 << 20
    assert [topk_widths(cap, c, min(k, c)) for cap, c in zip(
        (4096, 1 << 20, 1 << 17), windows)] == routes
    assert [topk_grouped(cap, c, min(k, c)) for cap, c in zip(
        (4096, 1 << 20, 1 << 17), windows)] == [bool(r) for r in routes]
    # what the route promises: no row it sorts is wider than this (each
    # level keeps k in whole lane tiles)
    kept = -(-k // G) * G
    for c, route in zip(windows, routes):
        if route:
            rows = [c // route[0]] + [
                kept * w // nxt for w, nxt in zip(route, route[1:] + (1,))]
            assert max(rows) <= max(TOPK_SORT_WIDTH, 16 * kept)


def test_grouped_topk_between_blocks_breaks_ties_to_the_lower_row(rng):
    """Two grouped blocks and a narrow one, every score one of two
    values: the merged top-10 of each query is its ten lowest real rows
    of the higher value, whichever block holds them."""
    caps, live = (WIDE, 2 * WIDE, 256), (WIDE - 77, WIDE + 5, 200)
    blocks, lives = synthetic_blocks(rng, caps, live, B=4, levels=2)
    real = ell_scores_to_real(blocks, lives, 4 * WIDE)
    num_docs = jnp.int32(sum(live))
    want = np.asarray(packed_topk(real, num_docs, k=10))
    for chunk in (WIDE, 1 << 17):
        assert np.array_equal(want, np.asarray(packed_topk_chunked(
            blocks, lives, k=10, chunk=chunk)))
    col = np.arange(4 * WIDE)[None, :]
    masked = np.where(col < sum(live), np.asarray(real), -np.inf)
    order = np.argsort(-masked, axis=1, kind="stable")[:, :10]
    assert np.array_equal(want[:, 10:], order)


def test_topk_chunk_counters_add_up_to_the_padded_space(tmp_path):
    """``topk_chunks`` / ``topk_chunks_skipped`` / ``topk_chunks_grouped``
    count, per dispatched chunk, the committed snapshot's padded chunk
    count, the dead ones among them and those ranked by group maxima —
    from shapes and the commit's host integers (``tests/test_cluster.py``
    reads them from ``/api/metrics``). grouped + skipped + straight =
    chunks."""
    def counted(fn):
        before = global_metrics.snapshot()
        fn()
        after = global_metrics.snapshot()
        return [after.get(key, 0) - before.get(key, 0) for key in
                ("dispatch_chunks", "topk_chunks", "topk_chunks_skipped",
                 "topk_chunks_grouped")]

    e = Engine(Config(documents_path=str(tmp_path), min_doc_capacity=8,
                      min_nnz_capacity=256, min_vocab_capacity=64,
                      query_batch=4, max_query_terms=8))
    for i in range(20):     # three widths -> three blocks
        e.ingest_text(f"d{i}", " ".join(
            f"w{i}x{j}" for j in range((3, 11, 20)[i % 3])) + " shared")
    e.commit()
    snap = e.index.snapshot
    caps = [imp.shape[1] for imp in snap.ell_impacts]
    assert len(caps) == 3
    assert snap.ell_live_host == tuple(np.asarray(snap.ell_live))
    # 3 dispatches of <= 4 queries; every block here is one live chunk
    # (narrower than eighty groups: straight through ``lax.top_k``)
    assert counted(lambda: e.search_batch(["shared"] * 9)) == [3, 9, 0, 0]

    # the msmarco2m cell's blocks (a commit of its corpus): 20 chunks of
    # 131072 columns, the third block's last one past its 870,316 rows;
    # the 17 chunks of the three wide blocks go by group maxima less the
    # skipped one, the 4096- and 256-column blocks straight
    caps = (4096, 1048576, 1048576, 131072, 256, 256)
    live = (3310, 1047691, 870316, 78448, 233, 2)
    assert topk_chunk_counts(caps, live, k=10) == (20, 1, 16)
    assert [topk_grouped(c, min(c, 1 << 17), 10)
            for c in caps[::2]] == [False, True, False]
    # wiki1m's: four dead chunks of fifteen, the 32768-column block grouped
    assert topk_chunk_counts(
        (256, 524288, 1048576, 32768, 256),
        (201, 393204, 598231, 8311, 53), k=10) == (15, 4, 9)
    # at msmarco2m-top1000's depth a WINDOW is no longer a chunk: each
    # 2^20-column block is one (its 8,192 groups outnumber the depth
    # where a chunk's 1,024 do not: ranked at two levels), the
    # 131,072-column block is one, ranked by its sub-groups of 8; three
    # grouped windows of six, none skipped (a window that starts at 0
    # is skipped only where its block is empty); the 4096-column block
    # is one small sort, the two 256-column ones are narrower than the
    # depth (the pad lanes of ``_block_topk``): straight
    assert topk_chunk_counts(caps, live, k=1000) == (6, 0, 3)
    assert [topk_grouped(c, c, min(1000, c)) for c in caps] == [
        False, True, True, True, False, False]
    # at 2,000 a block's groups no longer outnumber the depth but a
    # chunk's 16,384 sub-groups do: windows are chunks again, ranked by
    # sub-groups alone; past 2,048 every chunk goes straight
    assert topk_chunk_counts(caps, live, k=2000) == (20, 1, 16)
    assert topk_chunk_counts(caps, live, k=5000) == (20, 1, 0)
    assert not any(topk_grouped(c, min(c, 1 << 17), min(5000, c))
                   for c in caps)
    assert [c for c in caps if c < 1000] == [256, 256]
