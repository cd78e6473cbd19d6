"""The index holds an ELL block the way the kernel reads it (PR 43).

A block is ``[width, rows_cap]`` from the commit on; before, the commit
held ``[rows_cap, width]`` and ``score_block_pallas`` turned every block
on every dispatched batch, from 128 wide a physical copy of every
posting. Held here to the formulation it replaced, which is kept in
this file as the reference: the parent's row-major builder
(``_row_major_blocks``) and its XLA oracle over ``[rows, width]``
(``_row_major_score_block``). Same postings, same place, same bits.
A ``format_version`` 1 checkpoint holds row-major blocks and still
restores, turned on the way in.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfidf_tpu.engine.checkpoint import (FORMAT_VERSION, load_checkpoint,
                                         save_checkpoint)
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.ops import ell
from tfidf_tpu.ops.csr import build_coo, next_capacity
from tfidf_tpu.ops.ell import (_lane_sum_w, _pick_chunk, _score_block,
                               build_ell_from_coo, ell_impacts,
                               score_block_pallas, score_ell_with_residual)
from tfidf_tpu.ops.scoring import _compile_queries, make_query_batch
from tfidf_tpu.utils import storage
from tfidf_tpu.utils.config import Config

VOCAB = 4096


def _row_major_blocks(coo, *, width_cap, min_rows):
    """``build_ell_from_coo``'s block loop as commit 5ec209a (PR 41)
    had it: ``(tf, term)`` ``[rows_cap, width]`` a block, the scatter
    index ``(row, pos)``."""
    nnz, n_live = coo.nnz, coo.num_docs
    doc_ids = coo.doc[:nnz]
    bounds = np.searchsorted(doc_ids, np.arange(n_live + 1))
    row_len = np.diff(bounds)
    pos = np.arange(nnz, dtype=np.int64) - bounds[:-1][doc_ids]
    ladder = np.asarray([w for w in ell.ELL_WIDTH_LADDER
                         if 8 <= w <= width_cap], np.int64)
    widths = ladder[np.clip(np.searchsorted(
        ladder, np.minimum(row_len, ladder[-1])), 0, ladder.shape[0] - 1)]
    out, row0 = [], 0
    while row0 < n_live:
        w = int(widths[row0])
        hi = int(np.searchsorted(-widths, -w, side="right"))
        rows_cap = next_capacity(hi - row0, min_rows)
        tf = np.zeros((rows_cap, w), np.float32)
        term = np.zeros((rows_cap, w), np.int32)
        sel = (doc_ids >= row0) & (doc_ids < hi) & (pos < w)
        at = (doc_ids[sel] - row0, pos[sel])
        tf[at] = coo.tf[:nnz][sel]
        term[at] = coo.term[:nnz][sel]
        out.append((tf, term))
        row0 = hi
    return out


def _row_major_score_block(impact, term, slot_of, qc_t, doc_chunk):
    """``_score_block`` as commit 5ec209a had it, over a block
    ``[rows_cap, width]``: the arithmetic and the reduction order the
    width-major oracle keeps."""
    rows_cap, width = impact.shape
    B = qc_t.shape[1]
    chunk = _pick_chunk(rows_cap, width, B, doc_chunk)
    n_chunks = rows_cap // chunk

    def body(_, xs):
        imp_c, term_c = xs
        prod = qc_t[slot_of[term_c]] * imp_c[:, :, None]
        prod = jnp.where(term_c[:, :, None] >= 0, prod, 0.0)
        return None, _lane_sum_w(prod).T

    _, chunks = jax.lax.scan(body, None, (
        impact.reshape(n_chunks, chunk, width),
        term.reshape(n_chunks, chunk, width)))
    return jnp.moveaxis(chunks, 0, 1).reshape(B, rows_cap)


def _corpus(rng, sizes):
    """Documents of ``sizes`` distinct terms, longest first (the
    ``to_coo`` order), term 0 live in every third."""
    docs = []
    for i, n in enumerate(sorted(sizes, reverse=True)):
        ids = rng.choice(np.arange(1, VOCAB), size=n, replace=False)
        if i % 3 == 0:
            ids[0] = 0
        docs.append({int(t): float(rng.integers(1, 5)) for t in ids})
    return docs


# width of the block under test -> (distinct terms a document, width_cap)
CASES = {
    "narrow_48": (lambda rng: rng.integers(33, 49, 300), 48),
    "wide_384": (lambda rng: rng.integers(257, 385, 300), 384),
    # twenty documents past the rung: their tails are the residual
    "wide_512_with_a_residual": (
        lambda rng: np.r_[rng.integers(385, 513, 280),
                          rng.integers(513, 700, 20)], 512),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_width_major_block_scores_as_the_row_major_one(case):
    """A block built width-major holds the parent's row-major block
    turned, entry for entry (pads trailing down the width), and scores
    BIT-EQUAL to it: the interpreted kernel against the kernel over the
    parent's block turned, the XLA oracle against the parent's oracle
    over the parent's block; the residual's scores add the same."""
    sizes_of, cap = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case) + 43)
    docs = _corpus(rng, sizes_of(rng).tolist())
    coo = build_coo(docs, vocab_cap=VOCAB, min_nnz_cap=1 << 12,
                    min_doc_cap=256)
    built = build_ell_from_coo(coo, width_cap=cap, min_rows=256)
    parent = _row_major_blocks(coo, width_cap=cap, min_rows=256)
    assert len(built.blocks) == len(parent) >= 1
    assert built.blocks[0].width == cap
    assert (built.res_nnz > 0) == case.endswith("residual")
    for blk, (tf, term) in zip(built.blocks, parent):
        assert blk.tf.shape == blk.term.shape == (blk.width, tf.shape[0])
        assert np.array_equal(blk.tf, tf.T)
        assert np.array_equal(blk.term, term.T)
        filled = blk.tf != 0
        assert (filled[1:] <= filled[:-1]).all()   # pads trail the width
        assert not blk.term[~filled].any()

    B = 16
    blk = built.blocks[0]
    q_terms = np.zeros((B, 6), np.int32)
    q_weights = np.zeros((B, 6), np.float32)
    live = parent[0][1][:blk.n_rows]
    for i in range(B):
        n = rng.integers(1, 6)
        q_terms[i, :n] = live[rng.integers(0, blk.n_rows, n),
                              rng.integers(0, 33, n)]
        q_weights[i, :n] = (rng.integers(1, 4, n) if i % 2
                            else 1.0 + rng.random(n))
    q_terms[0, 0], q_weights[0, 0] = 0, 1.0           # term 0 itself
    qb = jax.tree.map(jnp.asarray, make_query_batch(q_terms, q_weights,
                                                    min_slots=256))
    rows_cap = blk.tf.shape[1]
    dl = np.zeros(rows_cap, np.float32)
    dl[:blk.n_rows] = coo.doc_len[:blk.n_rows]
    stats = (jnp.asarray(coo.df), jnp.float32(len(docs)),
             jnp.float32(coo.doc_len[:len(docs)].mean()))
    imp = ell_impacts(jnp.asarray(blk.tf), jnp.asarray(blk.term),
                      jnp.asarray(dl), *stats, None, model="bm25")
    term = jnp.asarray(blk.term)
    assert imp.shape == term.shape == (cap, rows_cap)
    slot_of, qc_ext = _compile_queries(qb, VOCAB)
    n_rows = jnp.int32(blk.n_rows)

    assert ell._pallas_eligible(rows_cap, B, qb.uniq.shape[0])
    kernel = np.asarray(score_block_pallas(
        imp, term, qb.uniq, qb.n_uniq, qc_ext, n_rows))
    # what the parent held, [rows_cap, width], turned as its wrapper did
    imp_rows = jnp.asarray(np.ascontiguousarray(np.asarray(imp).T))
    term_rows = jnp.asarray(parent[0][1])
    turned = np.asarray(score_block_pallas(
        imp_rows.T, term_rows.T, qb.uniq, qb.n_uniq, qc_ext, n_rows))
    assert np.abs(kernel).max() > 0 and kernel[0].max() > 0
    assert np.array_equal(kernel, turned)

    oracle = np.asarray(_score_block(imp, term, slot_of, qc_ext.T, 2048))
    want = np.asarray(_row_major_score_block(
        imp_rows, term_rows, slot_of, qc_ext.T, 2048))
    assert np.array_equal(oracle, want)
    assert np.abs(kernel - oracle).max() < 1e-4

    if built.res_nnz:
        # the whole step: every spilled row lies in block 0, whose
        # columns are real rows, so the residual adds there
        lives = jnp.asarray([b.n_rows for b in built.blocks], jnp.int32)
        imps, terms = [], []
        for b in built.blocks:
            dl_b = np.zeros(b.tf.shape[1], np.float32)
            dl_b[:b.n_rows] = coo.doc_len[b.row0:b.row0 + b.n_rows]
            terms.append(jnp.asarray(b.term))
            imps.append(ell_impacts(jnp.asarray(b.tf), terms[-1],
                                    jnp.asarray(dl_b), *stats, None,
                                    model="bm25"))
        rest = (jnp.asarray(built.res_tf), jnp.asarray(built.res_term),
                jnp.asarray(built.res_doc), jnp.asarray(coo.doc_len),
                stats[0], qb, stats[1], stats[2])
        with_res = score_ell_with_residual(
            tuple(imps), tuple(terms), lives, *rest, use_pallas=False)
        without = score_ell_with_residual(
            tuple(imps), tuple(terms), lives, None, None, None, *rest[3:],
            use_pallas=False)
        extra = np.asarray(with_res[0]) - np.asarray(without[0])
        spilled = np.unique(built.res_doc[:built.res_nnz])
        assert spilled.max() < 20 and np.array_equal(
            np.asarray(without[0]), oracle)
        assert not np.delete(extra, spilled, axis=1).any()


# ---- checkpoints: format_version 1 held row-major blocks --------------

def _wide_engine(tmp_path, sub="docs"):
    """Three ELL blocks whose widths (32, 24, 12) differ from their row
    capacities (8, 16, 32): a block served turned would not even
    compile against its neighbours' shapes, and one restored turned
    fails the shape asserts below."""
    e = Engine(Config(documents_path=str(tmp_path / sub),
                      min_nnz_capacity=64, min_doc_capacity=8,
                      min_vocab_capacity=256, query_batch=8,
                      max_query_terms=8))
    rng = np.random.default_rng(5)
    for i, n in enumerate([30] * 5 + [20] * 12 + [10] * 25):
        words = rng.choice(200, size=n, replace=False)
        e.ingest_text(f"d{i}.txt", " ".join(
            f"w{w}" for w in np.repeat(words, rng.integers(1, 3, n))))
    e.commit()
    return e


QUERIES = ["w1 w2", "w3", "w10 w20 w30", "w7 w7 w9", "w150", "nothing"]


def _shapes(engine):
    snap = engine.index.snapshot
    return [tuple(a.shape) for a in snap.ell_impacts], \
        [tuple(a.shape) for a in snap.ell_terms]


def _downgrade_to_version_1(ckpt):
    """Rewrite the published checkpoint as a ``format_version`` 1 tree
    wrote it: ``ell_imp_i`` / ``ell_term_i`` ``[rows_cap, width]``."""
    vdir = os.path.realpath(ckpt)
    path = os.path.join(vdir, "snapshot.npz")
    data = np.load(path)
    arrays = {k: data[k] for k in data.files}
    for i in range(int(arrays["n_blocks"])):
        for name in (f"ell_imp_{i}", f"ell_term_{i}"):
            arrays[name] = np.ascontiguousarray(arrays[name].T)
    storage.savez(path, **arrays)
    with open(os.path.join(vdir, "meta.json")) as f:
        meta = json.load(f)
    assert meta["format_version"] == FORMAT_VERSION == 2
    meta["format_version"] = 1
    storage.write_bytes(os.path.join(vdir, "meta.json"),
                        json.dumps(meta).encode())
    storage.write_manifest(vdir)
    return arrays


@pytest.mark.parametrize("version", [1, 2],
                         ids=["format_version_1", "fresh"])
def test_checkpoint_restores_width_major_blocks(tmp_path, version):
    """A fresh checkpoint round-trips; a ``format_version`` 1 directory
    (row-major ``ell_imp_i`` / ``ell_term_i``) restores INTO the new
    orientation: the installed blocks are the committed ones, shape and
    bits, and a query batch gets the same ids and scores."""
    e = _wide_engine(tmp_path)
    imps, terms = _shapes(e)
    assert imps == terms == [(32, 8), (24, 16), (12, 32)]
    want = e.searcher.search_arrays(QUERIES, k=5)[:2]
    ckpt = str(tmp_path / "ckpt")
    save_checkpoint(e, ckpt)
    stored = np.load(os.path.join(ckpt, "snapshot.npz"))
    assert [stored[f"ell_imp_{i}"].shape for i in range(3)] == imps
    if version == 1:
        old = _downgrade_to_version_1(ckpt)
        assert [old[f"ell_term_{i}"].shape for i in range(3)] \
            == [(8, 32), (16, 24), (32, 12)]
    restored = load_checkpoint(ckpt, e.config)
    snap = restored.index.snapshot
    # installed, not committed anew: the version is the saved one
    assert snap.version == e.index.snapshot.version
    assert _shapes(restored) == (imps, terms)
    for got, have in zip(snap.ell_impacts + snap.ell_terms,
                         e.index.snapshot.ell_impacts
                         + e.index.snapshot.ell_terms):
        assert np.array_equal(np.asarray(got), np.asarray(have))
    got = restored.searcher.search_arrays(QUERIES, k=5)[:2]
    assert np.asarray(want[0]).tobytes() == np.asarray(got[0]).tobytes()
    assert np.array_equal(np.asarray(want[1]), np.asarray(got[1]))
    # and what it saves next is the new format
    again = str(tmp_path / "again")
    save_checkpoint(restored, again)
    with open(os.path.join(again, "meta.json")) as f:
        assert json.load(f)["format_version"] == 2
    assert np.load(os.path.join(again, "snapshot.npz"))[
        "ell_term_1"].shape == (24, 16)


def test_unknown_checkpoint_format_is_refused(tmp_path):
    e = _wide_engine(tmp_path)
    ckpt = str(tmp_path / "ckpt")
    save_checkpoint(e, ckpt)
    vdir = os.path.realpath(ckpt)
    with open(os.path.join(vdir, "meta.json")) as f:
        meta = json.load(f)
    meta["format_version"] = 3
    storage.write_bytes(os.path.join(vdir, "meta.json"),
                        json.dumps(meta).encode())
    storage.write_manifest(vdir)
    with pytest.raises(ValueError, match="unknown checkpoint format 3"):
        load_checkpoint(ckpt, e.config)
