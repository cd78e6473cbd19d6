"""O(batch) commit stats: the incremental df/N/avgdl contract.

Two pins per index family (ISSUE 15 tentpole b):

* **witness**: steady-state commits never invoke the O(corpus) full
  stat recompute — the ``df_full_recomputes`` counter moves only on
  the documented exceptional paths (first commit / vocab growth /
  mesh rebuild);
* **exact parity**: after randomized upsert → delete → merge → commit
  sequences, the incrementally maintained device df and the N/avgdl
  scalars equal a full recompute BIT-EXACTLY (df counts are integer-
  valued f32 adds — the same anti-entropy style the placement map
  uses: incremental state must always be reconcilable with a scratch
  rebuild).
"""

import numpy as np
import pytest

from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.utils.config import Config

# a fixed word pool keeps the vocabulary (and its power-of-two
# capacity bucket) stable, so no commit takes the vocab-growth resync
WORDS = [f"w{i}" for i in range(48)]


def make_engine(tmp_path, sub, mode, **kw):
    cfg = Config(documents_path=str(tmp_path / sub),
                 engine_mode="mesh" if mode == "mesh" else "local",
                 index_mode="segments" if mode == "segments"
                 else "rebuild",
                 min_doc_capacity=8, min_nnz_capacity=256,
                 min_vocab_capacity=64, query_batch=4,
                 max_query_terms=8, **kw)
    return Engine(cfg)


def rand_text(rng, n_lo=3, n_hi=12):
    n = int(rng.integers(n_lo, n_hi))
    return " ".join(WORDS[i] for i in rng.integers(0, len(WORDS), n))


def seg_oracle(index, vocab_cap):
    """Full recompute over the segment set (tombstone-inclusive df and
    totals — the exact semantics of the old per-commit pass)."""
    with index._write_lock:
        return index._stats_scratch_locked(vocab_cap)


def assert_segment_stats_exact(engine):
    index = engine.index
    snap = index.snapshot
    vocab_cap = snap.df.shape[0]
    df_o, count_o, len_o, live_o = seg_oracle(index, vocab_cap)
    np.testing.assert_array_equal(np.asarray(snap.df), df_o)
    assert float(np.asarray(snap.n_docs)) == float(count_o)
    expect_avgdl = np.float32(len_o / count_o if count_o else 1.0)
    assert float(np.asarray(snap.avgdl)) == pytest.approx(
        float(expect_avgdl), rel=1e-6)
    assert index._live_total == live_o


class TestSegmentsWitness:
    def test_steady_commits_never_full_recompute(self, tmp_path):
        e = make_engine(tmp_path, "w", "segments")
        rng = np.random.default_rng(0)
        for i in range(4):
            e.ingest_text(f"d{i}.txt", rand_text(rng))
        e.commit()
        assert e.index.df_full_recomputes == 1   # first commit only
        base = e.index.df_full_recomputes
        # appends, upserts, deletes — all steady-state
        for round_ in range(5):
            e.ingest_text(f"n{round_}.txt", rand_text(rng))
            e.ingest_text("d0.txt", rand_text(rng))      # upsert
            e.commit()
            assert_segment_stats_exact(e)
        e.delete("d1.txt")
        e.commit()
        assert_segment_stats_exact(e)
        assert e.index.df_full_recomputes == base, \
            "a steady-state commit took the O(corpus) recompute path"

    def test_vocab_growth_takes_the_resync(self, tmp_path):
        e = make_engine(tmp_path, "vg", "segments")
        e.ingest_text("a.txt", "w0 w1 w2")
        e.commit()
        base = e.index.df_full_recomputes
        # push the vocabulary over the 64-term capacity bucket
        e.ingest_text("big.txt", " ".join(f"x{i}" for i in range(80)))
        e.commit()
        assert e.index.df_full_recomputes == base + 1
        assert_segment_stats_exact(e)


class TestSegmentsRandomized:
    @pytest.mark.parametrize("seed", [0, 7])
    def test_upsert_delete_merge_commit_parity(self, tmp_path, seed):
        """max_segments=2 forces inline merges nearly every commit, so
        the splice-delta bookkeeping is exercised alongside appends,
        upserts, and tombstones — df/N/avgdl must stay bit-exact vs
        the scratch recompute, with the witness frozen after setup."""
        e = make_engine(tmp_path, f"rz{seed}", "segments",
                        max_segments=2)
        rng = np.random.default_rng(seed)
        alive = set()
        for i in range(4):
            name = f"d{i}.txt"
            e.ingest_text(name, rand_text(rng))
            alive.add(name)
        e.commit()
        base = e.index.df_full_recomputes
        next_id = 4
        for _round in range(12):
            op = rng.integers(0, 3)
            if op == 0 or not alive:                    # add
                name = f"d{next_id}.txt"
                next_id += 1
                e.ingest_text(name, rand_text(rng))
                alive.add(name)
            elif op == 1:                               # upsert
                name = sorted(alive)[int(rng.integers(0, len(alive)))]
                e.ingest_text(name, rand_text(rng))
            else:                                       # delete
                name = sorted(alive)[int(rng.integers(0, len(alive)))]
                assert e.delete(name)
                alive.discard(name)
            e.commit()
            assert_segment_stats_exact(e)
        assert e.index.df_full_recomputes == base
        assert e.index.snapshot.version >= 12
        # merges actually happened (the point of max_segments=2)
        assert len(e.index.snapshot.segments) <= 3
        # end-to-end: equal results vs a fresh rebuild engine over the
        # surviving corpus (IDF from merged segments must not drift)
        if alive:
            reb = make_engine(tmp_path, f"rzr{seed}", "rebuild")
            with e.index._write_lock:
                live_docs = {d.name: d for d in
                             e.index._live_entries_locked()}
            for name in sorted(alive):
                d = live_docs[name]
                reb.index.add_document_arrays(
                    name, d.term_ids, d.tfs, d.length)
            # share the vocabulary mapping (ids must agree)
            reb.vocab = e.vocab
            reb.searcher.vocab = e.vocab
            reb.commit()
            q = WORDS[3] + " " + WORDS[11]
            got = [(h.name, round(h.score, 5)) for h in e.search(q)]
            want = [(h.name, round(h.score, 5)) for h in reb.search(q)]
            assert got == want

    def test_cosine_commits_still_exact(self, tmp_path):
        """The cosine model reads the CURRENT dense df host-side for
        norms — the incremental path must hand it the same df the
        device sees."""
        e = make_engine(tmp_path, "cos", "segments",
                        model="tfidf_cosine")
        rng = np.random.default_rng(3)
        for i in range(4):
            e.ingest_text(f"d{i}.txt", rand_text(rng))
        e.commit()
        e.ingest_text("d9.txt", rand_text(rng))
        e.commit()
        assert_segment_stats_exact(e)
        assert any(e.search(WORDS[5]) for _ in [0])    # serves


def mesh_stats_exact(engine):
    index = engine.index
    cap = engine.vocab.capacity()
    inc = index._live_stats(cap)
    scr = index._live_stats_scratch(cap)
    assert inc[1] == scr[1]
    assert abs(inc[2] - scr[2]) < 1e-6
    np.testing.assert_array_equal(inc[0], scr[0])
    snap = index.snapshot
    if snap is not None and not index._df_delta.journal:
        np.testing.assert_array_equal(
            np.asarray(snap.df_g)[:scr[0].shape[0]], scr[0])


class TestMeshWitness:
    def test_steady_append_commits_never_recompute(self, tmp_path):
        e = make_engine(tmp_path, "mw", "mesh")
        rng = np.random.default_rng(5)
        for i in range(6):
            e.ingest_text(f"d{i}.txt", rand_text(rng))
        e.commit()
        # the first commit is a rebuild (base construction) — the one
        # sanctioned O(corpus) resync
        assert e.index.df_full_recomputes == e.index.rebuilds == 1
        for round_ in range(3):
            e.ingest_text(f"n{round_}.txt", rand_text(rng))
            e.ingest_text("d0.txt", rand_text(rng))      # upsert
            e.commit()
            mesh_stats_exact(e)
        e.delete("d1.txt")
        e.commit()
        mesh_stats_exact(e)
        # witness only ever tracks rebuilds, never steady commits
        assert e.index.df_full_recomputes == e.index.rebuilds
