"""Blocked-ELL layout tests: build correctness, scoring parity with COO.

The ELL path must be a pure re-layout: identical scores to the chunked COO
scatter path for every model, including documents that spill into the
residual. Engine-level tests confirm the default layout produces the same
search results as layout="coo".
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.oracle import random_corpus as oracle_random_corpus
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.ops.csr import build_coo
from tfidf_tpu.ops.ell import (ELL_WIDTH_LADDER, build_ell_from_coo,
                               ell_impacts, ell_scores_to_real,
                               score_ell_batch)
from tfidf_tpu.ops.scoring import make_query_batch, score_coo_batch
from tfidf_tpu.utils.config import Config


def random_corpus(rng, n_docs=40, vocab=64, max_len=30):
    """Oracle corpus, re-sorted by distinct-term count DESC (the to_coo
    order the blocked layout requires)."""
    docs, lengths = oracle_random_corpus(rng, n_docs=n_docs, vocab=vocab,
                                         max_len=max_len)
    order = np.argsort([-len(d) for d in docs], kind="stable")
    return [docs[i] for i in order], [lengths[i] for i in order]


def random_queries(rng, vocab, B=4, T=6):
    q_terms = rng.integers(0, vocab, size=(B, T)).astype(np.int32)
    q_weights = rng.random((B, T)).astype(np.float32)
    return make_query_batch(q_terms, q_weights, min_slots=8)


def build_ell_arrays(coo, model, n_docs, avgdl, *, width_cap,
                     min_rows=8, doc_norms=None):
    """Mirror ShardIndex.commit's ELL assembly for direct op tests."""
    ell = build_ell_from_coo(coo, width_cap=width_cap, min_rows=min_rows)
    impacts, terms, live = [], [], []
    for blk in ell.blocks:
        rows_cap = blk.tf.shape[1]
        dl = np.zeros(rows_cap, np.float32)
        dl[:blk.n_rows] = coo.doc_len[blk.row0:blk.row0 + blk.n_rows]
        nrm = np.zeros(rows_cap, np.float32)
        if doc_norms is not None:
            nrm[:blk.n_rows] = doc_norms[blk.row0:blk.row0 + blk.n_rows]
        impacts.append(ell_impacts(
            jnp.asarray(blk.tf), jnp.asarray(blk.term), jnp.asarray(dl),
            jnp.asarray(coo.df), n_docs, avgdl, jnp.asarray(nrm),
            model=model))
        terms.append(jnp.asarray(blk.term))
        live.append(blk.n_rows)
    return ell, tuple(impacts), tuple(terms), jnp.asarray(
        np.asarray(live, np.int32))


class TestBuild:
    def test_roundtrip_no_spill(self, rng):
        docs, _ = random_corpus(rng)
        coo = build_coo(docs, vocab_cap=128, min_nnz_cap=1 << 10,
                        min_doc_cap=64)
        ell = build_ell_from_coo(coo, width_cap=64, min_rows=8)
        assert ell.res_nnz == 0
        # every doc's counts appear at its (blocked) row
        for d, counts in enumerate(docs):
            blk = next(b for b in ell.blocks
                       if b.row0 <= d < b.row0 + b.n_rows)
            r = d - blk.row0
            row = {int(t): float(f)
                   for t, f in zip(blk.term[:, r], blk.tf[:, r]) if f > 0}
            assert row == {t: float(f) for t, f in counts.items()}

    def test_blocks_bucketed_by_width(self, rng):
        docs, _ = random_corpus(rng, n_docs=60, vocab=128, max_len=100)
        coo = build_coo(docs, vocab_cap=256, min_nnz_cap=1 << 12,
                        min_doc_cap=64)
        ell = build_ell_from_coo(coo, width_cap=256, min_rows=8)
        widths = [b.width for b in ell.blocks]
        assert widths == sorted(widths, reverse=True)   # non-increasing
        assert len(set(widths)) == len(widths)          # distinct buckets
        # blocks tile the doc rows contiguously
        covered = 0
        for b in ell.blocks:
            assert b.row0 == covered
            covered += b.n_rows
        assert covered == len(docs)
        # padding stays bounded: blocked entries < 2x the true nnz + bucket
        padded = sum(b.tf.shape[1] * b.width for b in ell.blocks)
        assert all(b.tf.shape[0] == b.width for b in ell.blocks)
        assert padded < 2 * coo.nnz + 8 * 256

    def test_spill_to_residual(self, rng):
        docs, _ = random_corpus(rng, n_docs=10, vocab=200, max_len=150)
        coo = build_coo(docs, vocab_cap=256, min_nnz_cap=1 << 11,
                        min_doc_cap=16)
        ell = build_ell_from_coo(coo, width_cap=16, min_rows=8)
        total = sum(len(d) for d in docs)
        main = sum(int((b.tf > 0).sum()) for b in ell.blocks)
        assert main + ell.res_nnz == total
        assert ell.res_nnz > 0
        assert (np.diff(ell.res_doc) >= 0).all()

    @pytest.mark.parametrize("cap, top", [
        (20, 16), (100, 96), (256, 256), (300, 256), (384, 384),
        (512, 512), (600, 512), (4096, 4096), (5000, 4096)])
    def test_non_ladder_width_cap_conserves_entries(self, cap, top):
        """A ``width_cap`` on or off a rung, under or over the ladder's
        top, conserves every posting between blocks and residual: the
        blocks stop at the widest rung <= cap (``top``) and only what a
        document holds past THAT spills (since the ladder runs to 4096
        a cap of 512 is a rung, where it once fell back to 256)."""
        sizes = (4300, 600, 450, 300, 120, 90, 40, 3)
        docs = [{t: 1 for t in range(n)} for n in sizes]
        coo = build_coo(docs, vocab_cap=8192, min_nnz_cap=1 << 13,
                        min_doc_cap=16)
        ell = build_ell_from_coo(coo, width_cap=cap, min_rows=8)
        main = sum(int((b.tf > 0).sum()) for b in ell.blocks)
        assert main + ell.res_nnz == sum(sizes)
        assert ell.res_nnz == sum(max(n - top, 0) for n in sizes)
        assert ell.blocks[0].width == top
        assert all(b.width in ELL_WIDTH_LADDER for b in ell.blocks)

    def test_default_cap_is_the_ladders_top(self):
        """``Config.ell_width_cap`` defaults to None, no ceiling of its
        own, and the builder then cuts at the top rung: a row one wider
        than the ladder spills that one posting."""
        from tfidf_tpu.utils.config import Config
        assert Config().ell_width_cap is None
        top = ELL_WIDTH_LADDER[-1]
        docs = [{t: 1 for t in range(top + 1)}]
        coo = build_coo(docs, vocab_cap=8192, min_nnz_cap=1 << 13,
                        min_doc_cap=16)
        ell = build_ell_from_coo(coo, width_cap=Config().ell_width_cap,
                                 min_rows=8)
        assert (ell.blocks[0].width, ell.res_nnz) == (top, 1)

    @pytest.mark.parametrize("raw,want", [("512", 512), ("null", None)])
    def test_cap_from_the_environment(self, raw, want):
        """A field whose default is None reads its ``TFIDF_*`` variable
        as JSON: an int stays an int, not a string."""
        from tfidf_tpu.utils.config import load_config
        assert load_config(env={"TFIDF_ELL_WIDTH_CAP": raw}) \
            .ell_width_cap == want

    def test_unsorted_rows_rejected(self, rng):
        docs = [{1: 1}, {1: 1, 2: 1, 3: 1}]    # ascending length
        coo = build_coo(docs, vocab_cap=8, min_nnz_cap=64, min_doc_cap=8)
        with pytest.raises(AssertionError):
            build_ell_from_coo(coo, width_cap=8)

    def test_empty_corpus(self):
        coo = build_coo([], vocab_cap=32, min_nnz_cap=64, min_doc_cap=8)
        ell = build_ell_from_coo(coo, width_cap=32)
        assert ell.blocks == [] and ell.res_nnz == 0


class TestScoringParity:
    @pytest.mark.parametrize("model", ["bm25", "tfidf"])
    @pytest.mark.parametrize("width_cap", [8, 64])
    def test_ell_matches_coo(self, rng, model, width_cap):
        """Blocked ELL + residual scores == COO scatter scores."""
        docs, lengths = random_corpus(rng)
        coo = build_coo(docs, vocab_cap=128, min_nnz_cap=1 << 10,
                        min_doc_cap=64)
        qb = random_queries(rng, vocab=64)
        n_docs = jnp.float32(len(docs))
        avgdl = jnp.float32(np.mean(lengths))

        ref = score_coo_batch(
            jnp.asarray(coo.tf), jnp.asarray(coo.term), jnp.asarray(coo.doc),
            jnp.asarray(coo.doc_len), jnp.asarray(coo.df),
            qb, n_docs, avgdl, model=model, chunk=256)

        ell, impacts, terms, live = build_ell_arrays(
            coo, model, n_docs, avgdl, width_cap=width_cap)
        got = score_ell_batch(
            impacts, terms, live,
            jnp.asarray(ell.res_tf), jnp.asarray(ell.res_term),
            jnp.asarray(ell.res_doc),
            jnp.asarray(coo.doc_len), jnp.asarray(coo.df),
            qb, n_docs, avgdl, model=model)
        got = ell_scores_to_real(got, live, coo.doc_len.shape[0])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_doc_chunking_invariant(self, rng):
        """Scores identical for any doc_chunk."""
        docs, lengths = random_corpus(rng)
        coo = build_coo(docs, vocab_cap=128, min_nnz_cap=1 << 10,
                        min_doc_cap=64)
        qb = random_queries(rng, vocab=64)
        n_docs, avgdl = jnp.float32(len(docs)), jnp.float32(np.mean(lengths))
        ell, impacts, terms, live = build_ell_arrays(
            coo, "bm25", n_docs, avgdl, width_cap=64)
        ref = None
        for chunk in (8, 16, 64):
            s = score_ell_batch(
                impacts, terms, live,
                jnp.asarray(ell.res_tf), jnp.asarray(ell.res_term),
                jnp.asarray(ell.res_doc),
                jnp.asarray(coo.doc_len), jnp.asarray(coo.df),
                qb, n_docs, avgdl, model="bm25", doc_chunk=chunk)
            s = ell_scores_to_real(s, live, coo.doc_len.shape[0])
            if ref is None:
                ref = np.asarray(s)
            else:
                np.testing.assert_allclose(np.asarray(s), ref, rtol=1e-6)


class TestEngineLayouts:
    def test_engine_ell_equals_coo_results(self, tmp_path):
        texts = {
            "a.txt": "the quick brown fox jumps over the lazy dog",
            "b.txt": "a fast brown fox and a quick red fox",
            "c.txt": "lorem ipsum dolor sit amet " * 30,   # long doc
            "d.txt": "the dog sleeps all day " * 10,
        }
        results = {}
        for layout in ("ell", "coo"):
            cfg = Config(documents_path=str(tmp_path / layout),
                         scoring_layout=layout, ell_width_cap=8,
                         min_doc_capacity=8, min_nnz_capacity=256,
                         min_vocab_capacity=64, query_batch=4,
                         max_query_terms=8)
            e = Engine(cfg)
            for name, text in texts.items():
                e.ingest_text(name, text)
            e.commit()
            results[layout] = [
                e.search(q) for q in ("fox", "dog day", "lorem ipsum")]
        for hits_e, hits_c in zip(results["ell"], results["coo"]):
            assert [h.name for h in hits_e] == [h.name for h in hits_c]
            np.testing.assert_allclose([h.score for h in hits_e],
                                       [h.score for h in hits_c], rtol=1e-5)

    def test_commit_growth_reuses_executable(self, tmp_path):
        """Commits that stay within the same capacity buckets must NOT
        retrace the scoring executable (live counts are traced)."""
        # the public score_ell_batch is the nemesis dispatch seam (a
        # plain function); the compile cache lives on the jitted
        # executable behind it
        from tfidf_tpu.ops.ell import _score_ell_batch_jit as jitted
        cfg = Config(documents_path=str(tmp_path), min_doc_capacity=8,
                     min_nnz_capacity=256, min_vocab_capacity=64,
                     query_batch=4, max_query_terms=8)
        e = Engine(cfg)
        e.ingest_text("a.txt", "alpha beta gamma")
        e.commit()
        e.search("alpha")
        size0 = jitted._cache_size()
        e.ingest_text("b.txt", "alpha delta epsilon")
        e.commit()
        hits = e.search("alpha")
        assert {h.name for h in hits} == {"a.txt", "b.txt"}
        assert jitted._cache_size() == size0, "commit retraced the query path"

    def test_ell_snapshot_skips_device_coo(self, tmp_path):
        cfg = Config(documents_path=str(tmp_path), min_doc_capacity=8,
                     min_nnz_capacity=256, min_vocab_capacity=64,
                     query_batch=4, max_query_terms=8)
        e = Engine(cfg)
        e.ingest_text("x.txt", "hello world hello")
        e.commit()
        snap = e.index.snapshot
        assert snap.is_ell
        assert snap.tf is None and snap.term is None and snap.doc is None
        assert snap.ell_impacts and snap.size_bytes() > 0
        assert [h.name for h in e.search("hello")] == ["x.txt"]


class TestPallasKernel:
    """Fused Pallas gather kernel vs the XLA path (interpret mode on CPU;
    the same kernels run compiled on TPU)."""

    def _block(self, rng, rows_cap, width, vocab):
        """A random block, width-major ``[width, rows_cap]`` as the
        index holds it (drawn a document a line, then turned)."""
        imp = rng.random((rows_cap, width), dtype=np.float32)
        # distinct term ids within each row — the layout contract every
        # ELL builder guarantees (one posting per distinct term) and
        # the kernel's select chain relies on: position w draws from the
        # congruence class w mod width
        base = rng.integers(0, max(vocab // width, 1),
                            size=(rows_cap, width))
        term = (base * width
                + np.arange(width, dtype=np.int64)[None, :]
                ).astype(np.int32)
        # pad tail rows like a real block
        imp[-rows_cap // 4:] = 0.0
        term[-rows_cap // 4:] = 0
        return jnp.asarray(imp.T), jnp.asarray(term.T)

    @pytest.mark.parametrize("vocab", [1 << 12, 1 << 17])
    def test_matches_xla_block_path(self, rng, vocab):
        """The kernel vs the XLA oracle, on a small and a large
        vocabulary."""
        from tfidf_tpu.ops.ell import _score_block, score_block_pallas
        from tfidf_tpu.ops.scoring import (_compile_queries,
                                           make_query_batch)
        rows_cap, width, B = 512, 16, 64
        imp, term = self._block(rng, rows_cap, width, vocab)
        q_terms = rng.integers(0, vocab, size=(B, 4)).astype(np.int32)
        q_weights = (rng.random((B, 4), dtype=np.float32) + 0.1)
        qb = make_query_batch(q_terms, q_weights, min_slots=256)
        slot_of, qc_ext = _compile_queries(qb, vocab)
        ref = _score_block(imp, term, slot_of, qc_ext.T, 256)
        out = score_block_pallas(imp, term, jnp.asarray(qb.uniq),
                                 jnp.asarray(qb.n_uniq), qc_ext)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("width", [7, 16, 33])
    def test_matches_xla_at_width(self, rng, width):
        """The select chain at widths with and without the static tail
        (7, 33: all of it, one row of it), against the XLA oracle."""
        from tfidf_tpu.ops.ell import _score_block, score_block_pallas
        from tfidf_tpu.ops.scoring import (_compile_queries,
                                           make_query_batch)
        for vocab in (1 << 14, 1 << 16):
            rows_cap, B = 512, 32
            imp, term = self._block(rng, rows_cap, width, vocab)
            q_terms = rng.integers(0, vocab, size=(B, 4)).astype(np.int32)
            q_terms[0, 0] = int(np.asarray(term)[0, 0])   # force a hit
            q_weights = (rng.random((B, 4), dtype=np.float32) + 0.1)
            qb = make_query_batch(q_terms, q_weights, min_slots=256)
            slot_of, qc_ext = _compile_queries(qb, vocab)
            ref = np.asarray(_score_block(imp, term, slot_of, qc_ext.T,
                                          256))
            out = np.asarray(score_block_pallas(
                imp, term, jnp.asarray(qb.uniq), jnp.asarray(qb.n_uniq),
                qc_ext))
            assert np.abs(ref).max() > 0
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def _kernel_and_oracle(self, rng, width, n_uniq, weights, B=32,
                           rows_cap=512, vocab=1 << 14):
        """One block scored by the kernel and by the XLA oracle for a
        batch of exactly ``n_uniq`` distinct terms, every query
        holding ``len(weights)`` of them with those weights; also the
        host's and the wrapper's verdicts on the batch's weights."""
        from tfidf_tpu.ops.ell import (_score_block, bf16_exact,
                                       score_block_pallas)
        from tfidf_tpu.ops.scoring import (_compile_queries,
                                           make_query_batch)
        imp, term = self._block(rng, rows_cap, width, vocab)
        T = len(weights)
        # terms the block holds, so every query hits
        ids = rng.choice(np.unique(np.asarray(term)[:, :rows_cap // 2]),
                         size=n_uniq, replace=False)
        # every id is used: walk the ids T at a time, wrapping
        assert T < n_uniq <= B * T
        q_terms = ids[(np.arange(B * T) % n_uniq)].reshape(B, T)
        q_weights = np.tile(np.asarray(weights, np.float32), (B, 1))
        qb = make_query_batch(q_terms.astype(np.int32), q_weights,
                              min_slots=256)
        assert int(qb.n_uniq) == n_uniq
        slot_of, qc_ext = _compile_queries(qb, vocab)
        ref = np.asarray(_score_block(imp, term, slot_of, qc_ext.T, 256))
        out = np.asarray(score_block_pallas(
            imp, term, jnp.asarray(qb.uniq), jnp.asarray(qb.n_uniq),
            qc_ext))
        assert np.abs(ref).max() > 0
        return out, ref, bool(bf16_exact(qb.weights)), \
            bool(bf16_exact(qc_ext))

    @pytest.mark.parametrize("width", [7, 16, 33])
    @pytest.mark.parametrize("n_uniq", [127, 128, 129])
    def test_multiplicities_take_three_bf16_passes(self, rng, width,
                                                   n_uniq):
        """Term multiplicities (what the engine's queries carry) are
        exact in bfloat16: the kernel contracts A's three exact bf16
        pieces in one pass each, one under, at and one over a 128-row
        chunk, and matches the f32 oracle tighter than HIGHEST's
        tests ask."""
        out, ref, host, device = self._kernel_and_oracle(
            rng, width, n_uniq, [1, 2, 3, 256, 1, 2])
        assert host and device
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("weights", [
        [1, 2, 0.37, 3, 1], [1, 257, 2, 3, 1]],
        ids=["one-fraction", "257"])
    @pytest.mark.parametrize("width", [7, 16, 33])
    def test_inexact_weight_takes_highest(self, rng, width, weights):
        """ONE weight that bfloat16 cannot hold (a fraction; 257) in an
        otherwise integer batch: the whole batch takes the HIGHEST dot,
        as every batch did before, and still matches."""
        out, ref, host, device = self._kernel_and_oracle(
            rng, width, 129, weights)
        assert not host and not device
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_a_bf16_pass_alone_would_not_do(self, rng, monkeypatch):
        """The control: with A's lower two pieces dropped the same
        multiplicity batch misses the oracle by bfloat16's 2**-9, so
        the tests above do see the pieces."""
        from tfidf_tpu.ops import ell
        monkeypatch.setattr(
            ell, "split_bf16x3",
            lambda a: (a.astype(jnp.bfloat16),) + (jnp.zeros_like(
                a, jnp.bfloat16),) * 2)
        out, ref, _host, _device = self._kernel_and_oracle(
            rng, 16, 128, [1, 2, 3, 1, 2])
        assert np.abs(out - ref).max() > 1e-4 * np.abs(ref).max()

    @pytest.mark.parametrize("scale", [1e-6, 1e-3, 1.0, 1e3])
    def test_split_bf16x3_rebuilds_f32_bit_for_bit(self, rng, scale):
        """hi + mid + lo == a exactly: 8 + 8 + 8 significand bits, each
        remainder exact in f32; both signs, zeros, and impacts from
        1e-6 to 1e3."""
        from tfidf_tpu.ops.ell import split_bf16x3
        a = (rng.random((128, 512), dtype=np.float32) * 2 - 1) * scale
        a[rng.random(a.shape) < 0.1] = 0.0
        a = a.astype(np.float32)
        hi, mid, lo = (np.asarray(p) for p in split_bf16x3(jnp.asarray(a)))
        assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
        back = (hi.astype(np.float32) + mid.astype(np.float32)) \
            + lo.astype(np.float32)
        assert (back.view(np.uint32) == a.view(np.uint32)).all()
        assert (np.abs(mid.astype(np.float32)) > 0).any()

    @pytest.mark.parametrize("weights, exact", [
        ([1.0, 2.0, 3.0, 256.0, 0.0], True), ([0.5, 0.25, 384.0], True),
        ([257.0], False), ([1.0, 0.37], False), ([1 / 3], False)])
    def test_bf16_exact_host_and_device_agree(self, weights, exact):
        """The ONE predicate, on the host's numpy weights (the counter)
        and traced on the device (the kernel's flag)."""
        import jax

        from tfidf_tpu.ops.ell import bf16_exact, kernel_contract_chunks
        w = np.asarray(weights, np.float32)
        assert bool(bf16_exact(w)) is exact
        assert bool(jax.jit(bf16_exact)(jnp.asarray(w))) is exact
        # ... and it is the round trip through bfloat16 it stands for
        assert exact == bool(
            (w.astype(jnp.bfloat16).astype(np.float32) == w).all())
        assert kernel_contract_chunks(129, w) == (2, 2 if exact else 0)

    def test_pad_uniq_never_matches_term_zero(self, rng):
        """uniq is zero-padded but term id 0 is real: pad entries must
        not siphon term-0 impacts into the batch (the -1 mask)."""
        from tfidf_tpu.ops.ell import _score_block, score_block_pallas
        from tfidf_tpu.ops.scoring import (_compile_queries,
                                           make_query_batch)
        vocab = 64
        rows_cap, width, B = 512, 8, 8
        imp = np.abs(rng.random((width, rows_cap), dtype=np.float32))
        term = np.zeros((width, rows_cap), np.int32)   # ALL term 0
        q_terms = np.full((B, 2), 5, np.int32)         # term 0 not queried
        q_weights = np.ones((B, 2), np.float32)
        qb = make_query_batch(q_terms, q_weights, min_slots=16)
        slot_of, qc_ext = _compile_queries(qb, vocab)
        out = score_block_pallas(jnp.asarray(imp), jnp.asarray(term),
                                 jnp.asarray(qb.uniq),
                                 jnp.asarray(qb.n_uniq), qc_ext)
        assert np.asarray(out).max() == 0.0

    def test_end_to_end_engine_equivalence(self, tmp_path):
        """Engine with use_pallas on eligible shapes == engine without.
        min_doc_capacity=512 makes every block eligible (rows_cap
        512)."""
        from tfidf_tpu.engine.engine import Engine
        from tfidf_tpu.utils.config import Config

        rng = np.random.default_rng(7)
        texts = {}
        for i in range(40):
            words = rng.integers(0, 200, size=int(rng.integers(3, 30)))
            texts[f"d{i}.txt"] = " ".join(f"w{w}" for w in words)

        def build(use_pallas):
            cfg = Config(documents_path=str(tmp_path / f"{use_pallas}"),
                         min_doc_capacity=512, min_vocab_capacity=256,
                         query_batch=8, max_query_terms=8,
                         use_pallas=use_pallas)
            e = Engine(cfg)
            for n, t in texts.items():
                e.ingest_text(n, t)
            e.commit()
            return e

        ex = build(False)
        queries = ["w3 w17", "w100 w5 w9", "w42"]
        hx = [[(h.name, round(h.score, 5)) for h in ex.search(q)]
              for q in queries]
        ep = build(True)
        for q, want in zip(queries, hx):
            hp = [(h.name, round(h.score, 5)) for h in ep.search(q)]
            assert hp == want, (q, hp, want)
