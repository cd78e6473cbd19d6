"""Mesh ELL layout: base+delta lifecycle on the 8-virtual-device mesh.

The ELL mesh layout must be result-equivalent to both the COO mesh
layout and the single-device engine; appends land in the COO delta
without an O(corpus) rebuild; stats are live-corpus (so deletes tighten
IDF immediately, matching the local rebuild engine).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.bm25_reference import Bm25Reference
from tests.test_wide_rows import SIZES as WIDE_SIZES
from tests.test_wide_rows import TOP
from tests.test_wide_rows import VOCAB as WIDE_VOCAB
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.engine.index import DocEntry
from jax.sharding import PartitionSpec as P

from tfidf_tpu.ops.ell import (ELL_WIDTH_LADDER, _pallas_eligible,
                               _score_block, ell_scores_to_real,
                               score_block_pallas)
from tfidf_tpu.ops.scoring import (QueryBatch, _compile_queries,
                                   score_coo_compiled)
from tfidf_tpu.ops.topk import (exact_topk, merge_topk, topk_chunk_counts,
                                topk_widths)
from tfidf_tpu.parallel.mesh import make_mesh
from tfidf_tpu.parallel.mesh_ell import (build_mesh_ell,
                                         make_mesh_ell_search,
                                         mesh_ell_widths)
from tfidf_tpu.parallel.mesh_ell_index import MeshEllIndex
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

TEXTS = {
    "a.txt": "the quick brown fox jumps over the lazy dog",
    "b.txt": "a fast brown fox and a quick red fox",
    "c.txt": "lorem ipsum dolor sit amet",
    "d.txt": "the dog sleeps all day long",
    "e.txt": "red dogs chase brown foxes at dawn",
    "f.txt": "ipsum lorem amet dolor",
    "g.txt": "quick quick quick brown brown dog",
    "h.txt": "foxes and dogs and foxes again",
    "i.txt": "dawn chorus over the lazy meadow",
    "j.txt": "meadow fox naps in the red dawn",
}

QUERIES = ("fox", "brown dog", "lorem ipsum", "red dawn", "meadow")


def make_engine(tmp_path, sub, mode, **kw):
    cfg = Config(documents_path=str(tmp_path / sub), engine_mode=mode,
                 min_doc_capacity=8, min_nnz_capacity=256,
                 min_vocab_capacity=64, query_batch=4, max_query_terms=8,
                 **kw)
    return Engine(cfg)


def results(engine, queries=QUERIES, k=None):
    return [sorted(((h.name, round(h.score, 4)) for h in
                    engine.search(q, k=k)),
                   key=lambda nv: (-nv[1], nv[0]))
            for q in queries]


class TestEquivalence:
    @pytest.mark.parametrize("model", ["bm25", "tfidf"])
    def test_ell_mesh_equals_local(self, tmp_path, model):
        mesh = make_engine(tmp_path, "m", "mesh", model=model)
        local = make_engine(tmp_path, "l", "local", model=model)
        assert isinstance(mesh.index, MeshEllIndex)
        for e in (mesh, local):
            for name, text in TEXTS.items():
                e.ingest_text(name, text)
            e.commit()
        assert results(mesh) == results(local)

    def test_cosine_falls_back_to_coo(self, tmp_path):
        e = make_engine(tmp_path, "cf", "mesh", model="tfidf_cosine")
        assert not isinstance(e.index, MeshEllIndex)

    def test_parity_falls_back_to_coo(self, tmp_path):
        e = make_engine(tmp_path, "pf", "mesh", lucene_parity=True)
        assert not isinstance(e.index, MeshEllIndex)

    def test_delta_append_equals_local(self, tmp_path):
        """Appends after the initial build go to the COO delta and score
        identically to a local engine holding everything."""
        mesh = make_engine(tmp_path, "md", "mesh")
        local = make_engine(tmp_path, "ld", "local")
        items = list(TEXTS.items())
        for name, text in items:
            local.ingest_text(name, text)
        local.commit()
        for name, text in items[:8]:
            mesh.ingest_text(name, text)
        mesh.commit()          # base: 8 docs
        for name, text in items[8:]:
            mesh.ingest_text(name, text)
        mesh.commit()          # delta: 2 docs (below rebuild fraction)
        assert mesh.index.appends >= 1
        snap = mesh.index.snapshot
        assert snap.total_live == len(items)
        assert int(np.asarray(snap.delta.n_live).sum()) == 2
        assert results(mesh) == results(local)

    def test_stats_refresh_covers_delta(self, tmp_path):
        """df/N/avgdl include delta docs, and base impacts are refreshed
        — a doc in the base must see its score change when delta docs
        shift the global df."""
        e = make_engine(tmp_path, "sr", "mesh")
        e.ingest_text("a.txt", "rare shared")
        e.ingest_text("pad1.txt", "filler words only here")
        e.ingest_text("pad2.txt", "other filler words again")
        e.ingest_text("pad3.txt", "more padding text")
        e.ingest_text("pad4.txt", "yet more padding")
        e.ingest_text("pad5.txt", "final pad file")
        e.commit()
        s1 = {h.name: h.score for h in e.search("shared")}
        e.ingest_text("x.txt", "shared appears again")   # delta append
        e.commit()
        assert e.index.appends >= 1 or e.index.rebuilds >= 2
        s2 = {h.name: h.score for h in e.search("shared")}
        assert abs(s1["a.txt"] - s2["a.txt"]) > 1e-6


class TestUnboundedGuard:
    def test_parity_fallback_refuses_past_cap(self, tmp_path):
        """VERDICT r3 #7: the unbounded parity fallback is an O(corpus)
        duplicate-index replay; past the size cap it must fail fast with
        a clear error instead of stalling the node, and raising the cap
        explicitly must re-enable it."""
        e = make_engine(tmp_path, "ug", "mesh")
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        e.searcher.unbounded_parity_max_docs = 5   # below the 10 live docs
        with pytest.raises(ValueError, match="parity fallback refused"):
            e.search("fox", unbounded=True)
        e.searcher.unbounded_parity_max_docs = 1_000   # explicit opt-in
        hits = e.search("fox", unbounded=True)
        assert hits


class TestLifecycle:
    def test_delete_in_base_and_delta(self, tmp_path):
        e = make_engine(tmp_path, "del", "mesh")
        items = list(TEXTS.items())
        for name, text in items[:8]:
            e.ingest_text(name, text)
        e.commit()
        for name, text in items[8:]:
            e.ingest_text(name, text)
        e.commit()
        # b.txt lives in the base, j.txt in the delta
        assert e.delete("b.txt")
        assert e.delete("j.txt")
        e.commit()
        names = [h.name for h in e.search("fox", k=10)]
        assert "b.txt" not in names and "j.txt" not in names
        assert "a.txt" in names
        # live-corpus stats: the delete changed df -> scores match a
        # local engine over the surviving docs
        local = make_engine(tmp_path, "dl", "local")
        for name, text in items:
            if name not in ("b.txt", "j.txt"):
                local.ingest_text(name, text)
        local.commit()
        assert results(e) == results(local)

    def test_upsert_moves_doc_to_delta(self, tmp_path):
        e = make_engine(tmp_path, "up", "mesh")
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        e.ingest_text("a.txt", "replacement narwhal content")
        e.commit()
        assert [h.name for h in e.search("narwhal")] == ["a.txt"]
        assert "a.txt" not in [h.name for h in e.search("quick", k=10)]
        assert e.index.num_live_docs == len(TEXTS)

    def test_delta_growth_triggers_fold(self, tmp_path):
        e = make_engine(tmp_path, "fold", "mesh")
        e.ingest_text("seed.txt", "alpha beta")
        e.commit()
        r0 = e.index.rebuilds
        for i in range(30):     # far beyond delta_rebuild_frac
            e.ingest_text(f"d{i}.txt", f"alpha token{i % 7}")
            e.commit()
        assert e.index.rebuilds > r0
        assert e.index.num_live_docs == 31
        hits = e.search("token3", k=10)
        assert len(hits) == 4   # i in {3, 10, 17, 24} within range(30)

    def test_vocab_growth_reshards(self, tmp_path):
        e = make_engine(tmp_path, "vg", "mesh")
        for name, text in list(TEXTS.items())[:4]:
            e.ingest_text(name, text)
        e.commit()
        r0 = e.index.rebuilds
        for i in range(4):
            e.ingest_text(f"v{i}.txt",
                          " ".join(f"neo{i}_{j}" for j in range(40)))
        e.commit()
        assert e.index.rebuilds > r0
        assert [h.name for h in e.search("neo2_7")] == ["v2.txt"]
        assert "a.txt" in [h.name for h in e.search("fox", k=10)]

    def test_wide_doc_spills_to_residual(self, tmp_path):
        e = make_engine(tmp_path, "wide", "mesh", ell_width_cap=16)
        local = make_engine(tmp_path, "widel", "local", ell_width_cap=16)
        wide = " ".join(f"w{i:03d}" for i in range(100))
        for eng in (e, local):
            eng.ingest_text("wide.txt", wide)
            eng.ingest_text("a.txt", "w001 w002 and more")
            eng.commit()
        qs = ("w001", "w050 w099")
        assert results(e, qs) == results(local, qs)

    def test_name_mapping_through_permutation(self, tmp_path):
        """ELL rows are width-sorted (a permutation of insertion order):
        every doc must come back under its own name."""
        e = make_engine(tmp_path, "perm", "mesh")
        rng = np.random.default_rng(3)
        for i in range(24):
            n = int(rng.integers(1, 30))
            e.ingest_text(f"p{i:02d}.txt",
                          " ".join(f"u{i:02d}" for _ in range(n))
                          + f" mark{i:02d}")
        e.commit()
        for i in range(24):
            assert [h.name for h in e.search(f"mark{i:02d}")] == \
                [f"p{i:02d}.txt"], i

# ---------------------------------------------------------------------------
# The names by id (PR 45): a mesh snapshot's ``doc_names`` against the
# ``name_of`` methods it replaced, kept HERE to the letter as the plain
# reference (a divmod, a permutation lookup and a numpy scalar a hit)
# ---------------------------------------------------------------------------

def _name_of_ell(snap, gid):
    s, local = divmod(gid, snap.stride)
    if s >= len(snap.shard_docs):
        return None
    sd = snap.shard_docs[s]
    if local < snap.base.doc_cap:      # ELL row -> permuted ins id
        perm = snap.perms[s]
        if local >= perm.shape[0]:
            return None
        return sd[int(perm[local])].name
    delta_local = local - snap.base.doc_cap
    ins = snap.base_counts[s] + delta_local
    return sd[ins].name if ins < len(sd) else None


def _name_of_coo(snap, gid):
    doc_cap = snap.arrays.doc_cap
    sd = snap.shard_docs[gid // doc_cap]
    local = gid % doc_cap
    return sd[local].name if local < len(sd) else None


def _named(snap, name_of):
    """The snapshot's names by id, held to ``name_of`` on EVERY id of
    its id space."""
    names = snap.doc_names
    assert type(names) is list          # a C-level index a hit
    want = [name_of(snap, g) for g in range(len(names))]
    assert names == want
    with pytest.raises(IndexError):
        names[len(names)]
    return want


class TestNamesById:
    @pytest.mark.parametrize("layout", ["ell", "coo"])
    def test_every_id_class_through_append_and_reshard(self, tmp_path,
                                                       layout):
        name_of = {"ell": _name_of_ell, "coo": _name_of_coo}[layout]
        e = make_engine(tmp_path, layout, "mesh", mesh_layout=layout)
        D = e.index.D
        for i in range(24):
            e.ingest_text(f"p{i:02d}.txt", " ".join(
                f"u{i:02d}x{j}" for j in range(1 + 5 * i % 7)) + " common")
        e.commit()
        snap1 = e.index.snapshot
        names1 = _named(snap1, name_of)
        per_shard = len(names1) // D
        assert len(names1) == D * per_shard
        # a row of every shard is named, and every shard has pad rows
        for s in range(D):
            of_shard = names1[s * per_shard:(s + 1) * per_shard]
            assert sorted(n for n in of_shard if n is not None) == \
                sorted(f"p{i:02d}.txt" for i in range(s, 24, D))
            assert None in of_shard
        if layout == "ell":     # width-sorted: not the insertion order
            assert [n for n in names1 if n is not None] != \
                [d.name for sd in snap1.shard_docs for d in sd]

        # an append commit fills the SAME sequence, in place: the new
        # documents' slots (on the ELL mesh: delta slots, past the base)
        for i in range(3):
            e.ingest_text(f"new{i}.txt", f"fresh{i} common")
        assert e.delete("p05.txt")          # a tombstone keeps its name
        e.commit()
        snap2 = e.index.snapshot
        assert (e.index.rebuilds, e.index.appends) == (1, 1)
        assert snap2.doc_names is snap1.doc_names
        names2 = _named(snap2, name_of)
        added = {g: n for g, n in enumerate(names2) if names1[g] != n}
        assert sorted(added.values()) == [f"new{i}.txt" for i in range(3)]
        assert "p05.txt" in names2
        if layout == "ell":
            assert all(g % snap2.stride >= snap2.base.doc_cap
                       for g in added)
            # the slot past a shard's occupied delta slots
            g = max(added)
            assert names2[g + 1] is None
        # the OLD snapshot still resolves every id it could return
        assert all(snap1.doc_names[g] == n
                   for g, n in enumerate(names1) if n is not None)
        assert [h.name for h in e.search("fresh1")] == ["new1.txt"]

        # a re-shard lays a fresh sequence out; snapshots from before it
        # keep theirs
        for i in range(80):
            e.ingest_text(f"r{i:02d}.txt", f"more{i % 5} common")
        e.commit()
        snap3 = e.index.snapshot
        assert e.index.rebuilds == 2
        assert snap3.doc_names is not snap2.doc_names
        names3 = _named(snap3, name_of)
        assert "p05.txt" not in names3      # the re-shard dropped it
        assert sorted(n for n in names3 if n is not None) == sorted(
            d.name for d in e.index.live_entries())
        assert snap2.doc_names == names2
        assert [h.name for h in e.search("u07x0")] == ["p07.txt"]

    @pytest.mark.parametrize("layout", ["ell", "coo"])
    def test_nan_row_of_a_mesh_step_raises_naming_the_query(
            self, tmp_path, layout):
        """The poison check is the loop's, so every family's: a NaN a
        mesh step hands back is ``DevicePoisonedOutput`` with the
        offending query, from ``search`` and from ``search_arrays``
        (until PR 45 the mesh loop handed it to the caller)."""
        from tfidf_tpu.utils.device_nemesis import DevicePoisonedOutput
        e = make_engine(tmp_path, layout, "mesh", mesh_layout=layout)
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        queries = ["fox", "brown dog", "meadow"]
        assert all(e.searcher.search(queries, k=3))
        step_of = e.searcher._get_search_fn

        def poisoned(kk, depth):
            def step(*args):
                packed = np.array(step_of(kk, depth)(*args))
                packed[1, 0] = np.float32(np.nan).view(np.int32)
                return packed
            return step

        e.searcher._get_search_fn = poisoned
        for search in (e.searcher.search, e.searcher.search_arrays):
            with pytest.raises(DevicePoisonedOutput) as ei:
                search(queries, k=3)
            assert ei.value.queries == ("brown dog",)


class TestIncrementalStats:
    """Incremental df/N/avgdl must equal a from-scratch recompute after
    any mix of adds, upserts (base/delta/pending), and deletes."""

    def _check(self, e):
        cap = e.vocab.capacity()
        inc = e.index._live_stats(cap)
        scr = e.index._live_stats_scratch(cap)
        assert inc[1] == scr[1], "live count"
        assert abs(inc[2] - scr[2]) < 1e-6, "length sum"
        np.testing.assert_array_equal(inc[0], scr[0])
        # the DEVICE-resident replicated df (maintained by journaled
        # sparse scatters between rebuilds) must match the host truth
        snap = e.index.snapshot
        if snap is not None and not e.index._df_delta.journal:
            dev = np.asarray(snap.df_g)
            want, _n, _l = e.index._live_stats(dev.shape[0])
            np.testing.assert_array_equal(dev, want)

    def test_stats_track_mutations(self, tmp_path):
        e = make_engine(tmp_path, "inc", "mesh")
        for name, text in list(TEXTS.items())[:6]:
            e.ingest_text(name, text)
        self._check(e)
        e.commit()
        self._check(e)
        # delta appends
        for name, text in list(TEXTS.items())[6:]:
            e.ingest_text(name, text)
        e.commit()
        self._check(e)
        # upsert pending, base, and delta docs
        e.ingest_text("zz.txt", "pending upsert one")
        e.ingest_text("zz.txt", "pending upsert two rewritten")
        self._check(e)
        e.ingest_text("a.txt", "base upsert content")       # base doc
        e.ingest_text("j.txt", "delta upsert content")      # delta doc
        self._check(e)
        e.commit()
        self._check(e)
        # deletes across all regions
        e.delete("b.txt")
        e.delete("zz.txt")
        assert not e.delete("nope.txt")
        self._check(e)
        e.commit()
        self._check(e)
        # equivalence with a local engine over the same surviving docs
        local = make_engine(tmp_path, "incl", "local")
        survivors = {n: t for n, t in TEXTS.items() if n != "b.txt"}
        survivors["a.txt"] = "base upsert content"
        survivors["j.txt"] = "delta upsert content"
        for n, t in survivors.items():
            local.ingest_text(n, t)
        local.commit()
        assert results(e) == results(local)


# ---- whole documents: rows past 256 ride the mesh's buckets -----------

# the documents of tests/test_wide_rows.py: two in every rung from 256
# up (one at the rung exactly), two past the top, a crowd of short ones
PARENT_WIDTHS = (256, 192, 128, 96, 64, 48, 32, 24, 16, 8)


def _wide_corpus():
    rng = np.random.default_rng(40)
    p = 1.0 / np.arange(1, WIDE_VOCAB + 1) ** 0.6
    docs, lengths = [], []
    for n in WIDE_SIZES:
        ids = np.sort(rng.choice(WIDE_VOCAB, size=n, replace=False,
                                 p=p / p.sum())).astype(np.int32)
        tfs = rng.integers(1, 6, size=n).astype(np.float32)
        docs.append(dict(zip(ids.tolist(), tfs.tolist())))
        lengths.append(float(tfs.sum()))
    return docs, lengths


def _wide_engine(tmp_path, shape, docs, lengths):
    cfg = Config(documents_path=str(tmp_path / "wide"), engine_mode="mesh",
                 mesh_layout="ell", min_doc_capacity=256,
                 min_nnz_capacity=1 << 16, min_vocab_capacity=1 << 14,
                 query_batch=32, embedding_enabled=False)
    assert cfg.ell_width_cap is None
    engine = Engine(cfg, mesh=make_mesh(
        shape, devices=jax.devices()[:shape[0] * shape[1]]))
    for t in range(WIDE_VOCAB):
        engine.vocab.add(f"t{t}")
    for i, d in enumerate(docs):
        engine.index.add_document_arrays(
            f"d{i}", np.asarray(list(d), np.int32),
            np.asarray(list(d.values()), np.float32), lengths[i])
    engine.commit()
    return engine


def _wide_queries(docs):
    """Per document of 200 terms or more, a query of three of its terms
    with one repeated (a multiplicity of 2): two of them from past the
    row's 256th entry where it has one (a row's terms ascend, so those
    are its largest ids), for the two documents past the top rung from
    the residual."""
    rng = np.random.default_rng(41)
    out = []
    for d in docs:
        if len(d) < 200:
            continue
        ids = sorted(d)
        tail = ids[TOP:] or ids[256:] or ids
        picks = [int(rng.choice(ids)), int(rng.choice(tail)),
                 int(rng.choice(tail))]
        out.append(" ".join(f"t{t}" for t in picks + picks[:1]))
    return out


def _assert_equals_float64_bm25(engine, docs, lengths):
    """``search_batch`` against the plain float64 BM25 over ONE
    unsharded index (global df, N, avgdl): the same hits in the same
    order, each score within rtol 1e-4, the float32 limit
    ``tests/test_wide_rows.py`` uses (float32 impacts and sums differ
    from float64 by ~1e-6 relative a term; a dropped residual or a
    truncated row misses by a whole term's impact)."""
    ref = Bm25Reference(docs, lengths, vocab=WIDE_VOCAB,
                        k1=engine.config.bm25_k1, b=engine.config.bm25_b)
    queries = _wide_queries(docs)
    got = engine.search_batch(queries, k=10)
    for q, hits in zip(queries, got):
        want = ref.run(q, 10)
        assert [h.name for h in hits] == [f"d{d}" for d, _s in want], q
        np.testing.assert_allclose([h.score for h in hits],
                                   [s for _d, s in want], rtol=1e-4)
    return len(queries)


@pytest.fixture(scope="module")
def wide_corpus():
    return _wide_corpus()


@pytest.fixture(scope="module")
def wide_engines(wide_corpus, tmp_path_factory):
    """``shape -> (engine, the gauges its commit set)``, each built
    once (the metrics are reset after every test)."""
    built = {}

    def get(shape):
        if shape not in built:
            engine = _wide_engine(tmp_path_factory.mktemp("wide"), shape,
                                  *wide_corpus)
            built[shape] = engine, global_metrics.snapshot()
        return built[shape]

    return get


class TestWideRows:
    @pytest.mark.parametrize("shape", [(4, 1), (2, 2)])
    def test_every_rung_and_a_live_residual(self, wide_corpus,
                                            wide_engines, shape):
        """One mesh engine whose documents hold 200 ... 4,996 distinct
        terms: a bucket at 256 and at every rung past it, the same on
        every shard, and a live residual for the two documents past the
        top rung: exact against the unsharded float64 reference, the
        gauges and the counter saying so."""
        docs, lengths = wide_corpus
        D, T = shape
        engine, g = wide_engines(shape)
        snap = engine.index.snapshot
        widths = mesh_ell_widths(max(WIDE_SIZES))
        assert widths[0] == TOP and widths[-len(PARENT_WIDTHS):] \
            == PARENT_WIDTHS
        assert [tuple(a.shape) for a in snap.base.impact] \
            == [(D, w, 256) for w in widths]      # [D, width, rows]
        stats = engine.compute_stats()
        assert stats["kernel_blocks"] == stats["posting_blocks"] \
            == len(widths)
        spilled = sum(n - TOP for n in WIDE_SIZES if n > TOP)
        assert snap.res_nnz == spilled == 1100
        assert g["ell_blocks"] == D * len(widths)
        assert g["ell_width_max"] == TOP
        assert g["ell_rows_padded"] == D * 256 * len(widths)
        assert g["ell_entries_padded"] == D * 256 * sum(widths)
        assert g["ell_residual_nnz"] == 1100
        assert g["ell_residual_docs"] == 2
        assert g["mesh_docs_shards"] == D and g["mesh_terms_shards"] == T
        before = global_metrics.get("residual_entries_scored")
        n = _assert_equals_float64_bm25(engine, docs, lengths)
        # every dispatched step scored the residual's 1,100 live entries
        assert global_metrics.get("residual_entries_scored") - before \
            == 1100 * -(-n // engine.config.query_batch)

    @pytest.mark.parametrize("fault", ["residual_dropped",
                                       "tail_truncated_at_256"])
    def test_comparison_sees_a_lost_posting(self, wide_corpus, wide_engines,
                                            monkeypatch, fault):
        """The comparison above fails on a snapshot without its
        residual, and on one whose buckets are cut at a row's 256th
        entry (what a mesh whose buckets stop at 256 would score
        without its residual)."""
        engine, _g = wide_engines((4, 1))
        snap = engine.index.snapshot
        base = snap.base
        if fault == "residual_dropped":
            broken = dataclasses.replace(base,
                                         res_tf=jnp.zeros_like(base.res_tf))
        else:
            broken = dataclasses.replace(base, impact=tuple(
                imp.at[:, 256:, :].set(0.0) for imp in base.impact))
        monkeypatch.setattr(snap, "base", broken)
        with pytest.raises(AssertionError):
            _assert_equals_float64_bm25(engine, *wide_corpus)


class TestOneLadder:
    @pytest.mark.parametrize("widest, cap, want", [
        (0, None, PARENT_WIDTHS), (33, None, PARENT_WIDTHS),
        (256, None, PARENT_WIDTHS), (256, 256, PARENT_WIDTHS),
        (257, None, (384,) + PARENT_WIDTHS),
        (455, None, (512, 384) + PARENT_WIDTHS),
        (455, 256, PARENT_WIDTHS), (455, 400, (384,) + PARENT_WIDTHS),
        (100, 16, (16, 8)), (TOP + 1, None, None), (10 ** 6, None, None)])
    def test_bucket_widths(self, widest, cap, want):
        """The ladder's rungs that 8 divides: always the ten to 256,
        above them up to the rung that holds the widest row, under the
        cap; past the top, the whole ladder."""
        if want is None:
            want = tuple(w for w in reversed(ELL_WIDTH_LADDER) if w % 8 == 0)
        assert mesh_ell_widths(widest, cap) == want
        assert all(w in ELL_WIDTH_LADDER for w in want)

    @pytest.mark.parametrize("shape", [(4, 1), (2, 2)])
    def test_no_row_over_256_builds_the_parents_buckets(self, shape):
        """A corpus of passages commits exactly the ten buckets, with
        the capacities, that the mesh had before it had wide ones (so
        ``msmarco4m-mesh``'s compiled step does not move): rows, dl and
        live counts laid out as a plain per-document loop lays them."""
        rng = np.random.default_rng(7)
        mesh = make_mesh(shape, devices=jax.devices()[:4])
        D = shape[0]
        entries = []
        for i in range(700):
            k = int(rng.integers(0, 257))
            entries.append(DocEntry(
                name=f"d{i}", tfs=rng.integers(1, 5, k).astype(np.float32),
                term_ids=np.sort(rng.choice(5000, k, replace=False))
                .astype(np.int32), length=float(k + i % 3)))
        per_shard = [entries[s::D] for s in range(D)]
        host, perms = build_mesh_ell(per_shard, mesh, lambda x: x * 0.5,
                                     width_cap=None, min_rows=8)
        assert tuple(a.shape[1] for a in host.tf) == PARENT_WIDTHS
        assert host.res_nnz == 0 and not host.res_tf.any()
        for s, mine in enumerate(per_shard):
            order = sorted(range(len(mine)),
                           key=lambda i: -mine[i].term_ids.shape[0])
            assert perms[s].tolist() == order
            cursor = [0] * len(PARENT_WIDTHS)
            for i in order:
                e = mine[i]
                k = e.term_ids.shape[0]
                b = max(j for j, w in enumerate(PARENT_WIDTHS) if k <= w)
                r = cursor[b]
                cursor[b] += 1
                # a bucket is [D, width, rows]: document r is a column,
                # its entries lead down the width and its pads trail
                assert (host.term[b][s, :k, r] == e.term_ids).all()
                assert (host.tf[b][s, :k, r] == e.tfs).all()
                assert not host.tf[b][s, k:, r].any()
                assert not host.term[b][s, k:, r].any()
                assert host.dl[b][s, r] == np.float32(e.length * 0.5)
            assert host.block_live[s].tolist() == cursor
            for b, n in enumerate(cursor):
                assert not host.tf[b][s, :, n:].any()
        for b, a in enumerate(host.tf):
            fullest = int(host.block_live[:, b].max())
            assert a.shape[2] == max(8, 1 << max(fullest - 1, 0).bit_length())


# ---- a shard's top-k reads its score blocks in place -------------------

# ``make_mesh_ell_search``'s step as it was before it ranked the blocks
# where the scorers wrote them, kept here as the reference: the blocks
# gathered into ELL-row order (``ell_scores_to_real``), residual, psum
# and tombstone mask applied there, the delta concatenated behind, and
# ``exact_topk`` over the whole row.
def _rearranged_mesh_search(mesh, *, k, depth, model="bm25", k1=1.2,
                            b=0.75):
    def step(df_g, n_docs, avgdl, base_live, block_live,
             res_tf, res_term, res_doc, res_dl,
             d_tf, d_term, d_doc, d_len, d_n, d_live,
             q_uniq, q_n_uniq, q_slots, q_weights, *blocks):
        q = QueryBatch(q_uniq, q_n_uniq, q_slots, q_weights)
        nb = len(blocks) // 2
        impacts = [x.reshape(x.shape[1:]) for x in blocks[:nb]]
        terms = [x.reshape(x.shape[1:]) for x in blocks[nb:]]
        (base_live, block_live, res_tf, res_term, res_doc, res_dl, d_tf,
         d_term, d_doc, d_len, d_live) = (
            x.reshape(x.shape[-1]) for x in (
                base_live, block_live, res_tf, res_term, res_doc, res_dl,
                d_tf, d_term, d_doc, d_len, d_live))
        B = q.slots.shape[0]
        doc_cap_ell, doc_cap_delta = base_live.shape[0], d_live.shape[0]
        slot_of, qc_ext = _compile_queries(q, df_g.shape[0])
        parts = [
            score_block_pallas(imp, term, q.uniq, q.n_uniq, qc_ext,
                               block_live[i])
            if _pallas_eligible(imp.shape[1], B, q.uniq.shape[0])
            else _score_block(imp, term, slot_of, qc_ext.T, 2048)
            for i, (imp, term) in enumerate(zip(impacts, terms))]
        kw = dict(model=model, k1=k1, b=b)
        ell_scores = ell_scores_to_real(parts, block_live, doc_cap_ell)
        ell_scores = ell_scores + score_coo_compiled(
            res_tf, res_term, res_doc, res_dl, df_g, slot_of, qc_ext,
            n_docs, avgdl, None, chunk=min(1 << 10, res_tf.shape[0]), **kw)
        ell_scores = jax.lax.psum(ell_scores, "terms") * base_live[None, :]
        delta_scores = score_coo_compiled(
            d_tf, d_term, d_doc, d_len, df_g, slot_of, qc_ext, n_docs,
            avgdl, None, chunk=min(1 << 17, d_tf.shape[0]), **kw)
        delta_scores = jax.lax.psum(delta_scores, "terms") * d_live[None, :]
        vals, ids = exact_topk(
            jnp.concatenate([ell_scores, delta_scores], axis=1),
            jnp.int32(doc_cap_ell) + d_n.reshape(()), k=k)
        gids = (jax.lax.axis_index("docs").astype(jnp.int32)
                * jnp.int32(doc_cap_ell + doc_cap_delta) + ids)
        return merge_topk(jax.lax.all_gather(vals, "docs"),
                          jax.lax.all_gather(gids, "docs"), k=depth)

    def search(base, delta, df_g, n_docs, avgdl, q):
        docs, split = P("docs", None), P("docs", "terms", None)
        in_specs = ((P(None), P(), P(), docs, docs, split, split, split,
                     docs, split, split, split, docs, P("docs"), docs,
                     P(None), P(), P(None, None), P(None, None))
                    + (split,) * base.n_buckets * 2)
        return jax.shard_map(
            step, mesh=mesh, in_specs=in_specs, out_specs=(P(), P()),
            check_vma=False)(
            df_g, n_docs, avgdl, base.live, base.block_live,
            base.res_tf, base.res_term, base.res_doc, base.res_dl,
            delta.tf, delta.term, delta.doc, delta.doc_len, delta.n_live,
            delta.live, jnp.asarray(q.uniq), jnp.asarray(q.n_uniq),
            jnp.asarray(q.slots), jnp.asarray(q.weights),
            *base.impact, *base.term)

    return jax.jit(search)


# The corpus of the in-place top-k's cases, term ids under IN_PLACE_VOCAB:
# widths capped at 32, so a shard's buckets are (32, 24, 16, 8).
IN_PLACE_VOCAB = 400
(T_TIE, T_DEAD, T_RARE, T_WIDE, T_FRESH, T_COMMON) = range(300, 306)


def _in_place_engine(tmp_path, shape):
    """A mesh engine that holds every case at once: a crowd of narrow
    documents (a bucket of 1,024 or 2,048 rows a shard), some in every
    wider bucket, three past the widest (a live residual), twelve that
    score the SAME for ``T_TIE`` from two buckets and every shard, base
    documents deleted after the commit, and a delta of twelve with one
    slot deleted."""
    cfg = Config(documents_path=str(tmp_path / "inplace"),
                 engine_mode="mesh", mesh_layout="ell", ell_width_cap=32,
                 min_doc_capacity=16, min_nnz_capacity=256,
                 min_vocab_capacity=512, query_batch=8,
                 max_query_terms=8, embedding_enabled=False)
    engine = Engine(cfg, mesh=make_mesh(
        shape, devices=jax.devices()[:shape[0] * shape[1]]))
    for t in range(IN_PLACE_VOCAB):
        engine.vocab.add(f"t{t}")
    rng = np.random.default_rng(41)

    def add(name, counts):
        ids = np.asarray(sorted(counts), np.int32)
        tfs = np.asarray([counts[int(t)] for t in ids], np.float32)
        engine.index.add_document_arrays(name, ids, tfs, float(tfs.sum()))

    def filler(n):
        return {int(t): int(rng.integers(1, 4))
                for t in rng.choice(300, size=n, replace=False)}

    for i in range(2400):
        add(f"n{i}", filler(int(rng.integers(2, 8)))
            | ({T_COMMON: 1 + i % 3} if i % 5 == 0 else {})
            | ({T_DEAD: 2} if i % 400 == 7 else {})
            | ({T_RARE: 1} if i in (100, 1201, 2302) else {}))
    for i in range(120):
        add(f"m{i}", filler(int(rng.integers(9, 31)))
            | ({T_COMMON: 2} if i % 2 else {})
            | ({T_DEAD: 1} if i % 40 == 3 else {}))
    for i in range(3):      # 52 distinct terms: 20 spill past the cap
        add(f"w{i}", {t: 1 + (t + i) % 2 for t in range(i, 250, 5)}
            | {T_WIDE: 3, T_COMMON: 1})
    for i in range(6):
        # the same tf of T_TIE and the same length, 2 and 11 distinct
        # terms: one score, from the 8 bucket and from the 16 bucket
        add(f"tie8_{i}", {T_TIE: 2, 10 + i: 18})
        add(f"tie16_{i}", {T_TIE: 2} | {20 + 10 * i + j: 1 + (j < 8)
                                        for j in range(10)})
    engine.commit()
    for i in range(12):     # f5 ties with them from its shard's delta
        add(f"f{i}", {T_TIE: 2, T_FRESH: 1, T_COMMON: 1, 299: 16} if i == 5
            else filler(4) | {T_FRESH: 1 + i % 2, T_COMMON: 1})
    engine.commit()
    for name in ("n7", "n407", "m3", "tie16_1", "n1201", "f4"):
        assert engine.delete(name)
    engine.commit()
    return engine


@pytest.fixture(scope="module")
def in_place_engines(tmp_path_factory):
    built = {}

    def get(shape):
        if shape not in built:
            built[shape] = _in_place_engine(
                tmp_path_factory.mktemp("inplace"), shape)
        return built[shape]

    return get


# case -> (query terms, the request's depth)
IN_PLACE_CASES = {
    "tombstones_in_the_base": ((T_DEAD,), 10),
    "a_residual": ((T_WIDE,), 10),
    "a_delta_with_a_deleted_slot": ((T_FRESH,), 16),
    "fewer_than_k_matches": ((T_RARE,), 10),
    "ties_across_blocks_and_shards": ((T_TIE,), 10),
    "k_10": ((T_COMMON, 17), 10),
    "k_deeper_than_a_blocks_live_rows": ((T_COMMON, 17), 64),
    "group_maxima": ((T_COMMON, 17), 1),
}


class TestTopkInPlace:
    @pytest.mark.parametrize("shape", [(4, 1), (2, 2)])
    @pytest.mark.parametrize("case", sorted(IN_PLACE_CASES))
    def test_step_equals_the_rearranged_formulation(self, in_place_engines,
                                                    shape, case):
        """The step's (values, ids) against the formulation it replaced
        on the same snapshot: values equal to the bit and ids equal,
        zeros and ties included, on a docs-sharded mesh and on one whose
        ``"terms"`` axis splits the widths (the psum over the parts)."""
        engine = in_place_engines(shape)
        snap = engine.index.snapshot
        terms, k = IN_PLACE_CASES[case]
        D = shape[0]
        lives = np.asarray(snap.shard_live)
        assert snap.res_nnz == 3 * 20 and lives[:, -1].sum() == 12
        assert (lives.sum(1) > 64).all()    # no depth here passes a shard
        qb, _ = engine.searcher._vectorize(
            [" ".join(f"t{t}" for t in terms), "t1 t2", ""], 8)
        args = (snap.base, snap.delta, snap.df_g, snap.n_docs, snap.avgdl,
                qb)
        kw = dict(engine.searcher._model_kwargs(), k=k, depth=k)
        vals, gids = make_mesh_ell_search(engine.index.mesh, **kw)(*args)
        want_vals, want_gids = _rearranged_mesh_search(
            engine.index.mesh, **kw)(*args)
        vals, gids = np.asarray(vals), np.asarray(gids)
        np.testing.assert_array_equal(vals.view(np.int32),
                                      np.asarray(want_vals).view(np.int32))
        np.testing.assert_array_equal(gids, np.asarray(want_gids))

        # and the case is in the snapshot it ran on
        hits = [(snap.doc_names[g], float(v))
                for g, v in zip(gids[0], vals[0]) if v > 0]
        names = [n for n, _v in hits]
        shard, row = np.divmod(gids[0], snap.stride)
        if case == "tombstones_in_the_base":
            assert sorted(names) == ["m43", "m83", "n1207", "n1607",
                                     "n2007", "n807"]    # less n7 n407 m3
        elif case == "a_residual":
            # T_WIDE is among a wide row's last 20 ids: past the cap
            assert sorted(names) == ["w0", "w1", "w2"]
        elif case == "a_delta_with_a_deleted_slot":
            assert sorted(names) == sorted(
                f"f{i}" for i in range(12) if i != 4)
            assert (row[:11] >= snap.base.doc_cap).all()
        elif case == "fewer_than_k_matches":
            assert sorted(names) == ["n100", "n2302"] and (vals[0, 2:] == 0
                                                           ).all()
        elif case == "ties_across_blocks_and_shards":
            # thirteen equal scores (twelve in the base, one in a
            # delta) less the deleted one: the ten lowest (shard, row)
            # of them, a shard's from its 16 bucket before its 8 bucket
            assert len(hits) == 10 and len({v for _n, v in hits}) == 1
            assert (np.diff(shard * snap.stride + row) > 0).all()
            assert len(set(shard.tolist())) == D
            widths = [n.split("_")[0] for n in names]
            assert "tie8" in widths and "tie16" in widths \
                and "tie16_1" not in names
        elif case == "k_deeper_than_a_blocks_live_rows":
            assert len(hits) == 64 and (lives[:, :3] < 64).all()
        elif case == "group_maxima":
            caps = snap.topk_block_caps
            assert topk_widths(caps[3], caps[3], 1) == (128,)
        else:
            assert len(hits) == 10

    @pytest.mark.parametrize("shape", [(4, 1), (2, 2)])
    def test_topk_counters_follow_the_shards_blocks(self, in_place_engines,
                                                    shape):
        """A dispatched mesh step raises ``topk_chunks`` / ``_skipped`` /
        ``_grouped`` by the windows of every docs-shard's top-k over its
        buckets and its delta (``topk_chunk_counts`` on the snapshot's
        host integers), as the one-chip step's ``Searcher._rank`` does
        (``tests/test_topk_blocks.py``)."""
        engine = in_place_engines(shape)
        snap = engine.index.snapshot
        caps = snap.topk_block_caps
        assert caps[:-1] == tuple(a.shape[2] for a in snap.base.impact)
        assert np.array_equal(np.asarray(snap.shard_live)[:, :-1],
                              np.asarray(snap.base.block_live))
        assert np.array_equal(np.asarray(snap.shard_live)[:, -1],
                              np.asarray(snap.delta.n_live))
        for k, grouped in ((1, shape[0]), (10, 0)):
            want = np.sum([topk_chunk_counts(caps, live, k=k)
                           for live in snap.shard_live], axis=0)
            # five blocks a shard, one window each, skipped where the
            # shard has no row that wide; at k = 1 the crowd's bucket
            # has eight groups or more and goes by their maxima
            assert want.tolist() == [
                5 * shape[0], np.count_nonzero(
                    np.asarray(snap.shard_live) == 0), grouped]
            before = global_metrics.snapshot()
            engine.search_batch([f"t{T_COMMON}"] * 9, k=k)   # two chunks
            after = global_metrics.snapshot()
            got = [after.get(key, 0) - before.get(key, 0) for key in
                   ("mesh_steps", "topk_chunks", "topk_chunks_skipped",
                    "topk_chunks_grouped")]
            assert got == [2, *(2 * want).tolist()]
