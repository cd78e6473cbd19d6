"""MeshIndex/MeshSearcher — the mesh-sharded serving path (VERDICT r1 #1).

Runs on the 8-virtual-device CPU mesh (conftest). The mesh engine must be
result-equivalent to the single-device engine: global IDF via psum equals
single-shard IDF because stats are globalized across the mesh.
"""

import numpy as np
import pytest

from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.utils.config import Config

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
    # these tests cover the COO mesh layout's internals (snapshot.arrays,
    # ShardedArrays lifecycle); the ELL layout has its own suite in
    # test_mesh_ell.py
    kw.setdefault("mesh_layout", "coo")
    cfg = Config(documents_path=str(tmp_path / sub), engine_mode=mode,
                 min_doc_capacity=8, min_nnz_capacity=256,
                 min_vocab_capacity=64, query_batch=4, max_query_terms=8,
                 **kw)
    return Engine(cfg)


def results(engine, queries=QUERIES, k=None, unbounded=False):
    # ties broken by name: doc-id order differs between layouts, so the
    # within-tie order is not part of the equivalence contract
    return [sorted(((h.name, round(h.score, 4)) for h in
                    engine.search(q, k=k, unbounded=unbounded)),
                   key=lambda nv: (-nv[1], nv[0]))
            for q in queries]


class TestEquivalence:
    @pytest.mark.parametrize("model", ["bm25", "tfidf", "tfidf_cosine"])
    def test_mesh_equals_local(self, tmp_path, model):
        mesh = make_engine(tmp_path, "m", "mesh", model=model)
        local = make_engine(tmp_path, "l", "local", model=model)
        for e in (mesh, local):
            for name, text in TEXTS.items():
                e.ingest_text(name, text)
            e.commit()
        assert mesh.index.mesh.devices.size == 8
        assert results(mesh) == results(local)

    def test_unbounded_parity_equals_local(self, tmp_path):
        mesh = make_engine(tmp_path, "mu", "mesh")
        local = make_engine(tmp_path, "lu", "local")
        for e in (mesh, local):
            for name, text in TEXTS.items():
                e.ingest_text(name, text)
            e.commit()
        assert (results(mesh, unbounded=True)
                == results(local, unbounded=True))

    def test_incremental_append_equals_local(self, tmp_path):
        mesh = make_engine(tmp_path, "mi", "mesh")
        local = make_engine(tmp_path, "li", "local")
        items = list(TEXTS.items())
        for name, text in items:
            local.ingest_text(name, text)
        local.commit()
        # mesh: 1 initial build + incremental on-device appends
        for i in range(0, len(items), 3):
            for name, text in items[i:i + 3]:
                mesh.ingest_text(name, text)
            mesh.commit()
        assert mesh.index.appends >= 1, "appends must be on-device"
        assert results(mesh) == results(local)


class TestLifecycle:
    def test_delete_on_mesh(self, tmp_path):
        e = make_engine(tmp_path, "del", "mesh")
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        assert e.delete("b.txt")
        assert not e.delete("b.txt")
        e.commit()
        names = [h.name for h in e.search("fox", k=10)]
        assert "b.txt" not in names
        assert "a.txt" in names

    def test_upsert_on_mesh(self, tmp_path):
        e = make_engine(tmp_path, "up", "mesh")
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        e.ingest_text("a.txt", "replacement narwhal content")
        e.commit()
        assert [h.name for h in e.search("narwhal")] == ["a.txt"]
        assert "a.txt" not in [h.name for h in e.search("quick")]
        assert e.index.num_live_docs == len(TEXTS)

    def test_snapshot_isolation_across_delete(self, tmp_path):
        e = make_engine(tmp_path, "iso", "mesh")
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        snap1 = e.index.snapshot
        live1 = np.asarray(snap1.arrays.live).copy()
        e.delete("a.txt")
        e.commit()
        assert (np.asarray(snap1.arrays.live) == live1).all()
        assert (np.asarray(e.index.snapshot.arrays.live).sum()
                == live1.sum() - 1)

    def test_vocab_growth_reshards(self, tmp_path):
        e = make_engine(tmp_path, "vg", "mesh")
        for name, text in list(TEXTS.items())[:4]:
            e.ingest_text(name, text)
        e.commit()
        cap0 = e.index.snapshot.arrays.vocab_cap
        r0 = e.index.rebuilds
        # flood the vocabulary past its capacity bucket
        for i in range(4):
            e.ingest_text(f"v{i}.txt",
                          " ".join(f"neo{i}_{j}" for j in range(40)))
        e.commit()
        assert e.vocab.capacity() > cap0
        assert e.index.snapshot.arrays.vocab_cap >= e.vocab.capacity()
        assert e.index.rebuilds > r0
        assert [h.name for h in e.search("neo2_7")] == ["v2.txt"]
        # old docs still searchable after the re-shard
        assert "a.txt" in [h.name for h in e.search("fox", k=10)]

    def test_capacity_overflow_reshards(self, tmp_path):
        e = make_engine(tmp_path, "cap", "mesh")
        e.ingest_text("seed.txt", "alpha beta gamma")
        e.commit()
        r0 = e.index.rebuilds
        # far more docs than the initial doc/nnz buckets can append
        for i in range(300):
            e.ingest_text(f"bulk{i:03d}.txt",
                          f"alpha beta token{i % 50} extra{i % 7}")
        e.commit()
        assert e.index.rebuilds > r0
        assert e.index.num_live_docs == 301
        hits = e.search("token33", k=10)
        assert len(hits) == 6   # 300/50 docs contain token33

    def test_tombstones_reclaimed_by_reshard(self, tmp_path):
        e = make_engine(tmp_path, "rec", "mesh")
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        e.delete("a.txt")
        e.commit()
        # force a re-shard: tombstone must be gone from host postings
        e.index._rebuild_locked([], e.vocab.capacity())
        assert all(d.live for sd in e.index._shard_docs for d in sd)
        assert e.index.num_live_docs == len(TEXTS) - 1


class TestCheckpoint:
    def test_engine_checkpoint_roundtrip(self, tmp_path):
        from tfidf_tpu.engine.checkpoint import (load_checkpoint,
                                                 save_checkpoint)
        e = make_engine(tmp_path, "ck", "mesh")
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        save_checkpoint(e, str(tmp_path / "ckpt"))
        e2 = load_checkpoint(str(tmp_path / "ckpt"), e.config)
        assert results(e) == results(e2)

    def test_sharded_arrays_roundtrip(self, tmp_path):
        from tfidf_tpu.parallel.sharded import (load_sharded_arrays,
                                                save_sharded_arrays)
        e = make_engine(tmp_path, "ark", "mesh")
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
        arrays = e.index.snapshot.arrays
        path = str(tmp_path / "arrays.npz")
        save_sharded_arrays(arrays, path)
        restored = load_sharded_arrays(path, e.index.mesh)
        for f in ("tf", "term", "doc", "doc_len", "df", "n_live",
                  "nnz_used", "live", "len_sum"):
            assert (np.asarray(getattr(restored, f))
                    == np.asarray(getattr(arrays, f))).all(), f
        # restored arrays serve searches directly
        import dataclasses
        e.index.snapshot = dataclasses.replace(e.index.snapshot,
                                               arrays=restored)
        assert sorted(h.name for h in e.search("lorem")) == ["c.txt",
                                                             "f.txt"]

    def test_mesh_shape_mismatch_rejected(self, tmp_path):
        from tfidf_tpu.parallel.mesh import make_mesh
        from tfidf_tpu.parallel.sharded import (load_sharded_arrays,
                                                save_sharded_arrays)
        e = make_engine(tmp_path, "mm", "mesh")
        e.ingest_text("a.txt", "alpha")
        e.commit()
        path = str(tmp_path / "a.npz")
        save_sharded_arrays(e.index.snapshot.arrays, path)
        import jax
        other = make_mesh((2, 1), devices=jax.devices()[:2])
        with pytest.raises(ValueError, match="rebuild"):
            load_sharded_arrays(path, other)


@pytest.mark.parametrize("layout", ["coo", "ell"])
def test_index_size_is_readable_during_concurrent_ingest(tmp_path, layout):
    """The leader polls ``/worker/index-size`` while upload handlers
    ingest. On a mesh worker that read iterated the pending-doc dict
    unlocked and raised "dictionary changed size during iteration"
    under a real ingest — the leader then dropped the worker as sick
    (found by the four-chip smoke run). More threads than cores and a
    short switch interval make the interleaving certain."""
    import sys
    import threading

    engine = make_engine(tmp_path, f"size-{layout}", "mesh",
                         mesh_layout=layout)
    stop = threading.Event()
    errors: list[BaseException] = []

    def ingest(t: int) -> None:
        i = 0
        while not stop.is_set():
            engine.index.add_document_arrays(
                f"w{t}-{i}", np.asarray([1, 2, 3 + i % 50], np.int32),
                np.ones(3, np.float32), 3.0)
            i += 1

    def poll() -> None:
        try:
            last = 0
            for _ in range(300):
                size = engine.index_size_bytes()
                assert size >= last   # adds only: never shrinks
                last = size
        except BaseException as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        writers = [threading.Thread(target=ingest, args=(t,))
                   for t in range(16)]
        reader = threading.Thread(target=poll)
        for th in writers + [reader]:
            th.start()
        reader.join(timeout=60)
        stop.set()
        for th in writers:
            th.join(timeout=10)
        assert not reader.is_alive()
        assert not any(th.is_alive() for th in writers)
    finally:
        sys.setswitchinterval(old)
        stop.set()
    assert not errors, errors
