"""``assemble_hits`` (engine/searcher.py): the hit lists of a fetched
top-k block built in bulk, against the per-element loop it replaced.

The loop is kept HERE as the plain reference (it was
``Searcher._assemble`` and, a second time, ``MeshSearcher._assemble_hits``
until PR 36; since PR 45 every family's is ``SearchLoop._assemble``):
one numpy scalar drawn, tested and converted an entry.
The arithmetic is unchanged (no sum, no reorder), so equality is ``==``
on the lists, and the objects are the same types a caller saw before.
"""

from types import SimpleNamespace

import numpy as np
import pytest

from tfidf_tpu.engine.searcher import Searcher, SearchHit, assemble_hits
from tfidf_tpu.engine.segments import SegmentedSnapshot
from tfidf_tpu.parallel.mesh_index import MeshSearcher
from tfidf_tpu.utils.device_nemesis import DevicePoisonedOutput

NAMES = [f"doc-{i:03d}" for i in range(64)]


def loop_assemble(n, vals, ids, kk, name_of, result_order):
    """The old per-element loop, to the letter (the mesh's form: a
    ``None`` name is dropped; the local searcher never saw one)."""
    results = []
    for i in range(n):
        hits = []
        for v, d in zip(vals[i, :kk], ids[i, :kk]):
            if not (np.isfinite(v) and v > 0.0):
                continue
            name = name_of(int(d))
            if name is not None:
                hits.append(SearchHit(name, float(v)))
        if result_order == "name":
            hits.sort(key=lambda h: h.name)
        results.append(hits)
    return results


def same(got, want):
    assert got == want
    for row in got:
        assert type(row) is list
        for h in row:
            assert type(h) is SearchHit
            assert type(h.name) is str
            assert type(h.score) is float


def block(rng, n, kk, id_high=len(NAMES), id_dtype=np.int32):
    """Distinct positive float32 scores a row, descending, as a top-k
    leaves them (values no float16 holds, so a rounding would show)."""
    vals = np.sort(rng.random((n, kk), dtype=np.float32) * 37 + 0.01,
                   axis=1)[:, ::-1].copy()
    ids = np.stack([rng.permutation(id_high)[:kk] for _ in range(n)]
                   ).astype(id_dtype)
    return vals, ids


def searcher(result_order="score"):
    s = Searcher.__new__(Searcher)
    s.result_order = result_order
    return s


def plain_snap(names=NAMES):
    return SimpleNamespace(doc_names=names)


def segmented_snap():
    """Two segments of capacity 8 holding 5 and 3 documents: ids 5-7 and
    11-15 are pad slots of the padded id space."""
    snap = object.__new__(SegmentedSnapshot)
    snap.segments = [
        SimpleNamespace(doc_cap=8, n_docs=5, names=NAMES[:5]),
        SimpleNamespace(doc_cap=8, n_docs=3, names=NAMES[5:8])]
    return snap


def test_all_live():
    vals, ids = block(np.random.default_rng(1), 7, 10)
    got = assemble_hits(vals, ids, NAMES, "score")
    same(got, loop_assemble(7, vals, ids, 10, NAMES.__getitem__, "score"))
    assert all(len(r) == 10 for r in got)


def test_dead_tails():
    """-inf (a pad column), 0.0 (a document that holds no query term)
    and a negative value all end a row's hits."""
    vals, ids = block(np.random.default_rng(2), 4, 10)
    vals[0, 6:] = -np.inf
    vals[1, 3:] = 0.0
    vals[2, 8:] = -1.5
    vals[3, 4] = 0.0           # a hole, not a tail: the rest stay
    got = assemble_hits(vals, ids, NAMES, "score")
    same(got, loop_assemble(4, vals, ids, 10, NAMES.__getitem__, "score"))
    assert [len(r) for r in got] == [6, 3, 8, 9]


def test_fully_dead_row():
    vals, ids = block(np.random.default_rng(3), 3, 10)
    vals[1, :] = -np.inf
    got = assemble_hits(vals, ids, NAMES, "score")
    same(got, loop_assemble(3, vals, ids, 10, NAMES.__getitem__, "score"))
    assert got[1] == [] and len(got[0]) == len(got[2]) == 10


def test_every_row_dead():
    vals = np.zeros((3, 10), np.float32)
    ids = np.zeros((3, 10), np.int32)
    assert assemble_hits(vals, ids, NAMES, "score") == [[], [], []]


def test_kk_smaller_than_width():
    """The fetched buffer is wider than the quota: columns past ``kk``
    are another call's and never become hits."""
    vals, ids = block(np.random.default_rng(4), 5, 16)
    got = searcher()._assemble(plain_snap(), ["q"] * 5, vals, ids, 10)
    same(got, loop_assemble(5, vals, ids, 10, NAMES.__getitem__, "score"))
    assert all(len(r) == 10 for r in got)


def test_pad_rows_cut():
    """A chunk of 3 queries rides a bucket of 8: rows past the queries
    are padding and return nothing, not empty lists."""
    vals, ids = block(np.random.default_rng(5), 8, 10)
    got = searcher()._assemble(plain_snap(), ["a", "b", "c"], vals, ids,
                               10)
    same(got, loop_assemble(3, vals, ids, 10, NAMES.__getitem__, "score"))
    assert len(got) == 3


def test_result_order_name():
    vals, ids = block(np.random.default_rng(6), 6, 10)
    vals[2, 5:] = 0.0
    got = searcher("name")._assemble(plain_snap(), ["q"] * 6, vals, ids,
                                     10)
    same(got, loop_assemble(6, vals, ids, 10, NAMES.__getitem__, "name"))
    for row in got:
        assert [h.name for h in row] == sorted(h.name for h in row)
    assert got != loop_assemble(6, vals, ids, 10, NAMES.__getitem__,
                                "score")


def test_result_order_name_is_stable():
    """Two documents under one name keep their score order, as
    ``list.sort`` kept them."""
    names = ["b", "a", "a", "c"]
    vals = np.array([[4.0, 3.0, 2.0, 1.0]], np.float32)
    ids = np.array([[0, 1, 2, 3]], np.int32)
    got = assemble_hits(vals, ids, names, "name")
    same(got, loop_assemble(1, vals, ids, 4, names.__getitem__, "name"))
    assert got == [[("a", 3.0), ("a", 2.0), ("b", 4.0), ("c", 1.0)]]


def test_segmented_padded_names():
    snap = segmented_snap()
    vals, _ = block(np.random.default_rng(7), 4, 6)
    live_ids = [0, 1, 2, 3, 4, 8, 9, 10]
    rng = np.random.default_rng(8)
    ids = np.stack([rng.permutation(live_ids)[:6] for _ in range(4)]
                   ).astype(np.int32)
    vals[3, 4:] = 0.0          # as the pad slots score: their ids 5, 12
    ids[3, 4:] = [5, 12]
    got = searcher()._assemble(snap, ["q"] * 4, vals, ids, 6)
    same(got, loop_assemble(4, vals, ids, 6,
                            snap.doc_names.__getitem__, "score"))
    assert got[0][0].name == snap.doc_names[int(ids[0, 0])]
    assert len(got[3]) == 4


@pytest.mark.parametrize("result_order", ["score", "name"])
def test_mesh_name_of_none(result_order):
    """A mesh shard's pad row has no name: that hit is dropped, the
    row's others stay and the next row starts where it should."""
    gone = {3, 17, 40}
    names = [None if gid in gone else name
             for gid, name in enumerate(NAMES)]

    vals, gids = block(np.random.default_rng(9), 6, 10,
                       id_dtype=np.int64)
    gids[0, 2], gids[0, 9], gids[4, 0] = 3, 17, 40
    gids[2, :] = [3, 17, 40] * 3 + [3]     # a row that loses every hit
    vals[5, 7:] = -np.inf
    mesh = MeshSearcher.__new__(MeshSearcher)
    mesh.result_order = result_order
    got = mesh._assemble(plain_snap(names), ["q"] * 6, vals, gids, 10)
    same(got, loop_assemble(6, vals, gids, 10, names.__getitem__,
                            result_order))
    assert got[2] == []
    assert all(h.name is not None for row in got for h in row)


def test_nan_row_raises_with_the_offending_query_only():
    vals, ids = block(np.random.default_rng(10), 4, 10)
    vals[2, 1] = np.nan
    with pytest.raises(DevicePoisonedOutput) as ei:
        searcher()._assemble(plain_snap(), ["q0", "q1", "q2", "q3"],
                             vals, ids, 10)
    assert ei.value.queries == ("q2",)
    # a NaN in a PAD row (past the queries) blames nobody
    got = searcher()._assemble(plain_snap(), ["q0", "q1"], vals, ids, 10)
    same(got, loop_assemble(2, vals, ids, 10, NAMES.__getitem__, "score"))


def test_one_query_block():
    """The ``/worker/process`` shape, 1 x 10: one path for every size."""
    vals, ids = block(np.random.default_rng(11), 1, 10)
    vals[0, 7:] = -np.inf
    got = assemble_hits(vals, ids, NAMES, "score")
    same(got, loop_assemble(1, vals, ids, 10, NAMES.__getitem__, "score"))


def test_rank_all_block():
    """Parity mode: ``kk`` is the corpus, most of a row is dead, ids
    are int64 (``MeshSearcher._rank_all``)."""
    rng = np.random.default_rng(12)
    names = [f"d{i}" for i in range(4096)]
    vals = np.where(rng.random((5, 4096)) < 0.03,
                    rng.random((5, 4096)), 0.0).astype(np.float32)
    order = np.argsort(-vals, axis=1, kind="stable")
    vals = np.take_along_axis(vals, order, axis=1)
    got = assemble_hits(vals, order.astype(np.int64), names, "score")
    same(got, loop_assemble(5, vals, order, 4096, names.__getitem__,
                            "score"))
    assert 0 < len(got[0]) < 4096


def test_views_of_a_packed_buffer():
    """What ``unpack_topk`` hands over: two non-contiguous views of one
    int32 buffer, the values bit-cast."""
    from tfidf_tpu.ops.topk import unpack_topk
    vals, ids = block(np.random.default_rng(13), 6, 10)
    vals[1, 4:] = -np.inf
    packed = np.concatenate([vals.view(np.int32), ids], axis=1)
    pv, pi = unpack_topk(packed)
    got = assemble_hits(pv, pi, NAMES, "score")
    same(got, loop_assemble(6, vals, ids, 10, NAMES.__getitem__, "score"))
