"""``kernel_uniq_live`` / ``_built`` / ``_tiled``: per chunk dispatched
to the fused kernel, the batch's distinct terms, the uniq lanes of A
the kernel builds for them (sub-tiles of ``_PL_SU``) and what whole
uniq tiles would hold — host arithmetic (``ops.ell.kernel_uniq_lanes``)
on both searchers. ``live / built`` is what the benchmark's
``uniq_fill.*`` read."""

import jax
import pytest

from tfidf_tpu.engine import Engine
from tfidf_tpu.ops.ell import _PL_SU, kernel_uniq_lanes
from tfidf_tpu.parallel.mesh import make_mesh
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

KEYS = ("dispatch_chunks", "kernel_uniq_live", "kernel_uniq_built",
        "kernel_uniq_tiled")


def _counted(fn) -> list:
    before = global_metrics.snapshot()
    fn()
    after = global_metrics.snapshot()
    return [after.get(key, 0) - before.get(key, 0) for key in KEYS]


# the benchmark cells' batches (PERF.md §4): bucket, distinct terms ->
# lanes built, lanes the whole tiles hold
@pytest.mark.parametrize("n_uniq, B, u_cap, built, tiled", [
    (114, 64, 1024, 128, 512),      # msmarco2m.served-steady
    (352, 256, 1024, 352, 512),     # the served-sat cells
    (555, 512, 1024, 576, 1024),    # wiki1m.batch: a second tile begun
    (512, 512, 1024, 512, 512),     # the control: nothing to save
    (1024, 512, 1024, 1024, 1024),
    (1, 32, 256, _PL_SU, 256),
    (300, 2048, 1024, 320, 384),    # B > 1024: 128-lane uniq tiles
])
def test_kernel_uniq_lanes(n_uniq, B, u_cap, built, tiled):
    assert kernel_uniq_lanes(n_uniq, B, u_cap) == (built, tiled)
    assert n_uniq <= built <= tiled


def _ingest(e: Engine) -> None:
    for i in range(24):
        e.ingest_text(f"d{i}", " ".join(f"w{j}" for j in range(i, i + 6)))
    e.commit()


# 3 + 1 + 2 = 6 distinct known terms, "nowhere" is in no document
QUERIES = ["w1 w2 w3", "w2 w7 nowhere", "w7 w8 w9"]


@pytest.mark.parametrize("mode", ("local", "mesh"))
def test_kernel_uniq_counters_follow_the_batch(tmp_path, mode):
    cfg = Config(documents_path=str(tmp_path), min_doc_capacity=8,
                 min_nnz_capacity=256, min_vocab_capacity=64,
                 query_batch=4, max_query_terms=8)
    if mode == "mesh":
        e = Engine(cfg.replace(engine_mode="mesh", mesh_shape=(4, 1)),
                   mesh=make_mesh((4, 1), devices=jax.devices()[:4]))
    else:
        e = Engine(cfg)
    _ingest(e)
    u_cap = e.searcher._u_floor
    # one dispatch of 3 queries in a bucket of 4: 6 distinct terms
    want = (6,) + kernel_uniq_lanes(6, 4, u_cap)
    assert want[1] == _PL_SU and want[2] == u_cap == 256
    assert _counted(lambda: e.search_batch(QUERIES)) == [1, *want]
    # three dispatches (4 + 4 + 1 queries): 6, 6 and 3 distinct terms
    nine = (QUERIES + QUERIES[:1]) * 2 + QUERIES[:1]
    assert [len(nine), nine[-1]] == [9, "w1 w2 w3"]
    got = _counted(lambda: e.search_batch(nine))
    assert got == [3, 6 + 6 + 3, 3 * want[1], 3 * want[2]]
    assert got[1] <= got[2] <= got[3]
