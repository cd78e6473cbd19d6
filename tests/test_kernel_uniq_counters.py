"""``kernel_uniq_live`` / ``_built``: per chunk dispatched to the fused
kernel, the batch's distinct terms and the uniq lanes of A the kernel
builds for them (sub-tiles of ``_PL_SU``) — host arithmetic
(``ops.ell.kernel_uniq_lanes``) on both searchers. ``live / built`` is
what the benchmark's ``uniq_fill.*`` read. (``_tiled``, what the whole
uniq tiles of the kernel before PR 27 would have held, went in PR 35
with its last reader.)"""

import jax
import pytest

from tfidf_tpu.engine import Engine
from tfidf_tpu.ops.ell import _PL_SU, kernel_uniq_lanes
from tfidf_tpu.parallel.mesh import make_mesh
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

KEYS = ("dispatch_chunks", "kernel_uniq_live", "kernel_uniq_built")


def _counted(fn) -> list:
    before = global_metrics.snapshot()
    fn()
    after = global_metrics.snapshot()
    return [after.get(key, 0) - before.get(key, 0) for key in KEYS]


# the benchmark cells' batches (PERF.md §4): distinct terms -> lanes built
@pytest.mark.parametrize("n_uniq, built", [
    (114, 128),      # msmarco2m.served-steady
    (352, 352),      # the served-sat cells
    (555, 576),      # wiki1m.batch
    (512, 512),      # the control: nothing to save
    (1024, 1024),
    (1, _PL_SU),
    (300, 320),
])
def test_kernel_uniq_lanes(n_uniq, built):
    assert kernel_uniq_lanes(n_uniq) == built
    assert n_uniq <= built < n_uniq + _PL_SU


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
    want = (6, kernel_uniq_lanes(6))
    assert want[1] == _PL_SU and u_cap == 256
    assert _counted(lambda: e.search_batch(QUERIES)) == [1, *want]
    # three dispatches (4 + 4 + 1 queries): 6, 6 and 3 distinct terms
    nine = (QUERIES + QUERIES[:1]) * 2 + QUERIES[:1]
    assert [len(nine), nine[-1]] == [9, "w1 w2 w3"]
    got = _counted(lambda: e.search_batch(nine))
    assert got == [3, 6 + 6 + 3, 3 * want[1]]
    assert got[1] <= got[2]
