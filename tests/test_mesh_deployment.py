"""The ``msmarco4m-mesh`` deployment, held to its plain reference on the CPU.

``benchmarks/configs/msmarco4m-mesh.json`` is read as the harness reads it:
the engine is built from its ``engine_config`` on a (4, 1) ("docs",
"terms") mesh of the virtual devices ``tests/conftest.py`` gives, loaded
the way ``benchmarks/lib/worker_main.build_engine`` loads it (vocabulary
in id order, ``bulk_load_packed``, ``commit``) from
``benchmarks/lib/data.make_corpus`` at the rehearsal's size, and its
answers go through ``benchmarks/lib/oracle.compare`` — the float64 BM25
over ONE unsharded index that decides ``correct`` on the chip — by both
doors a query has: ``Engine.search_batch`` and a ``SearchNode``'s
``/worker/process-batch`` (on the mesh as on one chip: the searcher's
arrays, then ``pack_topk_arrays``).
"""

import json
import os

import jax
import numpy as np
import pytest

from tfidf_tpu.cluster.coordination import CoordinationCore, LocalCoordination
from tfidf_tpu.cluster.node import SearchNode, http_post
from tfidf_tpu.cluster.wire import pack_hit_lists, unpack_hit_lists
from tfidf_tpu.engine import Engine
from tfidf_tpu.parallel.mesh import make_mesh
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

from tests.test_mesh_block_capacities import ROOT, bench_lib, data

oracle = bench_lib("oracle")        # benchmarks/lib's, not tests/oracle.py

SEED, OTHER_SEED = 2147483659, 977
DOORS = ("search_batch", "process_batch")


def _config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "msmarco4m-mesh.json")) as f:
        spec = json.load(f)
    assert spec["engine_config"]["mesh_shape"] == [4, 1]
    assert spec["engine_config"]["engine_mode"] == "mesh"
    # the rehearsal's sizes; the mesh stays the deployment's (4, 1)
    spec.update(docs=spec["rehearse"]["docs"],
                vocab=spec["rehearse"]["vocab"])
    return spec


class Deployment:
    """One engine of the configuration over ``seed``'s order of the
    corpus, and the node that serves it."""

    def __init__(self, spec: dict, seed: int, tmp) -> None:
        self.spec = spec
        self.corpus = data.make_corpus(seed, **data.corpus_args(spec))
        cfg = Config(port=0, documents_path=str(tmp / "documents"),
                     index_path=str(tmp / "index")) \
            .replace(**spec["engine_config"])
        mesh = make_mesh(tuple(cfg.mesh_shape), devices=jax.devices()[:4])
        self.engine = engine = Engine(cfg, mesh=mesh)
        for i in range(spec["vocab"]):          # as worker_main does
            engine.vocab.add(f"t{i}")
        c = self.corpus
        engine.index.bulk_load_packed(
            [f"d{i}" for i in range(c.n_docs)], c.offsets, c.ids, c.tfs,
            c.lengths)
        engine.commit()
        self.core = CoordinationCore(session_timeout_s=5.0)
        self.node = SearchNode(cfg, coord=LocalCoordination(self.core, 0.1),
                               engine=engine).start(rebuild=False)

    def close(self) -> None:
        self.node.stop()
        self.core.close()

    def shard_of(self, name: str) -> int:
        return self.engine.index._placed[name][0]

    def answers(self, door: str, queries: list[str]) -> dict[int, list]:
        k = self.spec["scoring"]["top_k"]
        if door == "search_batch":
            got = [[(h.name, h.score) for h in hits]
                   for hits in self.engine.search_batch(queries, k=k)]
        else:
            got = unpack_hit_lists(http_post(
                self.node.url + "/worker/process-batch",
                json.dumps({"queries": queries, "k": k}).encode()))
        return {i: [(n, float(s)) for n, s in hits]
                for i, hits in enumerate(got)}

    def reference(self, queries: list[str], **kw) -> oracle.Oracle:
        sc = self.spec["scoring"]
        return oracle.Oracle(self.corpus, queries, k1=sc["k1"], b=sc["b"],
                             top_k=sc["top_k"], **kw)

    def postings(self) -> tuple[np.ndarray, np.ndarray]:
        """(document row of every posting, document frequency by term)"""
        c = self.corpus
        rows = np.repeat(np.arange(c.n_docs), np.diff(c.offsets))
        return rows, np.bincount(c.ids, minlength=c.vocab)


@pytest.fixture(scope="module")
def spec():
    return _config()


@pytest.fixture(scope="module")
def dep(spec, tmp_path_factory):
    d = Deployment(spec, SEED, tmp_path_factory.mktemp("mesh_dep"))
    yield d
    d.close()


@pytest.fixture(scope="module")
def queries(spec):
    return data.make_queries(SEED, 64, vocab=spec["vocab"],
                             query_terms=spec["query_terms"],
                             zipf_a=spec["zipf_a"])


def _held_to_reference(dep, door, queries) -> dict[int, list]:
    got = dep.answers(door, queries)
    v = oracle.compare(dep.reference(queries), got)
    assert v["correct"], v
    assert v["numbers"]["hit_count_mismatch"]["value"] == 0
    assert v["numbers"]["answers_compared"]["value"] == len(queries)
    assert oracle.LIMIT_REL_ERR == 9.0e-4
    return got


def test_the_engine_is_the_configurations(dep, spec, queries):
    stats = dep.engine.compute_stats()
    assert dict(dep.engine.index.mesh.shape) == {"docs": 4, "terms": 1}
    assert type(dep.engine.searcher).__name__ == "MeshEllSearcher"
    assert dep.engine.searcher.query_batch == 512
    assert stats["kernel_blocks"] >= 1
    assert dep.engine.index.snapshot.total_live == spec["docs"]
    # the mesh worker's wire reply comes from the searcher's arrays (no
    # hit object is built on the way) and is the hit lists' to the byte
    k = spec["scoring"]["top_k"]
    built = global_metrics.snapshot().get("hits_built", 0)
    reply = dep.node.worker_search_batch_wire(queries, k=k)
    assert global_metrics.snapshot().get("hits_built", 0) == built
    hits = dep.engine.search_batch(queries, k=k)
    assert global_metrics.snapshot()["hits_built"] > built
    assert reply == pack_hit_lists(hits)


@pytest.mark.parametrize("door", DOORS)
def test_sample_of_64_is_correct(dep, queries, door):
    _held_to_reference(dep, door, queries)


@pytest.mark.parametrize("door", DOORS)
def test_top10_drawn_from_all_four_shards(dep, queries, door):
    got = _held_to_reference(dep, door, queries)
    spread = [i for i, hits in got.items() if len(hits) == 10
              and {dep.shard_of(n) for n, _s in hits} == {0, 1, 2, 3}]
    assert spread, "no sampled query drew its top 10 from all four shards"


def _rare_terms(dep, lo: int, hi: int, one_shard: bool) -> list[str]:
    """Queries of one term each whose ``lo..hi`` documents lie all in one
    docs-shard (documents are dealt round-robin: row % 4), or not."""
    rows, df = dep.postings()
    out = []
    for t in np.flatnonzero((df >= lo) & (df <= hi)):
        shards = set((rows[dep.corpus.ids == t] % 4).tolist())
        if (len(shards) == 1) == one_shard:
            out.append(f"t{t}")
        if len(out) == 8:
            break
    assert out, f"no term with {lo}..{hi} postings, one_shard={one_shard}"
    return out


@pytest.mark.parametrize("door", DOORS)
def test_every_match_in_one_shard(dep, door):
    qs = _rare_terms(dep, 2, 9, one_shard=True)
    got = _held_to_reference(dep, door, qs)
    for hits in got.values():
        assert len(hits) >= 2
        assert len({dep.shard_of(n) for n, _s in hits}) == 1


@pytest.mark.parametrize("door", DOORS)
def test_fewer_than_10_matches(dep, door):
    rare = _rare_terms(dep, 3, 9, one_shard=False)
    got = dep.answers(door, rare + ["t1 nosuchterm", "nosuchterm"])
    assert got.pop(len(rare) + 1) == []     # no term of the vocabulary
    # a term outside the vocabulary adds nothing to the one beside it
    v = oracle.compare(dep.reference(rare + ["t1"]), got)
    assert v["correct"], v
    assert all(3 <= len(got[i]) <= 9 for i in range(len(rare)))
    assert len(got[len(rare)]) == 10


def test_a_second_seeds_order_gives_the_same_answers(dep, spec, queries,
                                                     tmp_path_factory):
    """The run's seed deals the same documents to other shards under
    other names; statistics are global, so every query's answer is the
    same documents with the same scores."""
    other = Deployment(spec, OTHER_SEED,
                       tmp_path_factory.mktemp("mesh_dep2"))
    try:
        def by_content(d, hits):
            c = d.corpus
            rows = [int(n[1:]) for n, _s in hits]
            return {c.ids[c.offsets[r]:c.offsets[r + 1]].tobytes()
                    + c.tfs[c.offsets[r]:c.offsets[r + 1]].tobytes(): s
                    for r, (_n, s) in zip(rows, hits)}

        a = _held_to_reference(dep, "search_batch", queries)
        b = _held_to_reference(other, "process_batch", queries)
        assert any([n for n, _s in a[i]] != [n for n, _s in b[i]]
                   for i in a), "the two orders name the documents alike"
        for i in a:
            ca, cb = by_content(dep, a[i]), by_content(other, b[i])
            np.testing.assert_allclose(
                sorted(s for _n, s in a[i]), sorted(s for _n, s in b[i]),
                rtol=2e-6)
            # the same documents, but for those tied with the last place
            floor = min(ca.values(), default=0.0) * (1 + 1e-5)
            assert {d for d, s in ca.items() if s > floor} \
                == {d for d, s in cb.items() if s > floor}, i
    finally:
        other.close()


def test_bfloat16_control_is_not_correct(dep, queries):
    """The comparison would catch a lower precision on the mesh too: the
    reference with every impact rounded to bfloat16, put in the
    program's place on this corpus, is not correct."""
    ref = dep.reference(queries)
    ctl = dep.reference(queries, precision="bfloat16")
    v = oracle.compare(ref, {i: ctl.topk(i) for i in range(len(queries))})
    assert not v["correct"], v
    assert max(v["numbers"]["doc_score_rel_err"]["value"],
               v["numbers"]["rank_score_rel_err"]["value"]) \
        > 2 * oracle.LIMIT_REL_ERR
