"""Expanded queries of ~130 terms (``msmarco2m-q2d``), at a small size on
the CPU: every term of every query is scored, and a query the padded
matrices cannot hold is refused by name.

The law is the benchmark cell's (``benchmarks/configs/msmarco2m-q2d.json``
``query_terms``: 40 + Poisson(90) tokens of the corpus's Zipf law, clipped
at 256), the queries and the corpus come from the harness's one generator
(``benchmarks/lib/data.py``) and the judge is the harness's plain float64
BM25 (``benchmarks/lib/oracle.py``, nothing of ``tfidf_tpu``), by the
comparison and the limit that decide ``correct`` on the chip. A batch of
64 such queries holds more than 1,024 distinct terms, so the compiled
step's unique-term capacity is 2,048: four uniq tiles of the
(interpreted) kernel a doc tile, the last of them partly live.

* all three searcher families through the ONE loop (local, COO mesh, ELL
  mesh) agree with the reference, and the same queries cut to their 32
  heaviest terms, what ``vectorize_queries`` did in silence before, do NOT;
* a query of 200 distinct terms is scored whole at a width that admits
  it; a term repeated 50 times weighs 50;
* a query past ``max_query_terms`` raises ``TooManyQueryTerms`` with its
  count and the limit, is counted, and its batch-mates are answered; at
  the node, every door answers 400 to that request alone.
"""

import json
import os
import sys
import urllib.error

import jax
import numpy as np
import pytest

from tfidf_tpu.engine import Engine
from tfidf_tpu.engine.searcher import TooManyQueryTerms
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import global_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "lib"))
import data  # noqa: E402  (benchmarks/lib: the cell's generator)
import oracle  # noqa: E402  (benchmarks/lib: the plain reference)

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "msmarco2m-q2d.json")) as _f:
    CELL = json.load(_f)
LAW = CELL["query_terms"]
K1, B_ = CELL["scoring"]["k1"], CELL["scoring"]["b"]
DOCS, VOCAB, BATCH, WIDTH = 3000, 6000, 64, CELL[
    "engine_config"]["max_query_terms"]
FAMILIES = ("local", "mesh-coo", "mesh-ell")


@pytest.fixture(scope="module")
def corpus():
    return data.make_corpus(46, corpus_seed=CELL["corpus_seed"], docs=DOCS,
                            vocab=VOCAB, doc_len_mean=CELL["doc_len_mean"],
                            doc_len_min=CELL["doc_len_min"],
                            zipf_a=CELL["zipf_a"])


@pytest.fixture(scope="module")
def queries():
    qs = data.make_queries(46, BATCH, vocab=VOCAB, query_terms=LAW,
                           zipf_a=CELL["zipf_a"])
    lens = [len(q.split()) for q in qs]
    assert LAW["min"] <= min(lens) and max(lens) <= LAW["max"]
    assert 100 < np.mean(lens) < 160
    # several uniq tiles, the last partly live
    assert 1024 < data.distinct_terms(qs) < 2048
    assert max(len(set(q.split())) for q in qs) <= WIDTH
    return qs


def build(corpus, family: str, **cfg) -> Engine:
    """The engine over ``corpus`` as ``benchmarks/lib/worker_main.py``
    builds it: vocabulary in id order, ``bulk_load_packed``, commit.
    A 256-row block rides the (interpreted) kernel."""
    cfg = dict(query_batch=BATCH, max_query_terms=WIDTH, top_k=10,
               embedding_enabled=False, bm25_k1=K1, bm25_b=B_,
               use_pallas=True, min_doc_capacity=256,
               min_vocab_capacity=1 << 13) | cfg
    if family == "local":
        engine = Engine(Config(**cfg))
    else:
        from tfidf_tpu.parallel.mesh import make_mesh
        engine = Engine(
            Config(engine_mode="mesh", mesh_layout=family[5:], **cfg),
            mesh=make_mesh((4, 1), devices=jax.devices()[:4]))
    for i in range(corpus.vocab):
        engine.vocab.add(f"t{i}")
    engine.index.bulk_load_packed(
        [f"d{i}" for i in range(corpus.n_docs)], corpus.offsets,
        corpus.ids, corpus.tfs, corpus.lengths)
    engine.commit()
    return engine


@pytest.fixture(scope="module")
def engines(corpus):
    made: dict[str, Engine] = {}

    def get(family: str) -> Engine:
        if family not in made:
            made[family] = build(corpus, family)
        return made[family]

    return get


def judged(corpus, queries, answers) -> dict:
    """``oracle.compare`` of ``answers`` (a hit list a query) with the
    float64 reference over ``queries``."""
    ref = oracle.Oracle(corpus, queries, k1=K1, b=B_, top_k=10)
    return oracle.compare(ref, {i: [(h.name, h.score) for h in hits]
                                for i, hits in enumerate(answers)})


def heaviest(query: str, n: int) -> str:
    """``query`` cut to its ``n`` heaviest terms, ties by term id, with
    their multiplicities: what ``vectorize_queries`` kept of a longer
    query before it refused one."""
    counts = oracle.parse_query(query)
    keep = sorted(counts, key=lambda t: (-counts[t], t))[:n]
    return " ".join(f"t{t}" for t in keep for _ in range(counts[t]))


@pytest.mark.parametrize("family", FAMILIES)
def test_every_term_of_every_query_is_scored(corpus, queries, engines,
                                             family):
    engine = engines(family)
    before = {k: global_metrics.get(k) for k in (
        "query_terms_seen", "query_terms_refused", "kernel_uniq_live",
        "kernel_contract_chunks", "kernel_contract_chunks_bf16x3")}
    verdict = judged(corpus, queries, engine.search_batch(queries))
    assert verdict["correct"], verdict
    assert verdict["numbers"]["answers_compared"]["value"] == BATCH
    assert verdict["numbers"]["doc_score_rel_err"]["value"] < 1e-5
    # the compiled step: capacity 2,048, matrices as wide as the limit
    assert engine.searcher._u_floor == 2048
    assert global_metrics.get("query_terms_width") == WIDTH

    def delta(key):
        return global_metrics.get(key) - before[key]

    assert delta("query_terms_seen") == sum(
        len(set(q.split())) for q in queries)
    assert delta("query_terms_refused") == 0
    if family != "mesh-coo":     # the families that ride the kernel
        n_uniq = data.distinct_terms(queries)
        assert delta("kernel_uniq_live") == n_uniq
        # multiplicities are exact in bfloat16: three passes a chunk
        assert delta("kernel_contract_chunks") == -(-n_uniq // 128) \
            == delta("kernel_contract_chunks_bf16x3")


@pytest.mark.parametrize("family", FAMILIES)
def test_cut_to_the_32_heaviest_terms_is_not_the_answer(
        corpus, queries, engines, family):
    """The control: what the default width did to these queries without
    a word is wrong by the reference, far outside the limit the
    comparison allows."""
    cut = [heaviest(q, 32) for q in queries]
    assert max(len(set(q.split())) for q in cut) == 32
    verdict = judged(corpus, queries, engines(family).search_batch(cut))
    assert not verdict["correct"], verdict
    assert verdict["numbers"]["rank_score_rel_err"]["value"] \
        > 10 * oracle.LIMIT_REL_ERR


def test_a_query_of_200_distinct_terms_is_scored_whole(corpus):
    engine = build(corpus, "local", max_query_terms=256)
    rng = np.random.default_rng(200)
    terms = rng.choice(VOCAB // 4, size=200, replace=False)
    wide = " ".join(f"t{t}" for t in terms)
    mates = ["t1 t2 t3", "t7"]
    verdict = judged(corpus, [wide, *mates],
                     engine.search_batch([wide, *mates]))
    assert verdict["correct"], verdict
    assert global_metrics.get("query_terms_width") == 256
    # ... and at the cell's width the same query is refused, by count
    narrow = build(corpus, "local")
    with pytest.raises(TooManyQueryTerms, match="200 distinct terms"):
        narrow.search_batch([wide])


@pytest.mark.parametrize("family", FAMILIES)
def test_a_term_repeated_50_times_weighs_50(corpus, engines, family):
    engine = engines(family)
    once, fifty = "t3 t40", "t3 " * 50 + "t40"
    got = engine.search_batch([once, fifty, "t3", "t40"])
    verdict = judged(corpus, [once, fifty, "t3", "t40"], got)
    assert verdict["correct"], verdict
    alone = {q: {h.name: h.score for h in engine.search_batch(
        [q], k=DOCS)[0]} for q in ("t3", "t40")}
    for hit in got[1]:
        want = 50 * alone["t3"].get(hit.name, 0.0) \
            + alone["t40"].get(hit.name, 0.0)
        assert hit.score == pytest.approx(want, rel=1e-5)
    assert [h.name for h in got[0]] != [h.name for h in got[1]]


@pytest.mark.parametrize("family", FAMILIES)
def test_a_query_past_the_width_is_refused_by_name(corpus, queries,
                                                   engines, family):
    engine = engines(family)
    wide = " ".join(f"t{t}" for t in range(100, 100 + WIDTH + 3))
    # terms the vocabulary lacks count as the analyzer counts them
    unknown = " ".join(f"nosuch{t}" for t in range(WIDTH + 1))
    mates = queries[:6]
    batch = [*mates[:3], wide, *mates[3:], unknown]
    before = global_metrics.get("query_terms_refused")
    with pytest.raises(TooManyQueryTerms) as err:
        engine.search_batch(batch)
    assert err.value.refused == ((wide, WIDTH + 3), (unknown, WIDTH + 1))
    assert err.value.limit == WIDTH
    assert f"{WIDTH + 3} distinct terms" in str(err.value) \
        and f"max_query_terms={WIDTH}" in str(err.value)
    assert global_metrics.get("query_terms_refused") == before + 2
    with pytest.raises(TooManyQueryTerms):
        engine.search_batch_arrays(batch)
    # the engine is none the worse, and the batch-mates are answered
    # once the refused are taken out, as the exception names them
    left = [q for q in batch if q not in err.value.queries]
    assert left == mates
    verdict = judged(corpus, mates, engine.search_batch(left))
    assert verdict["correct"], verdict
    assert engine.compute_stats()["state"] == "healthy"


# --------------------------------------------------------------------------
# the node's doors
# --------------------------------------------------------------------------

@pytest.fixture
def cluster(tmp_path):
    """A leader and one worker on localhost, eight terms wide."""
    from tfidf_tpu.cluster.coordination import (CoordinationCore,
                                                LocalCoordination)
    from tfidf_tpu.cluster.node import SearchNode
    from test_cluster import wait_until

    core = CoordinationCore(session_timeout_s=0.5)
    nodes = []
    for i in range(2):
        cfg = Config(
            documents_path=str(tmp_path / f"node{i}" / "documents"),
            index_path=str(tmp_path / f"node{i}" / "index"),
            port=0, replication_factor=1, min_doc_capacity=64,
            min_nnz_capacity=1 << 12, min_vocab_capacity=1 << 10,
            query_batch=4, max_query_terms=8, embedding_enabled=False)
        nodes.append(SearchNode(
            cfg, coord=LocalCoordination(core, 0.1)).start())
    assert wait_until(lambda: len(
        nodes[0].registry.get_all_service_addresses()) == 1)
    yield nodes
    for n in nodes:
        n.stop()
    core.close()


def post(url: str, body) -> tuple[int, object]:
    from tfidf_tpu.cluster.node import http_post
    try:
        return 200, http_post(url, json.dumps(body).encode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_every_door_of_the_node_refuses_by_name(cluster):
    from concurrent.futures import ThreadPoolExecutor

    from tfidf_tpu.cluster.node import http_post
    from tfidf_tpu.cluster.wire import unpack_hit_lists
    leader, worker = cluster
    words = "alpha beta gamma delta epsilon zeta eta theta iota kappa".split()
    docs = [{"name": f"{w}.txt", "text": f"{w} common " * (i + 1)}
            for i, w in enumerate(words)]
    http_post(leader.url + "/leader/upload-batch", json.dumps(docs).encode())
    wide = " ".join(words[:9])          # nine distinct terms, limit 8
    fine = " ".join(words[:8])          # as wide as the matrices
    refusal = {"error": "too many query terms", "max_query_terms": 8,
               "refused": [{"query": wide, "terms": 9}]}
    before = global_metrics.get("query_terms_refused")

    # the front door: 400 to that request alone, while the requests it
    # would have been coalesced with are answered
    def ask(q):
        return post(leader.url + "/leader/start", {"query": q})

    with ThreadPoolExecutor(8) as ex:
        replies = list(ex.map(ask, [fine, wide, "alpha", wide, "common",
                                    "beta gamma", wide, fine]))
    assert [code for code, _ in replies] == [200, 400, 200, 400, 200,
                                             200, 400, 200]
    assert all(body == refusal for code, body in replies if code == 400)
    whole = json.loads(replies[0][1])
    assert set(whole) == {w + ".txt" for w in words[:8]}
    assert json.loads(replies[2][1]).keys() == {"alpha.txt"}
    assert len(json.loads(replies[4][1])) == 10
    # a raw-text body is the same request
    code, body = 400, None
    try:
        http_post(leader.url + "/leader/start", wide.encode())
    except urllib.error.HTTPError as e:
        code, body = e.code, json.loads(e.read())
    assert (code, body) == (400, refusal)

    # the worker's own doors, for a caller that goes past the leader
    assert post(worker.url + "/worker/process", wide) == (400, refusal)
    code, body = post(worker.url + "/worker/process", fine)
    assert code == 200 and len(json.loads(body)) == 8
    code, body = post(worker.url + "/worker/process-batch",
                      {"queries": ["alpha", wide, "beta"], "k": 3})
    assert (code, body) == (400, refusal)
    code, body = post(worker.url + "/worker/process-batch",
                      {"queries": ["alpha", fine, "beta"], "k": 3})
    assert code == 200
    assert [len(hits) for hits in unpack_hit_lists(body)] == [1, 3, 1]
    # three at the front door, one raw, one a worker door
    assert global_metrics.get("query_terms_refused") == before + 6
    assert global_metrics.get("worker_batch_failures", 0) == 0
