"""``msmarco4m-mesh``'s ``docs`` keeps every seed on ONE compiled program.

On the mesh a run's seed decides which documents fall in which docs-shard
(round-robin over the seed's order), and every ELL block's row capacity is
the power of two above its fullest shard. At 4,000,000 passages the
33-48-term block's fullest shard came within 67 rows of 524,288 in the
worst of 64 seeds: the next unlucky seed would have doubled the block,
compiled another program and run a quarter more kernel work (the fault PR
23 found for one shard). The configuration holds 3,900,000 for that
reason; this is the arithmetic, on the host alone (numpy over
``benchmarks/lib/data.py``'s corpus law, no device), so that a later
change of ``docs`` cannot walk back into the trap unnoticed.

``msmarco-doc`` (one chip, whole documents) is held the same way at the
end of the file: its two blocks, at the 384 and the 512 rung of
``ops/ell.py``'s ladder, as its configuration states them; a docs-shard
of ``msmarco-doc-mesh`` (the same law, 400,000 documents a shard of a
(4, 1) mesh) by that draw's shares. And
``msmarco-full`` (one chip, the passage collection in one index): its
eleven blocks, the rungs over ``ELL_BLOCK_ROWS_MAX`` rows cut into full blocks
and a last one, that last one clear of its power of two.
"""

import importlib.util
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from tfidf_tpu.ops.csr import next_capacity
from tfidf_tpu.ops.ell import ELL_BLOCK_ROWS_MAX, ELL_WIDTH_LADDER
from tfidf_tpu.parallel.mesh_ell import mesh_ell_widths

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_lib(name: str):
    """``benchmarks/lib/<name>.py`` by its path (``tests/`` has a ``data``
    and an ``oracle`` of its own on ``sys.path``)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(ROOT, "benchmarks", "lib",
                                      name + ".py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module     # dataclasses look the module up
    spec.loader.exec_module(module)
    return module


data = bench_lib("data")

SEEDS = [2147483659 + 7919 * i for i in range(32)]
MIN_ROWS = 256          # build_mesh_ell's floor of a block's rows
ELL_WIDTHS = mesh_ell_widths()   # passages: no row past 256, ten buckets
CLEAR = 0.02            # of its capacity, every block's fullest shard


def _spec(name: str = "msmarco4m-mesh") -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _distinct_terms_by_chunk(args: dict) -> list[np.ndarray]:
    """Distinct terms of every document, chunk by chunk of the corpus in
    ``corpus_seed``'s own order (a run's seed only reorders them)."""
    bounds = np.linspace(0, args["docs"],
                         data.CORPUS_CHUNKS + 1).astype(np.int64)
    sizes = np.diff(bounds)
    with ThreadPoolExecutor(4) as ex:
        return list(ex.map(
            lambda c: data._corpus_chunk(
                args["corpus_seed"], c, int(sizes[c]), args["vocab"],
                args["doc_len_mean"], args["doc_len_min"], args["zipf_a"],
                0)[0], range(data.CORPUS_CHUNKS)))


def _in_seed_order(per_chunk: list[np.ndarray], seed: int) -> np.ndarray:
    """``make_corpus``'s order of the documents for ``seed`` (its chunk
    permutation and rotations), applied to the per-document counts."""
    rng = np.random.default_rng([seed, data._TAG_ORDER])
    order = rng.permutation(data.CORPUS_CHUNKS)
    rotate = [int(rng.integers(0, max(len(per_chunk[c]), 1)))
              for c in range(data.CORPUS_CHUNKS)]
    return np.concatenate([np.roll(per_chunk[c], -rotate[c])
                           for c in order])


def _fullest_shard(distinct: np.ndarray, shards: int) -> np.ndarray:
    """Documents of the fullest docs-shard in every ``ELL_WIDTHS`` bucket
    (``build_mesh_ell``: the narrowest bucket that holds the document;
    wider than the widest rides the widest), dealt ``i % shards``."""
    asc = np.asarray(sorted(ELL_WIDTHS))
    bucket = np.minimum(np.searchsorted(asc, distinct, side="left"),
                        len(asc) - 1)
    need = np.stack([np.bincount(bucket[s::shards], minlength=len(asc))
                     for s in range(shards)])
    return need.max(axis=0)[::-1]           # ELL_WIDTHS runs wide to narrow


def test_seed_order_is_make_corpus_order():
    """The shortcut above (per-document counts reordered, not the corpus
    redrawn) gives what ``make_corpus`` gives, at a size a test can draw
    twice."""
    args = {**data.corpus_args(_spec()), "docs": 20000, "vocab": 20000}
    per_chunk = _distinct_terms_by_chunk(args)
    for seed in SEEDS[:2]:
        corpus = data.make_corpus(seed, **args)
        assert np.array_equal(_in_seed_order(per_chunk, seed),
                              np.diff(corpus.offsets))


@pytest.fixture(scope="module")
def fullest():
    spec = _spec()
    assert spec["engine_config"]["mesh_shape"] == [4, 1]
    per_chunk = _distinct_terms_by_chunk(data.corpus_args(spec))
    return np.stack([_fullest_shard(_in_seed_order(per_chunk, seed), 4)
                     for seed in SEEDS])


def test_every_seed_compiles_the_same_ten_blocks(fullest):
    """... those the configuration states, which the compile-only test
    (``tests/kernel_compile_worker.py``) builds its shapes from."""
    blocks = _spec()["layout"]["shard_blocks"]
    assert tuple(blocks["widths"]) == ELL_WIDTHS \
        == (256, 192, 128, 96, 64, 48, 32, 24, 16, 8)
    for per_seed in fullest:
        assert [next_capacity(int(n) or 1, MIN_ROWS)
                for n in per_seed] == blocks["rows"]


def test_fullest_shard_clears_its_capacity_by_2_percent(fullest):
    rows = np.asarray(_spec()["layout"]["shard_blocks"]["rows"])
    worst = fullest.max(axis=0)
    assert (worst <= rows * (1 - CLEAR)).all(), \
        dict(zip(ELL_WIDTHS, zip(worst.tolist(), rows.tolist())))
    # the block the trap was in: 33-48 distinct terms
    assert 500_000 < worst[ELL_WIDTHS.index(48)] < 524_288 * (1 - CLEAR)


# ---- msmarco-doc: one chip, every row past the 256 rung ---------------

@pytest.fixture(scope="module")
def doc_rungs():
    """Documents of ``msmarco-doc`` in every rung of the local ladder
    (``build_ell_from_coo``: the narrowest rung that holds the document),
    for 64 seeds' orders: ``[64, len(ELL_WIDTH_LADDER)]``."""
    per_chunk = _distinct_terms_by_chunk(
        data.corpus_args(_spec("msmarco-doc")))
    ladder = np.asarray(ELL_WIDTH_LADDER)
    assert max(int(c.max()) for c in per_chunk) <= ladder[-1]
    return np.stack([
        np.bincount(np.searchsorted(
            ladder, _in_seed_order(per_chunk, 2147483659 + 7919 * i)),
            minlength=len(ladder)) for i in range(64)])


def test_doc_cell_blocks_are_the_configurations(doc_rungs):
    """Every seed commits the two blocks ``layout.blocks`` states, each
    2% clear of its power-of-two capacity, no row at or under the 256
    rung, none past the top: no residual. (One chip packs by the
    documents' multiset, which the seed only reorders: the 64 rows are
    equal, and this says so.)"""
    blocks = _spec("msmarco-doc")["layout"]["blocks"]
    assert (doc_rungs == doc_rungs[0]).all()
    live = {w: int(n) for w, n in zip(ELL_WIDTH_LADDER, doc_rungs[0]) if n}
    assert sorted(live, reverse=True) == blocks["widths"] == [512, 384]
    assert [live[w] for w in blocks["widths"]] == [57590, 342410]
    for w, rows in zip(blocks["widths"], blocks["rows"]):
        assert next_capacity(live[w], MIN_ROWS) == rows
        assert live[w] <= rows * (1 - CLEAR), (w, live[w], rows)
    assert next_capacity(sum(live.values()), MIN_ROWS) \
        == blocks["doc_cap"]


def test_doc_mesh_shard_stays_clear_of_its_buckets(doc_rungs):
    """``msmarco-doc-mesh`` deals 1,600,000 documents of ``msmarco-doc``'s
    law round-robin over four shards, and a bucket's row capacity is the
    power of two above its FULLEST shard. The 400,000 documents drawn
    above are a sample of that law: a shard's count in a bucket is
    binomial in the bucket's share of them, and six standard deviations
    over its mean still clear the capacity the configuration states by
    2% (the 512 bucket: 57,590 + 6 x 222 against 65,536), so every seed
    commits the same twelve buckets a shard: two past 256, the ladder's
    rungs to the one that holds the widest row, and the ten every mesh
    index has, empty at their floor. (The 1.6M documents themselves,
    drawn once by the builder of PR 40: fullest shard of 32 seeds
    342,923 and 57,856, no document past 455 distinct terms.)"""
    spec = _spec("msmarco-doc-mesh")
    doc = _spec("msmarco-doc")
    for key in ("vocab", "zipf_a", "doc_len_mean", "doc_len_min",
                "corpus_seed", "query_terms", "scoring",
                "unique_term_capacity"):
        assert spec[key] == doc[key], key
    assert spec["engine_config"]["mesh_shape"] == [4, 1]
    assert spec["engine_config"]["ell_width_cap"] is None
    assert list(spec["reduced"]) == ["docs"] and spec["docs"] % 4 == 0
    shard = spec["docs"] // 4
    blocks = spec["layout"]["shard_blocks"]
    share = {w: n / doc_rungs[0].sum()
             for w, n in zip(ELL_WIDTH_LADDER, doc_rungs[0]) if n}
    assert tuple(blocks["widths"]) == mesh_ell_widths(max(share))
    assert blocks["widths"][:2] == sorted(share, reverse=True)
    for w, rows in zip(blocks["widths"], blocks["rows"]):
        p = share.get(w, 0.0)
        worst = shard * p + 6 * (shard * p * (1 - p)) ** 0.5
        assert next_capacity(int(shard * p) or 1, MIN_ROWS) == rows
        assert worst <= rows * (1 - CLEAR), (w, worst, rows)
    assert next_capacity(shard, MIN_ROWS) == blocks["doc_cap"]


# ---- msmarco-full: one chip, rungs of several blocks --------------------

def test_full_collection_blocks_are_the_configurations():
    """``msmarco-full`` commits the eleven blocks its ``layout.blocks``
    states (what ``build_ell_from_coo`` cuts: a rung's rows in full
    blocks of ``ELL_BLOCK_ROWS_MAX`` and a last one of the rest), every
    last block of a rung 2% clear of its power-of-two capacity, no row
    past the 64 rung: no residual. One chip packs by the documents'
    multiset, which a run's seed only reorders (``doc_rungs`` above
    says so for 64 seeds), so the counts are drawn once."""
    spec = _spec("msmarco-full")
    assert spec["docs"] == 6_700_000 and list(spec["reduced"]) == ["docs"]
    per_doc = np.concatenate(
        _distinct_terms_by_chunk(data.corpus_args(spec)))
    ladder = np.asarray(ELL_WIDTH_LADDER)
    rungs = np.bincount(np.searchsorted(ladder, per_doc),
                        minlength=len(ladder))
    widths, rows, live = [], [], []
    for w, n in sorted(zip(ELL_WIDTH_LADDER, rungs.tolist()),
                       reverse=True):
        while n:
            take = min(n, ELL_BLOCK_ROWS_MAX)
            widths.append(w)
            live.append(take)
            rows.append(next_capacity(take, MIN_ROWS))
            n -= take
        if live and widths[-1] == w:
            assert live[-1] <= rows[-1] * (1 - CLEAR), (w, live[-1])
    blocks = spec["layout"]["blocks"]
    assert [blocks["widths"], blocks["rows"], blocks["live"]] \
        == [widths, rows, live]
    assert sum(r == ELL_BLOCK_ROWS_MAX for r in rows) == 6 \
        and len(rows) == 11
    assert next_capacity(int(per_doc.shape[0]), MIN_ROWS) \
        == blocks["doc_cap"]
