"""Seeded corpus, queries and arrival times: the one general generator.

Everything a run feeds the system is made here from ``--seed`` and the
numbers in a configuration or traffic file; the program receives only the
generated inputs. The same seed gives the same bytes whatever the thread
count, because the corpus is cut into a FIXED number of chunks, each with
a random stream of its own.

Every seed gives the same SET of documents in another order. The
documents themselves come from the configuration's ``corpus_seed``; the
run's seed shuffles the chunks and rotates the documents inside each, so
which document is ``d<i>`` changes with the seed while the multiset of
sizes does not. The reason is the program's layout: it packs documents
into blocks by their number of distinct terms, with power-of-two row
capacities, and with a corpus drawn afresh for each seed a 1M-passage
msmarco shard's block of 33-48 distinct terms held 523.5k +- 0.6k
documents against a capacity of 524,288 — one seed in ten doubled that
block, compiled a different program and ran different work (PERF.md,
PR 23). Queries,
arrival times and the sampled answers are drawn afresh from the seed.

Shapes copied from ``bench.py`` (``make_doc_arrays``, ``make_queries``)
and ``chip_smoke.py`` (``Corpus``): documents are a Zipf token stream cut
at Poisson lengths, kept as sorted unique (term id, tf) slices — what
``add_document_arrays`` / ``bulk_load_packed`` take — and a query is a
few tokens ``t<id>`` of the same Zipf law. numpy only: the parent process
imports this and must stay off jax.
"""

from __future__ import annotations

import concurrent.futures
from dataclasses import dataclass

import numpy as np

CORPUS_CHUNKS = 16          # part of the data's definition: never a knob
_TAG_CORPUS, _TAG_QUERIES, _TAG_ARRIVALS, _TAG_SAMPLE, _TAG_ORDER = \
    1, 2, 3, 4, 5


@dataclass
class Corpus:
    """``offsets [n+1]``, ``ids [nnz]`` i32 ascending inside a document,
    ``tfs [nnz]`` f32, ``lengths [n]`` f32 (token counts)."""
    n_docs: int
    vocab: int
    offsets: np.ndarray
    ids: np.ndarray
    tfs: np.ndarray
    lengths: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.ids.shape[0])


def _corpus_chunk(corpus_seed: int, chunk: int, n_docs: int, vocab: int,
                  mean_len: float, min_len: int, zipf_a: float,
                  rotate: int):
    rng = np.random.default_rng([corpus_seed, _TAG_CORPUS, chunk])
    lengths = np.clip(rng.poisson(mean_len, n_docs), min_len, None) \
        .astype(np.int64)
    total = int(lengths.sum())
    tokens = rng.zipf(zipf_a, size=total) % vocab
    key = np.repeat(np.arange(n_docs, dtype=np.int64), lengths) * vocab \
        + tokens
    key.sort()
    first = np.ones(total, bool)
    first[1:] = key[1:] != key[:-1]
    idx = np.flatnonzero(first)
    tfs = np.diff(np.append(idx, total)).astype(np.float32)
    ukey = key[idx]
    ids = (ukey % vocab).astype(np.int32)
    per_doc = np.bincount(ukey // vocab, minlength=n_docs)
    # the run's order: start the chunk at its ``rotate``-th document
    cut = int(per_doc[:rotate].sum())
    return (np.roll(per_doc, -rotate), np.roll(ids, -cut),
            np.roll(tfs, -cut), np.roll(lengths.astype(np.float32), -rotate))


def make_corpus(seed: int, *, corpus_seed: int, docs: int, vocab: int,
                doc_len_mean: float, doc_len_min: int = 5,
                zipf_a: float = 1.25, threads: int = 8) -> Corpus:
    """``docs`` documents of Poisson(``doc_len_mean``) Zipf(``zipf_a``)
    tokens over ``vocab`` terms, drawn from ``corpus_seed`` and put in
    ``seed``'s order. Sampling and sorting run off the GIL, so the chunks
    are made on a few threads."""
    bounds = np.linspace(0, docs, CORPUS_CHUNKS + 1).astype(np.int64)
    sizes = np.diff(bounds)
    order_rng = np.random.default_rng([seed, _TAG_ORDER])
    order = order_rng.permutation(CORPUS_CHUNKS)
    rotate = [int(order_rng.integers(0, max(int(sizes[c]), 1)))
              for c in range(CORPUS_CHUNKS)]
    with concurrent.futures.ThreadPoolExecutor(threads) as ex:
        parts = list(ex.map(
            lambda c: _corpus_chunk(corpus_seed, c, int(sizes[c]), vocab,
                                    doc_len_mean, doc_len_min, zipf_a,
                                    rotate[c]),
            order))
    per_doc = np.concatenate([p[0] for p in parts])
    offsets = np.concatenate([[0], np.cumsum(per_doc)]).astype(np.int64)
    return Corpus(
        n_docs=docs, vocab=vocab, offsets=offsets,
        ids=np.concatenate([p[1] for p in parts]),
        tfs=np.concatenate([p[2] for p in parts]),
        lengths=np.concatenate([p[3] for p in parts]))


def corpus_args(config: dict) -> dict:
    """The keyword arguments of :func:`make_corpus` a configuration
    file states (its top-level keys)."""
    return dict(corpus_seed=config["corpus_seed"],
                docs=config["docs"], vocab=config["vocab"],
                doc_len_mean=config["doc_len_mean"],
                doc_len_min=config.get("doc_len_min", 5),
                zipf_a=config.get("zipf_a", 1.25))


def make_queries(seed: int, n: int, *, vocab: int, query_terms: dict,
                 zipf_a: float = 1.25) -> list[str]:
    """``n`` DISTINCT queries (distinct, so that no result cache answers
    one). ``query_terms`` is the configuration's length law:
    ``{"law": "uniform", "min": 2, "max": 4}`` or ``{"law":
    "shifted-poisson", "min": 2, "max": 12, "mean": 6}`` (min +
    Poisson(mean - min), clipped at max)."""
    rng = np.random.default_rng([seed, _TAG_QUERIES])
    lo, hi = int(query_terms["min"]), int(query_terms["max"])
    seen: dict[str, None] = {}
    while len(seen) < n:
        want = (n - len(seen)) + 64
        if query_terms["law"] == "uniform":
            lens = rng.integers(lo, hi + 1, want)
        elif query_terms["law"] == "shifted-poisson":
            lens = np.clip(lo + rng.poisson(query_terms["mean"] - lo, want),
                           lo, hi)
        else:
            raise ValueError(f"unknown query length law "
                             f"{query_terms['law']!r}")
        toks = rng.zipf(zipf_a, size=int(lens.sum())) % vocab
        off = np.concatenate([[0], np.cumsum(lens)])
        for i in range(want):
            seen[" ".join(f"t{w}" for w in toks[off[i]:off[i + 1]])] = None
            if len(seen) == n:
                break
    return list(seen)


def distinct_terms(queries: list[str]) -> int:
    return len({t for q in queries for t in q.split()})


def capacity_batch(pool: list[str], batch: int, capacity: int) -> list[str]:
    """A warm-up batch of ``batch`` pool queries whose distinct terms
    number more than ``capacity / 2`` and at most ``capacity``: the
    program sizes its compiled step by a power-of-two high-water mark of
    that count, so this batch pins it at ``capacity`` for EVERY seed —
    otherwise a seed whose largest batch happens to cross a power of two
    would run a different program from its neighbours. Built from the
    queries with the most distinct terms, repeated to fill the batch."""
    order = sorted(range(len(pool)),
                   key=lambda i: -len(set(pool[i].split())))
    chosen: list[str] = []
    terms: set[str] = set()
    for i in order:
        if len(terms) > capacity // 2 or len(chosen) == batch:
            break
        chosen.append(pool[i])
        terms.update(pool[i].split())
    if not capacity // 2 < len(terms) <= capacity:
        raise ValueError(
            f"cannot pin the unique-term capacity at {capacity}: the "
            f"{len(chosen)} widest queries hold {len(terms)} terms")
    return [chosen[i % len(chosen)] for i in range(batch)]


def poisson_arrivals(seed: int, rate_per_s: float, horizon_s: float
                     ) -> np.ndarray:
    """Due times in seconds from the start of the load, a Poisson process
    of ``rate_per_s`` up to ``horizon_s``."""
    rng = np.random.default_rng([seed, _TAG_ARRIVALS])
    n = int(rate_per_s * horizon_s * 1.2) + 64
    due = np.cumsum(rng.exponential(1.0 / rate_per_s, n))
    while due[-1] < horizon_s:
        due = np.concatenate([due, due[-1] + np.cumsum(
            rng.exponential(1.0 / rate_per_s, n))])
    return due[due < horizon_s]


def sample_positions(seed: int, first: int, n: int) -> list[int]:
    """``n`` distinct positions among the first ``first`` of the query
    pool: the answers the check of outputs compares."""
    rng = np.random.default_rng([seed, _TAG_SAMPLE])
    return sorted(int(i) for i in rng.choice(first, size=min(n, first),
                                             replace=False))


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics — numpy's default rule, written out so the
    arithmetic is part of the yardstick."""
    v = sorted(float(x) for x in values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
