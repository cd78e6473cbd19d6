"""The plain reference of the deep run file: BM25, dense, numpy float64.

What ``tests/test_deep_topk.py`` holds the system to at a depth of 1,000
hits a query. It imports nothing of ``tfidf_tpu`` and is written the
other way round from the program: ONE dense ``[documents, vocabulary]``
float64 matrix of impacts, a query a matrix-vector product, a ranking a
full ``np.lexsort`` - no blocks, no chunks, no top-k, no float32.

    idf(t)      = ln(1 + (N - df_t + 0.5) / (df_t + 0.5))
    impact(t,d) = idf(t) * tf / (tf + k1 * (1 - b + b * dl_d / avgdl))
    score(q,d)  = sum over the query's terms of multiplicity * impact

(Lucene 9's BM25Similarity, as ``benchmarks/lib/oracle.py`` states it for
the chip's comparison.) A run is the documents by ``np.lexsort`` on
(-score, document): best first, EQUAL scores to the lower document, cut
at ``k``, zero scores dropped - a query that matches fewer than ``k``
documents returns exactly those. "The lower document" is the document's
place in the index's own order (a Lucene docid): an index that lays its
documents out in another order than they were added says so through
``position``, and nothing else of the program comes near the reference.
"""

from __future__ import annotations

import numpy as np


def parse_query(query: str, vocab: int) -> dict[int, int]:
    """``"t3 t3 t17"`` -> ``{3: 2, 17: 1}``; a token that is no ``t<id>``
    of the vocabulary matches nothing and is dropped."""
    counts: dict[int, int] = {}
    for tok in query.split():
        if tok[:1] == "t" and tok[1:].isdigit() and int(tok[1:]) < vocab:
            counts[int(tok[1:])] = counts.get(int(tok[1:]), 0) + 1
    return counts


class Bm25Reference:
    def __init__(self, docs: list[dict[int, float]], lengths, *,
                 vocab: int, k1: float, b: float) -> None:
        n = len(docs)
        tf = np.zeros((n, vocab), np.float64)
        for d, postings in enumerate(docs):
            tf[d, list(postings)] = list(postings.values())
        dl = np.asarray(lengths, np.float64)
        df = np.count_nonzero(tf, axis=0).astype(np.float64)
        idf = np.log1p((n - df + 0.5) / (df + 0.5))
        norm = k1 * (1.0 - b + b * dl / dl.mean())
        self.vocab = vocab
        self.impact = idf[None, :] * tf / (tf + norm[:, None])

    def scores(self, query: str) -> np.ndarray:
        q = np.zeros(self.vocab, np.float64)
        for t, c in parse_query(query, self.vocab).items():
            q[t] = c
        return self.impact @ q

    def run(self, query: str, k: int,
            position=None) -> list[tuple[int, float]]:
        """The ``k``-deep run of ``query``: ``(document, score)`` best
        first. ``position[d]`` is document ``d``'s place in the index's
        own order (None: the order they were added in)."""
        s = self.scores(query)
        place = np.arange(s.shape[0]) if position is None \
            else np.asarray(position)
        order = np.lexsort((place, -s))[:k]
        return [(int(d), float(s[d])) for d in order if s[d] > 0.0]
