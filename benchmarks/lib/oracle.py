"""The plain reference and the comparison that decides ``correct``.

BM25 by the textbook formula in numpy float64 over the same seeded
corpus, importing nothing of ``tfidf_tpu`` and taking nothing the
program made:

    idf(t)     = ln(1 + (N - df_t + 0.5) / (df_t + 0.5))
    impact(t,d)= idf(t) * tf / (tf + k1 * (1 - b + b * dl_d / avgdl))
    score(q,d) = sum over the query's terms of multiplicity * impact

It holds postings only for the terms its queries use. ``precision=
"bfloat16"`` is the CONTROL: the same reference with every impact
rounded to bfloat16 (8 bits of mantissa, round to nearest even) before
the float32 sum — the step below the float32 the configurations state,
and the one that would tempt a later PR (half the index bytes). The
comparison has to call it not correct.

What is compared, per sampled answer (a top-k list of (doc, score)):

* ``hit_count_mismatch`` — answers whose number of hits differs from the
  reference's number of positive scores among its top k. Limit 0.
* ``doc_score_rel_err`` — for every returned document, the gap between
  the returned score and the reference's score OF THAT DOCUMENT, over the
  reference's score; the widest over the sample. Catches a wrong score,
  a wrong document, a stale or partial index.
* ``rank_score_rel_err`` — the returned scores sorted, against the
  reference's top-k scores sorted, rank by rank (tie order free); the
  widest. Catches a better document left out.

LIMIT_REL_ERR and its readings are set out in PERF.md section 2.
"""

from __future__ import annotations

import numpy as np

# Set from chip readings (PERF.md section 2): above the largest gap sound
# float32 runs of the program showed over the seeds read, below the
# smallest gap the bfloat16 control showed, with room on both sides.
LIMIT_REL_ERR = 9.0e-4


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even),
    returned as float32."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return rounded.astype(np.uint32).view(np.float32)


def parse_query(query: str) -> dict[int, int]:
    counts: dict[int, int] = {}
    for tok in query.split():
        t = int(tok[1:])
        counts[t] = counts.get(t, 0) + 1
    return counts


class Oracle:
    def __init__(self, corpus, queries: list[str], *, k1: float, b: float,
                 top_k: int = 10, precision: str = "float64") -> None:
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.n_docs = n = corpus.n_docs
        self.top_k = top_k
        self.precision = precision
        self.queries = [parse_query(q) for q in queries]
        needed = np.zeros(corpus.vocab, bool)
        for q in self.queries:
            needed[list(q)] = True
        df = np.bincount(corpus.ids, minlength=corpus.vocab) \
            .astype(np.float64)
        idf = np.log1p((n - df + 0.5) / (df + 0.5))
        avgdl = float(corpus.lengths.mean(dtype=np.float64))
        # the postings of the needed terms, grouped by term (in-place
        # arithmetic: these arrays run to tens of millions of entries)
        sel = np.flatnonzero(needed[corpus.ids])
        term = corpus.ids[sel]
        order = np.argsort(term, kind="stable")
        sel, term = sel[order], term[order]
        del order
        first = np.flatnonzero(np.r_[True, term[1:] != term[:-1]])
        ends = np.append(first[1:], term.shape[0])
        self._span = {int(t): (int(lo), int(hi))
                      for t, lo, hi in zip(term[first], first, ends)}
        self._row = np.repeat(np.arange(n, dtype=np.int32),
                              np.diff(corpus.offsets))[sel]
        tf = corpus.tfs[sel].astype(np.float64)
        den = corpus.lengths[self._row].astype(np.float64)
        den *= k1 * b / avgdl
        den += k1 * (1 - b)
        den += tf
        tf /= den                      # tf / (tf + k1 (1 - b + b dl/avgdl))
        tf *= np.repeat(idf[term[first]], ends - first)
        impact = tf
        if precision == "bfloat16":
            impact = to_bfloat16(impact.astype(np.float32))
        self._impact = impact

    def scores(self, qi: int) -> np.ndarray:
        acc = np.float32 if self.precision == "bfloat16" else np.float64
        out = np.zeros(self.n_docs, acc)
        for t, c in self.queries[qi].items():
            lo, hi = self._span.get(t, (0, 0))
            # a document holds a term once, so this is one add per row
            out[self._row[lo:hi]] += (c * self._impact[lo:hi]).astype(acc)
        return out

    def topk(self, qi: int) -> list[tuple[str, float]]:
        """This reference's own answer, in the served form: at most
        ``top_k`` (``d<row>``, score) pairs with positive scores, best
        first. What the control puts in the program's place."""
        s = self.scores(qi)
        k = min(self.top_k, self.n_docs)
        idx = np.argpartition(s, -k)[-k:]
        idx = idx[np.argsort(-s[idx], kind="stable")]
        return [(f"d{int(i)}", float(s[i])) for i in idx if s[i] > 0]


def compare(oracle: Oracle, answers: dict[int, list[tuple[str, float]]]
            ) -> dict:
    """``answers``: sample index -> hit list as served. Returns the
    numbers compared, each beside its limit, and ``correct``."""
    if oracle.precision != "float64":
        raise ValueError("answers are compared with the float64 reference")
    count_bad = 0
    doc_err = rank_err = 0.0
    worst = ""
    for qi, hits in sorted(answers.items()):
        ref = oracle.scores(qi)
        k = min(oracle.top_k, oracle.n_docs)
        want = np.sort(np.partition(ref, -k)[-k:])[::-1]
        want = want[want > 0]
        have = np.asarray([s for _n, s in hits], np.float64)
        if have.shape != want.shape:
            count_bad += 1
            worst = worst or (f"query {qi}: {have.shape[0]} hits, the "
                              f"reference has {want.shape[0]}")
            continue
        if not have.size:
            continue
        of_doc = ref[[int(name[1:]) for name, _s in hits]]
        # a returned document the reference scores 0 is wrong outright
        d = float(np.max(np.abs(have - of_doc)
                         / np.where(of_doc > 0, of_doc, have * 1e-9)))
        r = float(np.max(np.abs(np.sort(have)[::-1] - want) / want))
        if max(d, r) > max(doc_err, rank_err):
            worst = f"query {qi}: doc gap {d:.3e}, rank gap {r:.3e}"
        doc_err, rank_err = max(doc_err, d), max(rank_err, r)
    numbers = {
        "answers_compared": {"value": len(answers), "limit": ">= 1"},
        "hit_count_mismatch": {"value": count_bad, "limit": 0},
        "doc_score_rel_err": {"value": doc_err, "limit": LIMIT_REL_ERR},
        "rank_score_rel_err": {"value": rank_err, "limit": LIMIT_REL_ERR},
    }
    correct = (len(answers) >= 1 and count_bad == 0
               and doc_err <= LIMIT_REL_ERR and rank_err <= LIMIT_REL_ERR
               and any(answers.values()))
    return {"correct": bool(correct), "numbers": numbers, "worst": worst}
