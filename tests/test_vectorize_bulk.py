"""``vectorize_queries`` (engine/searcher.py) fills the padded query
matrix by ONE assignment a batch; the loop it replaced stored a numpy
scalar a term. The old function is kept HERE as the plain reference.

What must hold is bit-equality of the arrays handed to
``make_query_batch`` (``q_terms``' column order is the per-query order
of terms, heaviest first and ties by term id) and therefore of the
``QueryBatch``: the compiled programs then see the parent's inputs and
their sums are the parent's.
"""

import numpy as np
import pytest

import tfidf_tpu.engine.searcher as searcher_mod
from tfidf_tpu.engine.searcher import vectorize_queries
from tfidf_tpu.engine.vocab import Vocabulary
from tfidf_tpu.models.base import ScoringModel, get_model
from tfidf_tpu.ops.analyzer import make_analyzer
from tfidf_tpu.ops.scoring import make_query_batch


def loop_vectorize(queries, analyzer, vocab, model, *, batch_cap,
                   max_terms, min_slots=256):
    """``vectorize_queries`` as it stood before PR 36, to the letter;
    returns the padded arrays beside the batch."""
    assert len(queries) <= batch_cap
    q_terms = np.zeros((batch_cap, max_terms), np.int32)
    q_weights = np.zeros((batch_cap, max_terms), np.float32)
    widest = 1
    for i, q in enumerate(queries):
        counts = vocab.map_counts(analyzer.counts(q), add=False)
        weights = model.query_weights(counts)
        items = sorted(weights.items(), key=lambda kv: (-kv[1], kv[0]))
        items = items[:max_terms]
        widest = max(widest, len(items))
        for j, (tid, w) in enumerate(items):
            q_terms[i, j] = tid
            q_weights[i, j] = w
    return (q_terms, q_weights,
            make_query_batch(q_terms, q_weights, min_slots=min_slots),
            widest)


class FractionalModel(ScoringModel):
    """Query weights no float32 holds exactly, several of them equal:
    the store's rounding and the tie order both show."""

    def query_weights(self, term_counts):
        return {t: c * 0.37 + (t % 3) * 0.1
                for t, c in term_counts.items()}


WORDS = ("alpha beta gamma delta epsilon zeta eta theta iota kappa lambda "
         "mu nu xi omicron pi rho sigma tau upsilon phi chi psi omega"
         ).split()


@pytest.fixture(scope="module")
def vocab():
    v = Vocabulary()
    # ids NOT in alphabetical order, so a sort by string shows
    for w in reversed(WORDS):
        v.add(w)
    for i in range(40):
        v.add(f"t{i}")
    return v


def both(monkeypatch, queries, vocab, model, **kw):
    seen = {}

    def spy(q_terms, q_weights, *, min_slots=256):
        seen["q_terms"], seen["q_weights"] = q_terms, q_weights
        return make_query_batch(q_terms, q_weights, min_slots=min_slots)

    monkeypatch.setattr(searcher_mod, "make_query_batch", spy)
    analyzer = make_analyzer()
    qb, widest = vectorize_queries(queries, analyzer, vocab, model, **kw)
    want = loop_vectorize(queries, analyzer, vocab, model, **kw)
    return (seen["q_terms"], seen["q_weights"], qb, widest), want


def bit_equal(got, want):
    g_terms, g_weights, g_qb, g_widest = got
    w_terms, w_weights, w_qb, w_widest = want
    pairs = [(g_terms, w_terms), (g_weights, w_weights),
             (g_qb.uniq, w_qb.uniq), (g_qb.slots, w_qb.slots),
             (g_qb.weights, w_qb.weights),
             (np.asarray(g_qb.n_uniq), np.asarray(w_qb.n_uniq))]
    for g, w in pairs:
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)
    assert g_weights.tobytes() == w_weights.tobytes()
    assert type(g_widest) is int and g_widest == w_widest


CASES = {
    "duplicate_terms": ["alpha beta alpha alpha gamma beta",
                        "omega omega", "pi"],
    "unknown_term": ["alpha nosuchterm beta", "nosuchterm", "zeta"],
    "empty_query": ["", "alpha", "   ", "?!", "beta gamma"],
    # as wide as the matrices, the widest a query may be
    "at_max_terms": [" ".join(WORDS[:8]) + " alpha alpha beta",
                     " ".join(f"t{i}" for i in range(8)),
                     "kappa"],
    "fewer_than_batch_cap": ["alpha beta", "gamma"],
    "weights_tie": ["beta beta alpha alpha gamma delta delta",
                    "tau sigma rho", "mu nu nu mu xi xi"],
    "one_term_queries": ["alpha", "omega", "alpha"],
    "no_queries": [],
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("model_name", ["bm25", "fractional"])
def test_bit_equal_to_the_loop(monkeypatch, vocab, case, model_name):
    model = (FractionalModel() if model_name == "fractional"
             else get_model("bm25"))
    got, want = both(monkeypatch, CASES[case], vocab, model,
                     batch_cap=8, max_terms=8, min_slots=16)
    bit_equal(got, want)


def test_full_width_orders_heaviest_first_ties_by_id(monkeypatch, vocab):
    query = "alpha " * 3 + "beta " * 3 + " ".join(WORDS[2:4])
    (q_terms, q_weights, qb, widest), want = both(
        monkeypatch, [query], vocab, get_model("bm25"),
        batch_cap=2, max_terms=4, min_slots=16)
    bit_equal((q_terms, q_weights, qb, widest), want)
    a, b = vocab.lookup("alpha"), vocab.lookup("beta")
    # the two weight-3 terms first, the lower id of them first; then
    # the two weight-1 terms by id
    assert q_terms[0, :2].tolist() == sorted([a, b])
    assert q_weights[0].tolist() == [3.0, 3.0, 1.0, 1.0]
    assert q_terms[0, 2:].tolist() == sorted(
        vocab.lookup(w) for w in WORDS[2:4])
    assert widest == 4 and not q_terms[1].any()


@pytest.mark.parametrize("model_name", ["bm25", "fractional"])
def test_over_max_terms_is_refused_by_name(vocab, model_name):
    """A query past the width is never cut to its heaviest terms (the
    loop above did, in silence): it is refused with its count and the
    limit, every such query of the chunk named, and counted. Terms the
    vocabulary lacks count too: the front door counts the same way."""
    from tfidf_tpu.engine.searcher import TooManyQueryTerms
    from tfidf_tpu.utils.metrics import global_metrics
    model = (FractionalModel() if model_name == "fractional"
             else get_model("bm25"))
    wide = " ".join(WORDS[:12]) + " alpha alpha beta"
    unknown = " ".join(f"nosuch{i}" for i in range(9))
    before = global_metrics.get("query_terms_refused")
    with pytest.raises(TooManyQueryTerms) as err:
        vectorize_queries(["kappa", wide, " ".join(WORDS[:8]), unknown],
                          make_analyzer(), vocab, model,
                          batch_cap=4, max_terms=8, min_slots=16)
    assert err.value.refused == ((wide, 12), (unknown, 9))
    assert err.value.queries == (wide, unknown) and err.value.limit == 8
    assert "12 distinct terms" in str(err.value) \
        and "max_query_terms=8" in str(err.value)
    assert global_metrics.get("query_terms_refused") == before + 2


def test_a_batch_of_the_cells_law(monkeypatch, vocab):
    """512 queries of 2-12 terms, the served cells' shape, with
    duplicates and unknowns mixed in."""
    rng = np.random.default_rng(36)
    pool = WORDS + [f"t{i}" for i in range(40)] + ["unknown1", "unknown2"]
    queries = [" ".join(rng.choice(pool, size=int(rng.integers(2, 13))))
               for _ in range(500)]
    got, want = both(monkeypatch, queries, vocab, get_model("bm25"),
                     batch_cap=512, max_terms=32, min_slots=256)
    bit_equal(got, want)
    assert got[3] > 1
