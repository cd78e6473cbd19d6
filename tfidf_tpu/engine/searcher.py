"""Query execution against a committed snapshot.

Replaces the reference's per-query path (``Worker.java:222-241``): parse
query with the same analyzer used at index time, score, return hits. Unlike
the reference — one query at a time over HTTP — queries are batched into a
fixed-size padded batch and scored in one device program; a single query is
just a batch of one (padding is free: executables are cached per batch
bucket).

Only documents containing at least one query term are returned (score > 0),
matching Lucene's behavior of only scoring docs in the postings of query
terms. Unknown query terms are dropped (they can match nothing); no other
term is: a query of more distinct terms than ``max_query_terms`` is
refused by name (:class:`TooManyQueryTerms`), as Lucene refuses a
disjunction past ``maxClauseCount``.
"""

from __future__ import annotations

import threading
from collections import deque
from functools import partial
from itertools import chain, compress
from operator import attrgetter, itemgetter
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tfidf_tpu.engine.index import ShardIndex, Snapshot
from tfidf_tpu.engine.pipeline import PipelineExecutor
from tfidf_tpu.engine.segments import SegmentedSnapshot
from tfidf_tpu.engine.vocab import Vocabulary
from tfidf_tpu.models.base import ScoringModel
from tfidf_tpu.ops.analyzer import Analyzer
from tfidf_tpu.ops.blockmax import query_upper_bounds, skip_mask
from tfidf_tpu.ops.csr import next_capacity
from tfidf_tpu.ops.ell import (_pallas_eligible, ell_scores_to_real,
                               kernel_contract_chunks, kernel_uniq_lanes,
                               plan_stretches, score_ell_batch,
                               score_segments_batch, stretch_budget)
from tfidf_tpu.ops.scoring import (QueryBatch, make_query_batch,
                                   score_coo_batch)
from tfidf_tpu.ops.topk import (fetch_packed, full_ranking, merge_packed,
                                packed_topk, packed_topk_chunked,
                                topk_chunk_counts, unpack_topk)
from tfidf_tpu.utils.metrics import global_metrics
from tfidf_tpu.utils.tracing import trace_phase


class SearchHit(NamedTuple):
    name: str
    score: float


# SearchHit._make without its Python frame a hit: the same objects from
# a (name, score) pair, built in C (assemble_hits makes 5,120 a call)
_new_hit = partial(tuple.__new__, SearchHit)
_hit_name = attrgetter("name")
_pair_weight = itemgetter(1)


class Stretch(NamedTuple):
    """A run of whole ELL blocks one score program and one top-k program
    take together (``ops.ell.plan_stretches``)."""
    first: int             # blocks [first, stop) of the snapshot
    stop: int
    live: jax.Array        # i32 [stop - first] their live rows (device)
    live_host: tuple       # the same, host integers
    base: jax.Array | None  # i32 real row of its first column; None: 0
    nbytes: int            # of its [B, rows] f32 scores


def device_bytes_limit(array) -> int | None:
    """``bytes_limit`` of the device that holds ``array``, or None where
    the backend keeps no such count (the CPU)."""
    stats = next(iter(array.devices())).memory_stats()
    return stats.get("bytes_limit") if stats else None


# guards lazy per-searcher PipelineExecutor construction
_pipe_init_lock = threading.Lock()


class TooManyQueryTerms(ValueError):
    """A query holds more distinct terms than ``max_query_terms``, the
    width of the padded query matrices: it is REFUSED, never cut to its
    heaviest terms. Lucene's ``IndexSearcher.TooManyClauses`` is the
    model (a disjunction past ``maxClauseCount``, 1,024 by default,
    throws; it never drops a clause in silence). ``refused`` holds every
    ``(query, distinct terms)`` of the chunk it was found in, ``queries``
    the strings alone, ``limit`` the configured width: a caller takes
    them out and asks again, and the front door answers 400 to the one
    request (``cluster/router.py``)."""

    def __init__(self, refused, limit: int) -> None:
        self.refused = tuple(refused)
        self.limit = limit
        query, n_terms = self.refused[0]
        more = (f" (and {len(self.refused) - 1} more of its batch)"
                if len(self.refused) > 1 else "")
        super().__init__(
            f"query of {n_terms} distinct terms, over max_query_terms="
            f"{limit}: {query[:80]!r}{more}")

    @property
    def queries(self) -> tuple[str, ...]:
        return tuple(q for q, _n in self.refused)


def vectorize_queries(queries: list[str], analyzer: Analyzer,
                      vocab: Vocabulary, model: ScoringModel,
                      *, batch_cap: int, max_terms: int,
                      min_slots: int = 256) -> tuple[QueryBatch, int]:
    """Analyze + pad a query batch to [batch_cap, max_terms] and dedup the
    batch's terms into a compact slot space (:class:`QueryBatch`).
    Returns ``(batch, max distinct terms in any one query)`` — the width
    statistic drives the Pallas query-group size.

    Pad entries are inert by construction in the scoring kernel. A query
    of more than ``max_terms`` distinct terms (as the analyzer counts
    them, in the vocabulary or not: what the front door can count too)
    raises :class:`TooManyQueryTerms` for its chunk; every term of every
    other query is scored. ``min_slots`` floors the unique-term
    capacity: searchers pass their high-water mark so successive batches
    reuse ONE compiled program instead of recompiling whenever the
    unique count crosses a power-of-two bucket (capacity padding is free
    in the u-tiled kernel).

    Two stages of the one timer, inside the caller's ``vectorize``:
    ``vectorize_analyze`` (tokens, counts, vocabulary lookup, query
    weights: a Python pass a token) and ``vectorize_pack`` (the one fill
    of the matrices and ``make_query_batch``'s dedup); and two counters,
    ``query_terms_seen`` (the entries filled: distinct terms a query,
    summed over the chunk, before the batch's dedup) and
    ``query_terms_refused`` (queries refused).
    """
    assert len(queries) <= batch_cap
    term_counts = analyzer.counts
    map_counts = vocab.map_counts
    query_weights = model.query_weights
    # every query's (term id, weight) pairs end to end and how many each
    # query gave: the matrices are filled ONCE after the loop, where a
    # numpy scalar store a term was a seventh of this function
    pairs: list[tuple[int, float]] = []
    sizes: list[int] = []
    refused: list[tuple[str, int]] = []
    with trace_phase("vectorize_analyze"):
        for q in queries:
            counts = term_counts(q)
            if len(counts) > max_terms:
                refused.append((q, len(counts)))
                continue
            items = query_weights(map_counts(counts, add=False)).items()
            if len(items) > 1:
                # heaviest first, ties by term id, i.e. key (-weight,
                # id): by id, then a stable sort by weight. This is
                # q_terms' column order, so it is kept to the letter and
                # the compiled programs see the same arrays
                items = sorted(items)
                items.sort(key=_pair_weight, reverse=True)
            sizes.append(len(items))
            pairs.extend(items)
    if refused:
        global_metrics.inc("query_terms_refused", len(refused))
        raise TooManyQueryTerms(refused, max_terms)
    with trace_phase("vectorize_pack"):
        q_terms = np.zeros((batch_cap, max_terms), np.int32)
        q_weights = np.zeros((batch_cap, max_terms), np.float32)
        if pairs:
            # row-major, the filled entries of the matrices are the
            # pairs in order: query by query, column by column
            filled = np.arange(max_terms) < np.array(sizes)[:, None]
            # one pass from Python objects to numpy for ids and weights
            # both: a term id is exact in a float64, and a float64
            # rounds to float32 as the scalar store did
            flat = np.fromiter(chain.from_iterable(pairs), np.float64,
                               2 * len(pairs)).reshape(-1, 2)
            q_terms[:len(sizes)][filled] = flat[:, 0]
            q_weights[:len(sizes)][filled] = flat[:, 1]
        qb = make_query_batch(q_terms, q_weights, min_slots=min_slots)
    global_metrics.inc("query_terms_seen", len(pairs))
    return qb, max([1, *sizes])


def assemble_hits(vals: np.ndarray, ids: np.ndarray, doc_names,
                  result_order: str) -> list[list[SearchHit]]:
    """The hit lists of one fetched ``[n, kk]`` block of top-k values
    and document ids, a list a query: the ONE place result arrays become
    Python objects, for every searcher family. An entry is a hit where
    its value is finite and > 0 (dead and pad entries are 0 or -inf);
    ``doc_names`` is the snapshot's, the names by id, and an id it
    names ``None`` (a mesh shard's pad row) is dropped. Every conversion
    between numpy and Python happens once for the block, not once an
    entry: a float32 becomes the same ``float`` through ``tolist`` as
    through ``float()``.

    Two stages of the one timer, inside the caller's ``assemble``:
    ``hit_names`` (the mask, the two ``tolist``, the name of every live
    entry) and ``hit_objects`` (the hits and their cut into rows), which
    grow with the depth where the rest of ``assemble`` does not; and two
    counters, ``hit_slots`` (the block's entries, queries x depth) and
    ``hits_built`` (the hits that leave here): their ratio says how full
    the depth is.
    """
    with trace_phase("hit_names"):
        live = np.isfinite(vals) & (vals > 0.0)
        counts = np.count_nonzero(live, axis=1)
        names = list(map(doc_names.__getitem__, ids[live].tolist()))
        scores = vals[live].tolist()
        if None in names:
            named = [name is not None for name in names]
            counts = np.bincount(np.nonzero(live)[0][named],
                                 minlength=len(counts))
            names = list(compress(names, named))
            scores = list(compress(scores, named))
    with trace_phase("hit_objects"):
        # ALL the hits in one C-level call, then cut: the interpreter
        # runs the cyclic collector between bytecodes only, so these
        # 5,120 allocations cost one young collection, where building
        # them a row (or an entry, as the old loop did) ran one every
        # 700 and promoted enough survivors for eight times the full
        # collections
        hits = list(map(_new_hit, zip(names, scores)))
        results = []
        lo = 0
        for count in counts.tolist():
            results.append(hits[lo:lo + count])
            lo += count
        if result_order == "name":
            for row in results:
                row.sort(key=_hit_name)
    global_metrics.inc("hit_slots", vals.size)
    global_metrics.inc("hits_built", len(hits))
    return results


class SearchLoop:
    """The ONE search loop of every searcher family (local, COO mesh,
    ELL mesh): :meth:`search` and :meth:`search_arrays` cut the queries
    into chunks, vectorize, hand each chunk to the family's dispatch
    hook, fetch its packed top-k and finish it on the caller's thread,
    ``pipeline_depth`` chunks deep (:meth:`_run_pipelined`). A family
    provides ``index``, analyzer / vocab / model, ``query_batch``,
    ``max_query_terms``, ``top_k``, ``result_order`` and the hooks
    :meth:`_dispatch_chunk`, :meth:`_search_unbounded` and, where it
    caches by snapshot, :meth:`_on_snapshot`; its snapshots name their
    documents by id (``doc_names``) and count them (``num_names``).

    Batches are vectorized with ``min_slots`` floored at the largest
    u_cap seen so far, so the compiled scoring program stays stable
    across query batches instead of recompiling whenever the unique
    count crosses a power-of-two bucket."""

    _u_floor = 256
    _pipe: PipelineExecutor | None = None
    pipeline_mode = "auto"

    def __init__(self, index, analyzer: Analyzer, vocab: Vocabulary,
                 model: ScoringModel,
                 *, query_batch: int = 32, max_query_terms: int = 32,
                 top_k: int = 10, result_order: str = "score",
                 pipeline_depth: int = 2,
                 pipeline_mode: str = "auto") -> None:
        self.index = index
        self.analyzer = analyzer
        self.vocab = vocab
        self.model = model
        self.query_batch = query_batch
        self.max_query_terms = max_query_terms
        self.top_k = top_k
        # "name" reproduces the reference's alphabetical result ordering
        # (Leader.java:80-91 sorts the merged map by document name)
        self.result_order = result_order
        # in-flight chunks: on small corpora the device step is far
        # shorter than the device->host fetch RTT, so serial execution
        # caps throughput at ~1 chunk per RTT; depth D keeps D fetches
        # overlapped (D+1 chunks in flight including the one just
        # dispatched — see _run_pipelined's in-flight accounting; each
        # pending chunk holds only a packed [B, 2k] top-k buffer)
        self.pipeline_depth = max(1, pipeline_depth)
        # "auto" | "executor" | "inline" — see _use_executor
        self.pipeline_mode = pipeline_mode

    def _batch_cap(self, n: int) -> int:
        return min(self.query_batch, next_capacity(max(n, 1), 1))

    def search(self, queries: list[str], k: int | None = None,
               *, unbounded: bool = False) -> list[list[SearchHit]]:
        """Score queries against the current snapshot.

        ``unbounded=True`` returns every matching document (the reference's
        ``Integer.MAX_VALUE`` behavior, ``Worker.java:230``) via a host-side
        full ranking — parity mode only; exact top-k is the fast path.

        Chunks are PIPELINED ``pipeline_depth`` deep (default 2): later
        chunks' device programs are dispatched before earlier chunks'
        packed top-k buffers are fetched, so the device->host round trip
        and host-side hit assembly hide under device time. Fetches
        serialize on one stream, so depth beyond 2 buys nothing; what
        depth a locally attached chip needs is not measured (ROADMAP
        D3).
        """
        snap = self._snapshot(queries)
        if snap is None:
            return [[] for _ in queries]
        if unbounded:
            cap = self._batch_cap(len(queries))
            return [hits for lo in range(0, len(queries), cap)
                    for hits in self._search_unbounded(
                        snap, queries[lo:lo + cap])]
        return self._search_chunks(
            snap, queries, k,
            lambda vals, ids: assemble_hits(vals, ids, snap.doc_names,
                                            self.result_order))

    def search_arrays(self, queries: list[str], k: int | None = None):
        """Pipelined exact top-k returning the RAW result arrays —
        ``(vals [N, kk] f32, ids [N, kk] i32, kk, names)`` — instead of
        assembled :class:`SearchHit` lists. ``ids`` index ``names``;
        entries whose value is non-finite or <= 0 are dead (padding /
        no match), exactly the entries :func:`assemble_hits` drops. The
        worker serving path packs these straight into the scatter wire
        reply (:func:`tfidf_tpu.cluster.wire.pack_topk_arrays`) without
        building per-hit Python objects, keeping the post-fetch host
        cost off the serving critical path."""
        snap = self._snapshot(queries)
        if snap is None:
            return self._arrays_reply([], len(queries), [])
        return self._arrays_reply(
            self._search_chunks(snap, queries, k,
                                lambda vals, ids: [(vals, ids)]),
            len(queries), snap.doc_names)

    @staticmethod
    def _arrays_reply(parts: list, n_queries: int, names):
        """``search_arrays``' reply of a search's chunks, each its
        checked ``(vals, ids)``; none: nothing was there to search."""
        if not parts:
            return (np.zeros((n_queries, 0), np.float32),
                    np.zeros((n_queries, 0), np.int32), 0, [])
        vals = np.concatenate([p[0] for p in parts], axis=0)
        ids = np.concatenate([p[1] for p in parts], axis=0)
        return vals, ids, vals.shape[1], names

    def _snapshot(self, queries: list[str]):
        """The snapshot a search runs on, or None where there is
        nothing to search."""
        snap = self.index.snapshot
        self._on_snapshot(snap)
        if snap is None or not snap.num_names or not queries:
            return None
        return snap

    def _search_chunks(self, snap, queries: list[str], k: int | None,
                       finish) -> list:
        """Every chunk of ``queries`` through the pipeline: counted and
        vectorized, its device work launched by the family's
        :meth:`_dispatch_chunk`, its packed top-k fetched in ONE
        device->host transfer (high-latency links make per-fetch cost
        dominate) and, on the caller's thread, split into two views,
        checked for poison and handed to ``finish(vals, ids)``: the
        hits of ``search`` or the arrays of ``search_arrays``. A chunk
        is finished while later ones are in flight."""
        k = self.top_k if k is None else k
        cap = self._batch_cap(len(queries))

        def dispatch(chunk):
            chunk_cap = self._batch_cap(len(chunk))
            self._count_chunk(len(chunk), chunk_cap)
            with trace_phase("vectorize"):
                qb, _widest = self._vectorize(chunk, chunk_cap)
            return (chunk, *self._dispatch_chunk(snap, qb, len(chunk), k))

        def assemble(chunk, arr, kk):
            with trace_phase("assemble"):
                return finish(*self._checked(chunk, *unpack_topk(arr), kk))

        return self._run_pipelined(
            (queries[lo:lo + cap] for lo in range(0, len(queries), cap)),
            dispatch,
            lambda chunk, packed, kk: (chunk, fetch_packed(packed), kk),
            assemble)

    def _on_snapshot(self, snap) -> None:
        """Family hook: called with the snapshot each search (lets a
        family drop per-snapshot caches when the version moves)."""

    def _dispatch_chunk(self, snap, qb, n_queries: int, k: int):
        """Family hook: launch one vectorized chunk's device work;
        returns ``(packed, kk)``, the packed top-k still ON DEVICE (not
        fetched) and the depth of the reply it holds."""
        raise NotImplementedError

    def _search_unbounded(self, snap,
                          queries: list[str]) -> list[list[SearchHit]]:
        """Family hook: the reference's unbounded (parity) results of
        one chunk."""
        raise NotImplementedError

    def _checked(self, queries: list[str], vals, ids, kk: int):
        """A fetched block cut to its real queries and its depth, and
        held to the poison-detection seam: a NaN in a fetched result
        row is never legitimate (scores are finite by construction;
        dead/pad entries are 0 or -inf), so it means the device produced
        garbage for that query — a miscompiled kernel, corrupted HBM, or
        the nemesis' injected poison. Raises with the OFFENDING query
        strings only, so the worker can report per-query blame and the
        leader's quarantine never punishes innocent batch cohorts."""
        n = len(queries)
        rows = np.isnan(vals[:n]).any(axis=1)
        if rows.any():
            from tfidf_tpu.utils.device_nemesis import \
                DevicePoisonedOutput
            raise DevicePoisonedOutput(tuple(
                q for q, bad in zip(queries, rows) if bad))
        return vals[:n, :kk], ids[:n, :kk]

    def _assemble(self, snap, queries: list[str], vals, ids,
                  kk: int) -> list[list[SearchHit]]:
        """The checked hit lists of a block ranked on the host (the
        unbounded results, ``compute_health``'s fallback)."""
        return assemble_hits(*self._checked(queries, vals, ids, kk),
                             snap.doc_names, self.result_order)

    def _vectorize(self, queries, cap):
        qb, widest = vectorize_queries(
            queries, self.analyzer, self.vocab, self.model,
            batch_cap=cap, max_terms=self.max_query_terms,
            min_slots=self._u_floor)
        self._u_floor = max(self._u_floor, qb.uniq.shape[0])
        # the compiled T: the matrices are as wide as the limit
        global_metrics.set_gauge("query_terms_width", self.max_query_terms)
        return qb, widest

    @staticmethod
    def _count_chunk(n_queries: int, cap: int) -> None:
        """One dispatched chunk: its real queries and the padded bucket
        they ride in. ``dispatch_queries / dispatch_slots`` is the share
        of every ``[B, rows]`` fusion, copy and top-k row that holds a
        real query."""
        global_metrics.inc("dispatch_chunks")
        global_metrics.inc("dispatch_queries", n_queries)
        global_metrics.inc("dispatch_slots", cap)

    @staticmethod
    def _count_kernel_uniq(qb) -> None:
        """One chunk dispatched to the fused kernel: its distinct terms
        (``kernel_uniq_live``) and the uniq lanes of A the kernel
        builds for them (``_built``). ``live / built`` is the share of
        the A-build's compare/select work on lanes a query uses. And the
        128-row chunks of A it contracts (``kernel_contract_chunks``), with
        those that take three bf16 passes because the batch's weights
        are exact in bfloat16 (``_bf16x3``: all of a batch or none)."""
        n_uniq = int(qb.n_uniq)
        built = kernel_uniq_lanes(n_uniq)
        chunks, bf16x3 = kernel_contract_chunks(n_uniq, qb.weights)
        global_metrics.inc("kernel_uniq_live", n_uniq)
        global_metrics.inc("kernel_uniq_built", built)
        global_metrics.inc("kernel_contract_chunks", chunks)
        global_metrics.inc("kernel_contract_chunks_bf16x3", bf16x3)

    def _pipeline(self) -> PipelineExecutor:
        """The searcher's SHARED dispatch/fetch executor (lazy). One per
        searcher, shared by every concurrent search call: chunks from
        concurrent ``/worker/process-batch`` handlers interleave on its
        dispatch thread, so batch B's device program launches while
        batch A's fetch is still on the wire — the overlap the old
        per-call loop could not provide (PERF.md round 6)."""
        pipe = self._pipe
        if pipe is None:
            with _pipe_init_lock:
                pipe = self._pipe
                if pipe is None:   # lost the race: reuse the winner's
                    # (two first-searches double-constructing would
                    # transiently double the depth+1 HBM budget and
                    # leak a thread pair until idle exit)
                    pipe = self._pipe = PipelineExecutor(
                        depth=self.pipeline_depth, name="search")
        return pipe

    def _use_executor(self) -> bool:
        """Resolve ``pipeline_mode``: the executor buys overlap only
        where the d2h fetch has real latency (an accelerator); on the
        CPU backend a "fetch" is a shared-memory view, and the
        three thread hand-offs per chunk cost more than they hide —
        measured ~27% concurrent-caller throughput loss — so "auto"
        keeps CPU inline and turns the executor on for accelerators."""
        if self.pipeline_mode == "executor":
            return True
        if self.pipeline_mode == "inline":
            return False
        return jax.default_backend() != "cpu"

    def _run_pipelined(self, chunks, dispatch, fetch, assemble) -> list:
        """Run chunks with up to ``pipeline_depth`` OVERLAPPED fetches:
        ``dispatch(chunk) -> state`` launches device work,
        ``fetch(*state) -> fetched`` performs the single d2h transfer,
        ``assemble(*fetched) -> hits`` builds results on the caller's
        thread. On accelerator backends (or ``pipeline_mode=
        "executor"``) the stages run on the shared
        :class:`PipelineExecutor`, so chunks from CONCURRENT search
        calls also overlap; on CPU ("auto") the same stages run inline
        dispatch-then-drain (the fetch is free there and the executor's
        thread hand-offs are pure overhead).

        In-flight accounting (ADVICE r4, option B): dispatch-then-drain
        keeps **depth+1 chunks in flight** (depth fetches overlapping
        the newest chunk's compute; enforced by the executor's bounded
        hand-off queue). The r5 drain-before-dispatch variant (depth
        chunks total, depth-1 overlapped) measured ~2x slower on
        RTT-bound configs, so the extra in-flight buffer is kept
        deliberately — HBM sizing must budget depth+1 packed buffers."""
        if not self._use_executor():
            return self._run_inline(chunks, dispatch, fetch, assemble)
        pipe = self._pipeline()
        futures = [pipe.submit(lambda c=chunk: dispatch(c), fetch)
                   for chunk in chunks]
        out: list = []
        try:
            for fut in futures:
                out.extend(assemble(*fut.result()))
        except BaseException:
            for fut in futures:   # don't run chunks nobody will read
                fut.cancel()
            raise
        return out

    def _run_inline(self, chunks, dispatch, fetch, assemble) -> list:
        """Single-thread dispatch-then-drain over the SAME three stages
        (the pre-executor loop): overlaps one call's chunks via async
        dispatch, but not chunks across concurrent calls."""
        pending: deque = deque()
        out: list = []
        for chunk in chunks:
            pending.append(dispatch(chunk))
            if len(pending) > self.pipeline_depth:
                out.extend(assemble(*fetch(*pending.popleft())))
        while pending:
            out.extend(assemble(*fetch(*pending.popleft())))
        return out


class Searcher(SearchLoop):
    def __init__(self, index: ShardIndex, analyzer: Analyzer,
                 vocab: Vocabulary, model: ScoringModel,
                 *, use_pallas: bool = False, **loop) -> None:
        super().__init__(index, analyzer, vocab, model, **loop)
        self.use_pallas = use_pallas
        # the packed top-k of the stretches whose scores may still be
        # allocated, oldest first (see _hold_back), and the stretch
        # plans of the current snapshot by batch bucket
        self._in_flight: deque = deque()
        self._flight_lock = threading.Lock()
        self._plans: dict[tuple[int, int], list[Stretch]] = {}

    def posting_blocks(self) -> list[tuple]:
        """``(array, rides_kernel)`` for every posting block of the
        committed snapshot — what ``Engine.compute_stats`` reports on:
        where the index lives, and how many blocks the fused Pallas
        kernel scores at this searcher's full query batch (the same
        predicate ``score_ell_impl`` dispatches on)."""
        snap = self.index.snapshot
        if snap is None:
            return []
        if isinstance(snap, SegmentedSnapshot):
            views = snap.views or tuple(v for _i, _b, v in snap.hot)
            return [(tf, False) for v in views for tf in v.tfs]
        if not snap.is_ell:
            return [(snap.tf, False)]
        return [(imp, self.use_pallas and _pallas_eligible(
                    imp.shape[1], self.query_batch, self._u_floor))
                for imp in snap.ell_impacts]

    def _score_chunk(self, snap: Snapshot, qb):
        """``(blocks, live, live_host)``: the chunk's WHOLE score space
        as the tuple of ``[B, cap_i]`` blocks ``packed_topk_chunked``
        takes (the ELL layout's own; one block for the others), the
        blocks' live column counts on the device, and the same counts as
        the host integers the commit had. The serving path of the ELL
        layout takes that space a stretch at a time instead
        (:meth:`_dispatch_ell`)."""
        if (self.use_pallas and not isinstance(snap, SegmentedSnapshot)
                and snap.is_ell):
            self._count_kernel_uniq(qb)
        with trace_phase("score"):
            if isinstance(snap, SegmentedSnapshot):
                # tiered snapshots publish no eager views; materialize
                # them all (faulting in the whole cold tier) — this IS
                # the untiered computation, used by the unbounded path
                # and the tier_bypass parity oracle
                views = (snap.views if snap.tier is None
                         else snap.tier.all_views(snap))
                scores = score_segments_batch(
                    views, snap.df, qb, snap.n_docs, snap.avgdl,
                    **self.model.score_kwargs())
                # the whole padded space is live: pads score 0
                return (scores,), snap.num_docs, (scores.shape[1],)
            if snap.is_ell:
                whole, = self._stretches(snap, qb.slots.shape[0], None)
                return (self._score_ell(snap, qb, whole), whole.live,
                        whole.live_host)
            scores = score_coo_batch(
                snap.tf, snap.term, snap.doc, snap.doc_len, snap.df,
                qb, snap.n_docs, snap.avgdl, snap.doc_norms,
                **self.model.score_kwargs())
            return (scores,), snap.num_docs, (snap.num_names,)

    def _score_ell(self, snap: Snapshot, qb, st: Stretch) -> tuple:
        """Enqueue the score program over one stretch of the ELL
        blocks: their ``[B, cap_i]`` scores. Gather fast path: impacts
        precomputed at commit; big blocks ride the fused compare/MXU
        Pallas kernel; what spilled past the widest block, the scatter
        path — into block 0, so with the stretch that holds it."""
        res = (None, None, None)
        if st.first == 0:
            res = (snap.res_tf, snap.res_term, snap.res_doc)
            global_metrics.inc("residual_entries_scored", snap.res_nnz)
        return score_ell_batch(
            snap.ell_impacts[st.first:st.stop],
            snap.ell_terms[st.first:st.stop], st.live, *res,
            snap.doc_len, snap.df, qb,
            snap.n_docs, snap.avgdl, snap.doc_norms,
            use_pallas=self.use_pallas,
            **self.model.score_kwargs())

    def _stretches(self, snap: Snapshot, cap: int,
                   budget: int | None) -> list[Stretch]:
        """The snapshot's ELL blocks as the stretches a chunk of bucket
        ``cap`` takes them in, each within ``budget`` bytes of scores
        (``ops.ell.plan_stretches``). One stretch over every block
        passes the snapshot's own live counts and no base: the program
        pair of a corpus that fits is the unstretched one."""
        rows = [imp.shape[1] for imp in snap.ell_impacts]
        live = snap.ell_live_host
        out = []
        for first, stop in plan_stretches(rows, cap, budget):
            whole = stop - first == len(rows)
            out.append(Stretch(
                first, stop,
                snap.ell_live if whole
                else jnp.asarray(live[first:stop], jnp.int32),
                live[first:stop],
                None if whole else jnp.int32(sum(live[:first])),
                4 * cap * sum(rows[first:stop])))
        return out

    def _stretch_plan(self, snap: Snapshot, cap: int) -> list[Stretch]:
        """:meth:`_stretches` under the budget this process observes —
        what its device holds, less the committed index, shared by the
        stretches kept in flight (``ops.ell.stretch_budget``) — once a
        snapshot and bucket."""
        key = (snap.version, cap)
        plan = self._plans.get(key)
        if plan is None:
            budget = stretch_budget(
                device_bytes_limit(snap.ell_impacts[0]),
                snap.size_bytes(), self.pipeline_depth + 1)
            plan = self._stretches(snap, cap, budget)
            # (whole-dict swap: concurrent inline callers read it)
            self._plans = {k: v for k, v in self._plans.items()
                           if k[0] == snap.version} | {key: plan}
        return plan

    def _hold_back(self) -> None:
        """Before a stretch's score program is enqueued: wait out the
        oldest stretch in flight once ``pipeline_depth + 1`` of them
        are. PjRt allocates a program's outputs when it is ENQUEUED and
        frees them once their reader has run, so a host running ahead
        of the device would allocate every stretch's scores at once;
        held back, at most ``pipeline_depth + 1`` stretches' are — what
        ``stretch_budget`` divided the device by. A chunk of one
        stretch never waits here: the pipeline keeps no more chunks
        than that unfetched. (Inline callers on several threads can
        overshoot by one each; there the backend is the CPU.)"""
        with self._flight_lock:
            full = len(self._in_flight) > self.pipeline_depth
            oldest = self._in_flight.popleft() if full else None
        if oldest is not None:
            with trace_phase("stretch_wait"):
                jax.block_until_ready(oldest)

    def _rank(self, blocks, live, live_host, base, kk: int):
        """Enqueue the top-k over a stretch's score blocks (of every
        layout: a non-ELL chunk is one block); the packed ``[B, 2kk]``
        winners, still on the device."""
        # the top-k's windows over this padded score space (chunks; at
        # a depth past 128 whole blocks), those of them wholly in dead
        # tails, which it skips, and those it ranks by group maxima
        chunks, skipped, grouped = topk_chunk_counts(
            [blk.shape[1] for blk in blocks], live_host, k=kk)
        global_metrics.inc("topk_chunks", chunks)
        global_metrics.inc("topk_chunks_skipped", skipped)
        global_metrics.inc("topk_chunks_grouped", grouped)
        return packed_topk_chunked(blocks, live, base, k=kk)

    # oracle switch: True forces tiered snapshots through the untiered
    # scoring path (every segment faulted + scored) — the parity
    # baseline tests and chaos runs compare the skipping path against
    tier_bypass = False

    def _dispatch_chunk(self, snap: Snapshot, qb, n_queries: int,
                        k: int):
        kk = min(k, snap.num_names)
        if isinstance(snap, SegmentedSnapshot):
            if snap.tier is not None and not self.tier_bypass:
                return self._dispatch_tiered(snap, qb, n_queries, kk), kk
        elif snap.is_ell:
            return self._dispatch_ell(snap, qb, kk), kk
        blocks, live, live_host = self._score_chunk(snap, qb)
        with trace_phase("topk"):
            return self._rank(blocks, live, live_host, None, kk), kk

    def _dispatch_ell(self, snap: Snapshot, qb, kk: int):
        """One chunk over the ELL blocks, a STRETCH at a time: score
        program on the stretch, top-k program on its ``[B, cap_i]``
        outputs with the stretch's base row, the scores dropped (their
        last reference goes with this frame's ``blocks``), and the
        per-stretch ``[B, kk]`` winners merged — exact, and a tie goes
        to the earlier stretch, so still to the lower document id. The
        live score space is a stretch's times those in flight, whatever
        the corpus holds. ONE ``score`` and one ``topk`` span a chunk,
        as every per-batch device metric divides by them: ``score``
        holds every stretch but the last one's top-k (and the waits of
        :meth:`_hold_back`), ``topk`` that and the merge — for a
        corpus of one stretch, exactly the two enqueues."""
        cap = qb.slots.shape[0]
        if self.use_pallas:
            self._count_kernel_uniq(qb)
        plan = self._stretch_plan(snap, cap)
        global_metrics.inc("score_stretches", len(plan))
        # the most score bytes this chunk can hold allocated at once
        global_metrics.inc("score_space_bytes", sum(sorted(
            st.nbytes for st in plan)[-(self.pipeline_depth + 1):]))
        done = []

        def rank(st: Stretch, blocks):
            packed = self._rank(blocks, st.live, st.live_host, st.base, kk)
            with self._flight_lock:
                self._in_flight.append(packed)
            return packed

        with trace_phase("score"):
            for st in plan[:-1]:
                self._hold_back()
                done.append(rank(st, self._score_ell(snap, qb, st)))
            self._hold_back()
            blocks = self._score_ell(snap, qb, plan[-1])
        with trace_phase("topk"):
            packed = rank(plan[-1], blocks)
            return merge_packed((*done, packed)) if done else packed

    def _dispatch_tiered(self, snap: SegmentedSnapshot, qb, B: int,
                         kk: int):
        """Tiered top-k: score the HOT segments in one device program,
        then walk the COLD segments in descending bound order, skipping
        every segment whose block-max upper bound proves it cannot beat
        the current kk-th positive candidate and faulting in the rest
        through the upload ring (next candidates prefetched so the
        host→HBM transfer hides behind scoring).

        Exactness: per-view outputs of ``score_segments_impl`` are
        independent, so scoring a segment alone is bit-identical to its
        slice of the full concat; (hot top-kk ∪ each scored cold
        segment's top-kk) ⊇ the global top-kk over live positive docs;
        skipped segments are provably below the kk-th positive
        candidate (STRICT bound comparison — an equal score could still
        displace on the (-score, gid) tie-break, so equality faults
        in). The host merge reproduces ``lax.top_k``'s order: descending
        score, ascending gid on ties. Returns a HOST buffer in the
        packed [B, 2·kk] wire layout (``fetch_packed`` is a no-op on
        host arrays)."""
        import jax.numpy as jnp

        tier = snap.tier
        cap = qb.slots.shape[0]
        skw = self.model.score_kwargs()

        # ---- hot pass: one device program over the resident set ----
        cand_vals = np.zeros((B, 0), np.float64)
        cand_gids = np.zeros((B, 0), np.int64)

        def add_candidates(vals, gids):
            nonlocal cand_vals, cand_gids
            cand_vals = np.concatenate(
                [cand_vals, vals.astype(np.float64)], axis=1)
            cand_gids = np.concatenate(
                [cand_gids, gids.astype(np.int64)], axis=1)

        if snap.hot:
            with trace_phase("score_hot"):
                hot_views = tuple(v for _i, _b, v in snap.hot)
                hot_caps = [v.live_mask.shape[0] for v in hot_views]
                hot_total = int(sum(hot_caps))
                scores = score_segments_batch(
                    hot_views, snap.df, qb, snap.n_docs, snap.avgdl,
                    **skw)
                kk_h = min(kk, hot_total)
                packed = packed_topk_chunked(
                    scores, jnp.int32(hot_total), k=kk_h)
                hvals, hids = unpack_topk(np.asarray(packed))
            # concat-local index -> global gid (hot segments need not
            # be contiguous in the snapshot's gid space)
            offs = np.cumsum([0] + hot_caps)
            hbase = np.asarray([b for _i, b, _v in snap.hot], np.int64)
            seg_of = np.searchsorted(offs, hids[:B], side="right") - 1
            gids = hbase[seg_of] + (hids[:B] - offs[seg_of])
            add_candidates(hvals[:B], gids)
            tier.touch_hot([snap.segments[i] for i, _b, _v in snap.hot])

        # ---- block-max bounds for every cold segment ----
        def thresholds() -> np.ndarray:
            """Per query: the kk-th largest strictly-positive candidate
            (-inf when fewer than kk positives exist — only positive
            scores fill the result quota)."""
            pos = np.where(cand_vals > 0.0, cand_vals, -np.inf)
            if pos.shape[1] < kk:
                return np.full(B, -np.inf)
            return -np.partition(-pos, kk - 1, axis=1)[:, kk - 1]

        handles = list(snap.cold)
        ub_of = {}
        if handles:
            U = int(qb.n_uniq)
            u_cap = qb.uniq.shape[0]
            # per-query f64 term weights in the batch's compact slot
            # space (the host mirror of _compile_queries' qc_ext;
            # column u_cap collects the pad writes and is dropped)
            qc = np.zeros((cap, u_cap + 1), np.float64)
            rows = np.repeat(np.arange(cap), qb.slots.shape[1])
            np.add.at(qc, (rows, np.asarray(qb.slots).reshape(-1)),
                      np.asarray(qb.weights,
                                 np.float64).reshape(-1))
            qc = qc[:B, :U]   # REAL query rows only: a padded row's
            # qc is all-zero -> bound exactly 0 -> always skippable
            uniq_terms = np.asarray(qb.uniq[:U]).astype(np.int64)
            df_u = snap.df_host[uniq_terms].astype(np.float64)
            # host mirrors, stamped at commit: reading the device
            # scalars here was a blocking d2h sync per dispatched chunk
            n_docs_f = snap.n_docs_f
            avgdl_f = snap.avgdl_f
            for h in handles:
                ub_of[id(h)] = query_upper_bounds(
                    h.bounds, uniq_terms, qc, df_u, n_docs_f, avgdl_f,
                    margin=tier.skip_margin,
                    **{kw: skw[kw] for kw in ("model", "k1", "b")
                       if kw in skw})
            # visit the likeliest contributors first: thresholds only
            # rise as candidates accumulate, so a high-bound-first walk
            # maximizes how many later segments prove skippable
            handles.sort(key=lambda h: -float(ub_of[id(h)].max())
                         if ub_of[id(h)].shape[0] else 0.0)
        tier.note_considered(len(handles))

        # ---- cold walk: skip by bound, else fault in + score ----
        skipped = 0
        for pos_i, h in enumerate(handles):
            thresh = thresholds()
            if tier.skip_enabled \
                    and skip_mask(ub_of[id(h)], thresh).all():
                skipped += 1
                continue
            # queue THIS segment's upload first, then prefetch the
            # upcoming survivors ring_depth deep — the single-worker
            # ring preserves submission order, so the wait below blocks
            # on this segment only while the next uploads stream behind
            # the scoring that follows
            tier.prefetch(h.seg)
            for nh in handles[pos_i + 1:pos_i + 1 + tier.ring_depth]:
                if not tier.skip_enabled \
                        or not skip_mask(ub_of[id(nh)], thresh).all():
                    tier.prefetch(nh.seg)
            view = tier.handle_view(h)
            with trace_phase("score_cold"):
                seg_scores = score_segments_batch(
                    (view,), snap.df, qb, snap.n_docs, snap.avgdl,
                    **skw)
                cap_i = int(view.live_mask.shape[0])
                kk_i = min(kk, cap_i)
                packed = packed_topk(seg_scores, jnp.int32(cap_i),
                                     k=kk_i)
                svals, sids = unpack_topk(np.asarray(packed))
            add_candidates(svals[:B], sids[:B].astype(np.int64) + h.base)
        tier.note_skips(skipped)

        # ---- host merge into the packed wire layout ----
        with trace_phase("topk"):
            C = cand_vals.shape[1]
            if C < kk:   # fewer candidate lanes than the quota: pad
                pad = kk - C
                cand_vals = np.concatenate(
                    [cand_vals, np.full((B, pad), -np.inf)], axis=1)
                cand_gids = np.concatenate(
                    [cand_gids, np.zeros((B, pad), np.int64)], axis=1)
            order = np.lexsort((cand_gids, -cand_vals),
                               axis=-1)[:, :kk]
            rsel = np.arange(B)[:, None]
            top_v = np.ascontiguousarray(
                cand_vals[rsel, order].astype(np.float32))
            top_g = cand_gids[rsel, order].astype(np.int32)
            arr = np.zeros((B, 2 * kk), np.int32)
            arr[:, :kk] = top_v.view(np.int32)
            arr[:, kk:] = top_g
        return arr

    def _search_unbounded(self, snap: Snapshot,
                          queries: list[str]) -> list[list[SearchHit]]:
        with trace_phase("vectorize"):
            qb, _widest = self._vectorize(queries,
                                          self._batch_cap(len(queries)))
        blocks, live, _ = self._score_chunk(snap, qb)
        segmented = isinstance(snap, SegmentedSnapshot)
        with trace_phase("rank_all"):
            # ELL blocks -> document order; the other layouts' one
            # block is in it already
            scores = (blocks[0] if segmented or not snap.is_ell
                      else ell_scores_to_real(blocks, live,
                                              snap.doc_len.shape[0]))
            # segmented doc ids interleave padding, so rank the whole
            # padded space (pads score 0 and are filtered below)
            rank_n = (scores.shape[-1] if segmented
                      else snap.num_names)
            vals, ids = full_ranking(scores, rank_n)
            vals = np.asarray(vals)
            ids = np.asarray(ids)
        return self._assemble(snap, queries, vals, ids, rank_n)
