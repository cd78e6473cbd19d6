"""Pallas kernel parity harness.

Asserts that ``score_block_pallas`` matches the XLA reduce-fusion path
bit-closely across the eligibility envelope — block shapes, batch
widths, u_cap sizes, dead-row/dead-uniq tile skipping, widths with and
without a static tail past the 8-row loop — and that the top-10 ranking
is stable against the XLA path. Every case runs twice, because the kernel
contracts by what the batch's weights are: FRACTIONAL weights take the
``Precision.HIGHEST`` dot, term MULTIPLICITIES (what the engine's
queries carry; exact in bfloat16) the three bf16 passes. Beside the
kernel's matrix runs one case of the top-k that reads its blocks
(``run_topk_case``): the two-stage selection of ``ops/topk.py`` against
``lax.top_k`` of the masked block, ids as well as values; and one of
the stretched step (``run_stretch_case``): three blocks scored and ranked
a block at a time and merged, against the one program pair over all
three, ties on the stretch edges included; and the matrix's last kernel
cases are the TRAP the A-build's order is held by (``run_trap_case``: a
live term 0 before trailing ``term 0`` pads, bit-equal to the oracle)
and the LONG queries of ``msmarco2m-q2d`` (``run_long_query_case``:
~10,000 distinct terms a batch under a capacity of 16,384, ``T`` 128,
a multiplicity up to 53); and one of the MESH step on whole documents (``run_mesh_case``: a 512-
and a 384-wide bucket a shard inside ``shard_map``, the kernel against
``_score_block`` in the same step, both weight kinds).
``python kernel_parity.py --against <checkout>`` also runs every kernel
case on the kernel of ANOTHER checkout's ``tfidf_tpu/ops/ell.py`` (a
parent commit unpacked under a directory ``.gitignore`` lists) and
reports whether the two outputs are BIT-EQUAL: what a PR that changes
the kernel's body and not its arithmetic has to show on the chip. Three
callers share ``run_case``:

* ``chip_smoke.py``'s engine stage runs ``CASES`` on the TPU, where the
  kernels are Mosaic programs — the on-chip record;
* ``python kernel_parity.py`` does the same alone and writes
  ``KERNEL_PARITY.json`` (it refuses to write a record from a backend
  that interprets the kernel);
* ``tests/test_kernel_parity.py`` runs scaled-down shapes of the same
  edges in interpret mode on CPU, so a kernel regression fails tier-1
  without a chip.
"""

from __future__ import annotations

import importlib.util
import inspect
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from tfidf_tpu.ops.ell import (_pallas_eligible, _score_block, bf16_exact,
                               pallas_interpret, plan_stretches,
                               score_block_pallas, score_ell_batch)
from tfidf_tpu.ops.scoring import (QueryBatch, _compile_queries,
                                   make_query_batch)
from tfidf_tpu.ops.topk import (TOPK_CHUNK, merge_packed,
                                packed_topk_chunked, topk_chunk_counts,
                                unpack_topk)

TOP_K = 10


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def make_case(rng, *, rows_cap, width, n_rows, B, n_terms, u_req,
              vocab=500_000, ragged=False, multiplicity=False):
    """Random ELL block + query batch, the block as a case is written
    and read: a document a line, ``[rows_cap, width]`` (the device takes
    it through :func:`width_major`). Term ids are DISTINCT within
    each row (the layout contract every ELL builder guarantees and the
    kernel's select chain relies on: stride-offset construction — position
    w draws from the congruence class w mod width). Pad rows
    (>= n_rows) are zeroed like the real build; ``ragged`` additionally
    zeroes a random per-row tail (within-row trailing pads, the shape
    real width buckets produce); uniq capacity is driven via
    min_slots. Query weights are fractions in [1, 2), or with
    ``multiplicity`` the counts 1..3 a query's repeated terms give."""
    slots = max(vocab // width, 1)
    base = rng.integers(0, slots, size=(rows_cap, width))
    term = (base * width
            + np.arange(width, dtype=np.int64)[None, :]).astype(np.int32)
    imp = rng.random((rows_cap, width), dtype=np.float32)
    if ragged:
        fill = rng.integers(1, width + 1, size=(rows_cap, 1))
        dead = np.arange(width)[None, :] >= fill
        term[dead] = 0
        imp[dead] = 0.0
    term[n_rows:] = 0
    imp[n_rows:] = 0.0
    # queries draw from the same vocab so some terms hit
    q_terms = np.zeros((B, 8), np.int32)
    q_weights = np.zeros((B, 8), np.float32)
    for i in range(B):
        k = rng.integers(1, 5)
        ids = rng.integers(0, vocab, size=k)
        # seed a few query terms from the block so scores are non-zero
        if i % 3 == 0:
            ids[0] = term[rng.integers(0, max(n_rows, 1)),
                          rng.integers(0, width)]
        q_terms[i, :k] = ids
        q_weights[i, :k] = (rng.integers(1, 4, size=k) if multiplicity
                            else 1.0 + rng.random(k, dtype=np.float32))
    qb = make_query_batch(q_terms, q_weights, min_slots=u_req)
    return imp, term, qb


def width_major(block: np.ndarray) -> jax.Array:
    """A case's ``[rows_cap, width]`` block on the device as the index
    holds it and the kernel reads it: ``[width, rows_cap]``."""
    return jnp.asarray(np.ascontiguousarray(block.T))


def kernel_of(tree: str):
    """``score_block_pallas`` of the checkout at ``tree`` (a parent
    commit unpacked beside this one), its ``ops/ell.py`` loaded under a
    module name of its own: everything it imports is this tree's. It
    takes this tree's width-major blocks: a checkout from before PR 43,
    whose wrapper takes ``[rows_cap, width]`` and turns it itself, is
    handed them turned back."""
    spec = importlib.util.spec_from_file_location(
        "tfidf_tpu.ops._ell_against",
        os.path.join(tree, "tfidf_tpu", "ops", "ell.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    kernel = mod.score_block_pallas
    if "impact.T" not in inspect.getsource(kernel):
        return kernel
    return lambda imp, term, *rest: kernel(imp.T, term.T, *rest)


def _kernel_and_oracle(imp, term, qb, vocab, n_rows, against=None):
    """``(kernel, XLA oracle, same)``: the ``[B, rows_cap]`` numpy
    scores of one block under one batch, from ONE jitted program, and
    whether ``against``, another checkout's ``score_block_pallas``
    (:func:`kernel_of`), gave the kernel's bits (None without one)."""
    imp_d, term_d = width_major(imp), width_major(term)
    n_rows = jnp.int32(n_rows)

    @jax.jit
    def run(uniq, n_uniq, slots, weights):
        q = QueryBatch(uniq, n_uniq, slots, weights)
        slot_of, qc_ext = _compile_queries(q, vocab)
        args = (imp_d, term_d, q.uniq, q.n_uniq, qc_ext, n_rows)
        return (score_block_pallas(*args),
                _score_block(imp_d, term_d, slot_of, qc_ext.T, 2048),
                None if against is None else against(*args))

    out, ref, other = run(*(jnp.asarray(a) for a in qb))
    out = np.asarray(out)
    return out, np.asarray(ref), (
        None if other is None else bool(np.array_equal(out, other)))


def run_case(name, rng, against=None, **kw):
    """One case: the kernel against the XLA oracle on the same inputs,
    scores within 1e-4 and the top-10 ranking identical. ``bf16x3``
    reports which contraction the batch's weights select;
    ``bit_equal_against`` whether ``against`` (another checkout's
    kernel) gave the same bits, which the case then also needs (None
    without one)."""
    imp, term, qb = make_case(rng, **kw)
    bf16x3 = bool(bf16_exact(qb.weights))
    assert bf16x3 == kw.get("multiplicity", False), (name, bf16x3)
    rows_cap, B = kw["rows_cap"], kw["B"]
    u_cap = qb.uniq.shape[0]
    assert _pallas_eligible(rows_cap, B, u_cap), (name, rows_cap, B, u_cap)
    out, ref, same = _kernel_and_oracle(
        imp, term, qb, kw.get("vocab", 500_000), kw["n_rows"], against)
    live = slice(None), slice(None, kw["n_rows"])  # dead rows: both 0
    a = out[live]
    b = ref[live]
    k = min(TOP_K, kw["n_rows"])
    max_abs = float(np.max(np.abs(a - b))) if a.size else 0.0
    denom = np.maximum(np.abs(b), 1e-6)
    max_rel = float(np.max(np.abs(a - b) / denom)) if a.size else 0.0
    topk_equal = bool(
        (np.argsort(-a, axis=1, kind="stable")[:, :k]
         == np.argsort(-b, axis=1, kind="stable")[:, :k]).all())
    ok = max_abs < 1e-4 and topk_equal and same is not False
    log(f"[{name}] bf16x3={bf16x3} max|d|={max_abs:.2e} "
        f"topk={topk_equal} bit_equal_against={same} ok={ok}")
    return {"name": name, "bf16x3": bf16x3, "max_abs_delta": max_abs,
            "max_rel_delta": max_rel, "topk_identical": topk_equal,
            "bit_equal_against": same, "ok": ok, **kw}


# the trap the A-build's ORDER is held by (PR 33), at the loop-plus-even-
# tail width: on the chip a block of eight doc tiles, interpreted one
TRAP_CASE = dict(rows_cap=4096, width=38, B=256, u_req=512)
TRAP_INTERPRET_CASE = dict(rows_cap=256, width=38, B=16, u_req=256)


def make_trap_case(rng, *, rows_cap, width, B, u_req, vocab=500_000):
    """A block whose rows are SHORTER than its width: row ``r`` holds
    ``1 + r % width`` live entries (every length from 1 to the width)
    over trailing pads ``term 0, impact 0``, as every builder lays them
    out, and every other row's first entry is a LIVE posting of term 0.
    Query 0 is term 0 alone, every other query ONE term of the block,
    weight 1 or 2: a score is one exact product whatever the order of
    the sum, so kernel and oracle must agree BIT for bit. Returns
    ``(imp, term, qb, rows)``, ``rows`` those that hold the live term
    0: their pads match its lane too, and a chain that applied them
    after the live entry would score 0 there."""
    imp, term, _qb = make_case(rng, rows_cap=rows_cap, width=width,
                               n_rows=rows_cap, B=1, n_terms=1,
                               u_req=u_req, vocab=vocab)
    imp += np.float32(0.5)                  # no live impact is 0
    term[::2, 0] = 0
    fill = 1 + np.arange(rows_cap) % width
    dead = np.arange(width)[None, :] >= fill[:, None]
    term[dead] = 0
    imp[dead] = 0.0
    q_terms = np.zeros((B, 8), np.int32)
    q_weights = np.zeros((B, 8), np.float32)
    q_weights[0, 0] = 1.0                   # query 0: term 0 alone
    r = rng.integers(0, rows_cap, B - 1)
    q_terms[1:, 0] = term[r, rng.integers(0, fill[r])]
    q_weights[1:, 0] = rng.integers(1, 3, B - 1)
    return (imp, term, make_query_batch(q_terms, q_weights, min_slots=u_req),
            np.flatnonzero(term[:, 0] == 0))


def run_trap_case(rng, against=None, *, vocab=500_000, **kw):
    """:func:`make_trap_case` through the kernel and the XLA oracle:
    BIT-EQUAL, and query 0's score of every row with a live term 0 its
    impact, not the 0.0 of the pads behind it."""
    imp, term, qb, rows = make_trap_case(rng, vocab=vocab, **kw)
    assert qb.uniq[0] == 0 and rows.size
    out, ref, same = _kernel_and_oracle(imp, term, qb, vocab,
                                        kw["rows_cap"], against)
    equal = bool(np.array_equal(out, ref))
    term0_live = bool(np.array_equal(out[0, rows], imp[rows, 0])
                      and out[0, rows].all())
    # the block as the kernel was handed it: down the width axis of
    # ``[width, rows_cap]`` no live entry stands behind a pad
    filled = imp.T != 0
    trail = bool(filled.shape == (kw["width"], kw["rows_cap"])
                 and (filled[1:] <= filled[:-1]).all())
    ok = equal and term0_live and trail and same is not False
    log(f"[trap] width={kw['width']} oracle_bit_equal={equal} "
        f"term0_live={term0_live} bit_equal_against={same} ok={ok}")
    return {"name": "trap", "oracle_bit_equal": equal,
            "term0_live": term0_live, "term0_rows": int(rows.size),
            "pads_trail_the_width": trail,
            "bit_equal_against": same, "ok": ok, **kw}


# the top-k beside the kernel: one block as wide as the cells' widest,
# its live count inside a group of 128, at the batch bucket they run
TOPK_CASE = dict(B=512, cap=1 << 20, live=870_316, k=TOP_K)
TOPK_INTERPRET_CASE = dict(B=16, cap=3 << 14, live=40_037, k=TOP_K)
# the msmarco2m cell's blocks (tests/kernel_compile_worker.py CELL_STEPS)
TOPK_CELL_CAPS = (4096, 1048576, 1048576, 131072, 256, 256)


def run_topk_case(rng, *, B, cap, live, k, chunk=TOPK_CHUNK):
    """``packed_topk_chunked`` over one ``[B, cap]`` block of TIED scores
    (integers 1..3 over 97% exact zeros; every row's ``live - 1`` column
    a 3, so the live edge itself ranks) against ``lax.top_k`` of the same
    block masked past ``live``: values and ids bit-equal. Also the
    compiled program's temporaries at this bucket on the ``msmarco2m``
    cell's blocks (``memory_analysis()``; the parent's read 2,842,112
    bytes at B = 512: PERF.md)."""
    x = rng.integers(1, 4, size=(B, cap), dtype=np.int8)
    x[rng.random((B, cap), dtype=np.float32) < 0.97] = 0
    x[:, live - 1] = 3
    x_d = jnp.asarray(x, jnp.float32)
    del x

    @jax.jit
    def oracle(x):
        col = jnp.arange(cap, dtype=jnp.int32)[None, :]
        return jax.lax.top_k(jnp.where(col < live, x, -jnp.inf), k)

    want_v, want_i = (np.asarray(a) for a in oracle(x_d))
    lives = jnp.asarray([live], jnp.int32)
    got_v, got_i = unpack_topk(np.asarray(packed_topk_chunked(
        (x_d,), lives, k=k, chunk=chunk)))
    chunks, skipped, grouped = topk_chunk_counts([cap], [live], chunk, k=k)
    structs = tuple(jax.ShapeDtypeStruct((B, c), jnp.float32)
                    for c in TOPK_CELL_CAPS)
    temp = packed_topk_chunked.lower(
        structs, jax.ShapeDtypeStruct((len(structs),), jnp.int32),
        k=k).compile().memory_analysis().temp_size_in_bytes
    values_equal = bool(np.array_equal(got_v, want_v))
    ids_equal = bool(np.array_equal(got_i, want_i))
    ok = values_equal and ids_equal and grouped == chunks - skipped > 0
    log(f"[topk] values={values_equal} ids={ids_equal} chunks={chunks} "
        f"skipped={skipped} grouped={grouped} temp_bytes={temp} ok={ok}")
    return {"name": "topk", "B": B, "cap": cap, "live": live, "k": k,
            "values_equal": values_equal, "ids_equal": ids_equal,
            "chunks": chunks, "skipped": skipped, "grouped": grouped,
            "cell_temp_bytes": int(temp), "ok": ok}


# three of msmarco-full's 1M-row blocks of the 33-48-term rung, the last
# with a dead tail, at the batch the cell is answered at
STRETCH_CASE = dict(rows_cap=1 << 20, width=48, B=512, n_blocks=3,
                    last_live=700_481)
STRETCH_INTERPRET_CASE = dict(rows_cap=512, width=24, B=16, n_blocks=3,
                              last_live=300)
TIE_IMPACT = 1000.0     # over any sum of four impacts under 1 times 3


def run_stretch_case(rng, *, rows_cap, width, B, n_blocks, last_live,
                     vocab=500_000):
    """The stretched step against the whole one: ``n_blocks`` blocks
    of ``rows_cap`` rows, ``width`` wide (the last with ``last_live``
    live rows), scored
    and ranked ONE BLOCK A STRETCH — ``score_ell_batch`` on the block,
    ``packed_topk_chunked`` with the stretch's base row, ``merge_packed``
    — and once by the one program pair over every block. The packed
    answers are bit-equal: values and ids. One term that no other row
    holds sits, with the same impact, in the last five rows of every
    block and the first five of all but the first, so query 0's ten
    winners TIE exactly and
    straddle the first stretch edge (rows ``rows_cap - 5 ..
    rows_cap + 4``); queries 1 and 2 hold it beside other terms."""
    tie_term = vocab - 1
    lives = [rows_cap] * (n_blocks - 1) + [last_live]
    imps, terms = [], []
    q_terms = np.zeros((B, 8), np.int32)
    q_weights = np.zeros((B, 8), np.float32)
    for i, live in enumerate(lives):
        imp, term, _qb = make_case(rng, rows_cap=rows_cap, width=width,
                                   n_rows=live, B=1, n_terms=4, u_req=256,
                                   vocab=vocab - width)
        # a third of the queries draw their terms from this block's rows
        for b in range(3 + i, B, n_blocks):
            n = rng.integers(1, 5)
            q_terms[b, :n] = term[rng.integers(0, live, n),
                                  rng.integers(0, width, n)]
            q_weights[b, :n] = rng.integers(1, 4, n)
        edge = np.r_[0:5 if i else 0, live - 5:live]
        term[edge, 0] = tie_term
        imp[edge, 0] = TIE_IMPACT
        imps.append(width_major(imp))
        terms.append(width_major(term))
    q_terms[:3, 0], q_weights[:3, 0] = tie_term, 1.0
    q_terms[1:3, 1:3], q_weights[1:3, 1:3] = q_terms[3:5, :2], 1.0
    qb = make_query_batch(q_terms, q_weights, min_slots=1024)
    q = QueryBatch(*(jnp.asarray(a) for a in qb))
    doc_cap = n_blocks * rows_cap
    rest = (None, None, None, jnp.zeros(doc_cap, jnp.float32),
            jnp.zeros(vocab, jnp.float32), q, jnp.float32(doc_cap),
            jnp.float32(1.0), None)
    kw = dict(model="bm25", use_pallas=True)
    lives_d = jnp.asarray(lives, jnp.int32)

    whole = np.asarray(packed_topk_chunked(
        score_ell_batch(tuple(imps), tuple(terms), lives_d, *rest, **kw),
        lives_d, k=TOP_K))
    parts, base = [], 0
    for i, live in enumerate(lives):
        one = jnp.asarray([live], jnp.int32)
        scores = score_ell_batch((imps[i],), (terms[i],), one, *rest, **kw)
        parts.append(packed_topk_chunked(scores, one, jnp.int32(base),
                                         k=TOP_K))
        del scores
        base += live
    got = np.asarray(merge_packed(tuple(parts)))
    vals, ids = unpack_topk(got)
    first = rows_cap - 5
    tied = bool((vals[0] == TIE_IMPACT).all()
                and ids[0].tolist() == list(range(first, first + 10)))
    equal = bool(np.array_equal(got, whole))
    stretches = len(plan_stretches([rows_cap] * n_blocks, B,
                                   4 * B * rows_cap))
    ok = equal and tied and stretches == n_blocks
    log(f"[stretch] packed_equal={equal} tie_straddles_edge={tied} "
        f"stretches={stretches} ok={ok}")
    return {"name": "stretch", "B": B, "rows_cap": rows_cap,
            "width": width, "lives": lives, "stretches": stretches,
            "packed_equal": equal, "tie_straddles_edge": tied, "ok": ok}


# the expanded queries of ``msmarco2m-q2d`` (PR 46): a batch of 512
# queries of ~66 distinct terms out of ~10,000, ``T`` 128, capacity
# 16,384 (twenty live uniq tiles of 32, the last partly live), one
# term a query repeated up to 53 times
LONG_CASE = dict(rows_cap=8192, width=48, n_rows=8000, B=512, T=128,
                 n_pool=10_000, u_req=16384, max_mult=53)
LONG_INTERPRET_CASE = dict(rows_cap=512, width=24, n_rows=400, B=64,
                           T=128, n_pool=1_500, u_req=2048, max_mult=53)


def make_long_case(rng, *, rows_cap, width, n_rows, B, T, n_pool, u_req,
                   max_mult, vocab=500_000):
    """A block as :func:`make_case` draws it under a batch of LONG
    queries: every query holds 40 .. 97 distinct terms (``msmarco2m-
    q2d``'s range, 66 on average) of a pool of ``n_pool`` terms, half of
    them terms of the block's live rows so that most documents score;
    multiplicities are 1 for most terms, 2 .. 5 for a tenth, and one
    term a query repeats up to ``max_mult`` times (exact in bfloat16:
    the three-pass contraction)."""
    imp, term, _qb = make_case(rng, rows_cap=rows_cap, width=width,
                               n_rows=n_rows, B=1, n_terms=1, u_req=256,
                               vocab=vocab)
    in_block = np.unique(term[:n_rows])
    pool = np.unique(np.r_[
        rng.choice(in_block, min(n_pool // 2, in_block.size), replace=False),
        rng.integers(0, vocab, n_pool // 2)])
    q_terms = np.zeros((B, T), np.int32)
    q_weights = np.zeros((B, T), np.float32)
    for i in range(B):
        n = int(np.clip(40 + rng.poisson(26), 40, min(97, T)))
        q_terms[i, :n] = rng.choice(pool, n, replace=False)
        mult = np.ones(n, np.float32)
        some = rng.random(n) < 0.1
        mult[some] = rng.integers(2, 6, int(some.sum()))
        mult[0] = rng.integers(max_mult // 2, max_mult + 1)
        q_weights[i, :n] = mult
    return imp, term, make_query_batch(q_terms, q_weights, min_slots=u_req)


def run_long_query_case(rng, against=None, *, vocab=500_000, **kw):
    """:func:`make_long_case` through the kernel and the XLA oracle. A
    score is a sum of ~66 products of up to 53 x an impact, so it is
    held relative to the block's largest score, 1e-5, and the ranking
    free of tie order: the oracle's scores of the kernel's ten best
    documents are the oracle's ten best scores, to 1e-5 (a near-tie the
    two summation orders split is no fault of either)."""
    imp, term, qb = make_long_case(rng, vocab=vocab, **kw)
    n_uniq, u_cap = int(qb.n_uniq), qb.uniq.shape[0]
    assert bool(bf16_exact(qb.weights)) and u_cap == kw["u_req"] \
        and u_cap // 2 < n_uniq and qb.weights.max() > kw["max_mult"] // 2
    assert _pallas_eligible(kw["rows_cap"], kw["B"], u_cap)
    out, ref, same = _kernel_and_oracle(imp, term, qb, vocab,
                                        kw["n_rows"], against)
    a, b = out[:, :kw["n_rows"]], ref[:, :kw["n_rows"]]
    top = float(b.max())
    max_rel = float(np.max(np.abs(a - b))) / top
    k = min(TOP_K, kw["n_rows"])
    mine = np.argsort(-a, axis=1, kind="stable")[:, :k]
    best = -np.sort(-b, axis=1)[:, :k]
    rank_rel = float(np.max(np.abs(
        np.take_along_axis(b, mine, axis=1) - best) / top))
    dead_zero = bool(not out[:, kw["n_rows"]:].any())
    ok = max_rel < 1e-5 and rank_rel < 1e-5 and dead_zero \
        and same is not False and top > kw["max_mult"] // 2
    log(f"[long] n_uniq={n_uniq} u_cap={u_cap} T={kw['T']} "
        f"max_weight={float(qb.weights.max()):.0f} top_score={top:.2f} "
        f"max_rel={max_rel:.2e} rank_rel={rank_rel:.2e} "
        f"bit_equal_against={same} ok={ok}")
    return {"name": "long", "n_uniq": n_uniq, "u_cap": u_cap,
            "max_weight": float(qb.weights.max()), "top_score": top,
            "max_rel_delta": max_rel, "rank_rel_delta": rank_rel,
            "dead_rows_zero": dead_zero, "bit_equal_against": same,
            "ok": ok, **kw}


MESH_CASE = dict(docs=12_000, B=512, u_req=1024)
# interpreted: the two wide buckets alone ride the kernel (the empty
# narrow ones stay at 8 rows, on the XLA path: a twelfth of the compile)
MESH_INTERPRET_CASE = dict(docs=2_400, B=16, u_req=256, min_rows=8)


def run_mesh_case(rng, *, docs, B, u_req, vocab=500_000, devices=None,
                  min_rows=256):
    """The mesh step on whole documents: ``docs`` documents of 257 ...
    512 distinct terms committed by ``MeshEllIndex`` over ``devices``
    (None: every attached one) as a (D, 1) mesh (each shard a 512- and a
    384-wide bucket,
    the ten narrower ones empty at ``min_rows``, no residual), then
    ``make_mesh_ell_search`` with the kernel against the same step with
    ``_score_block`` in its place, once with fractional weights and once
    with multiplicities: the merged top-10's scores within 1e-4 and its
    documents identical."""
    from tfidf_tpu.models.base import get_model
    from tfidf_tpu.parallel.mesh import make_mesh
    from tfidf_tpu.parallel.mesh_ell import make_mesh_ell_search
    from tfidf_tpu.parallel.mesh_ell_index import MeshEllIndex

    D = len(devices or jax.devices())
    index = MeshEllIndex(get_model("bm25", k1=0.9, b=0.4),
                         mesh=make_mesh((D, 1), devices=devices),
                         min_doc_cap=min_rows)
    rows = []
    for i in range(docs):
        ids = np.unique(rng.integers(0, vocab, 560))[
            :rng.integers(257, 513)].astype(np.int32)
        tfs = rng.integers(1, 6, ids.shape[0]).astype(np.float32)
        index.add_document_arrays(f"d{i}", ids, tfs)
        rows.append(ids)
    snap = index.commit(vocab)
    shapes = [tuple(a.shape) for a in snap.base.impact]
    wide = [s[1] for s in shapes if s[1] > 256]     # [D, width, rows]
    eligible = all(_pallas_eligible(s[2], B, u_req) for s in shapes[:2])
    steps = [make_mesh_ell_search(index.mesh, k=TOP_K, model="bm25", k1=0.9,
                                  b=0.4, use_pallas=use)
             for use in (True, False)]
    out = {"name": "mesh", "devices": D, "docs": docs, "B": B,
           "buckets": [list(s[1:]) for s in shapes[:2]],
           "residual_nnz": snap.res_nnz, "weights": {}}
    ok = wide == [512, 384] and eligible and snap.res_nnz == 0
    for kind in ("fractional", "multiplicity"):
        q_terms = np.zeros((B, 8), np.int32)
        q_weights = np.zeros((B, 8), np.float32)
        for i in range(B):
            k = rng.integers(1, 5)
            q_terms[i, :k] = rng.choice(rows[rng.integers(0, docs)], k,
                                        replace=False)
            q_weights[i, :k] = (rng.integers(1, 4, size=k)
                                if kind == "multiplicity"
                                else 1.0 + rng.random(k, dtype=np.float32))
        qb = make_query_batch(q_terms, q_weights, min_slots=u_req)
        assert bool(bf16_exact(qb.weights)) == (kind == "multiplicity")
        (vals, gids), (ref_vals, ref_gids) = (
            tuple(np.asarray(x) for x in step(
                snap.base, snap.delta, snap.df_g, snap.n_docs, snap.avgdl,
                qb)) for step in steps)
        delta = float(np.max(np.abs(vals - ref_vals)))
        same = bool(np.array_equal(gids, ref_gids))
        found = bool((vals[:, 0] > 0).all())
        out["weights"][kind] = {"max_abs_delta": delta,
                                "topk_identical": same}
        ok = ok and delta < 1e-4 and same and found
        log(f"[mesh {kind}] devices={D} buckets={out['buckets']} "
            f"max|d|={delta:.2e} topk={same}")
    out["ok"] = ok
    return out


# the hardware matrix: north-star-like shapes + every eligibility edge
# (the tier-1 interpret run uses scaled-down shapes of the same edges)
CASES = [
    # north-star-like shapes (width buckets 128/64, big row caps —
    # scaled to keep the XLA reference path's runtime sane)
    dict(rows_cap=131072, width=128, n_rows=98000, B=512,
         n_terms=4, u_req=512),
    dict(rows_cap=262144, width=64, n_rows=250000, B=512,
         n_terms=4, u_req=512),
    # eligibility edges: small block (256 rows), non-%512 rows
    dict(rows_cap=256, width=32, n_rows=200, B=256, n_terms=4,
         u_req=256),
    dict(rows_cap=768, width=32, n_rows=700, B=256, n_terms=4,
         u_req=256),
    # the old U1=1024 ceiling boundary, exactly at and beyond it
    dict(rows_cap=4096, width=64, n_rows=4000, B=512, n_terms=4,
         u_req=1024),
    dict(rows_cap=4096, width=64, n_rows=4000, B=512, n_terms=4,
         u_req=2048),
    dict(rows_cap=4096, width=64, n_rows=4000, B=2048, n_terms=4,
         u_req=1024),
    # heavy dead-tile skipping: few live rows / few live uniq
    dict(rows_cap=65536, width=64, n_rows=700, B=256, n_terms=4,
         u_req=4096),
    # select-chain edges: ODD width (a static tail of one row), within-
    # row ragged pads, a small vocabulary (dense term-id collisions
    # between rows, and term 0 among them)
    dict(rows_cap=4096, width=33, n_rows=4000, B=256, n_terms=4,
         u_req=512),
    dict(rows_cap=4096, width=48, n_rows=4000, B=256, n_terms=4,
         u_req=512, ragged=True),
    dict(rows_cap=4096, width=31, n_rows=4000, B=256, n_terms=4,
         u_req=512, vocab=20_000, ragged=True),
    # the tile schedule's B steps (512 -> 256 -> 128 tiles)
    dict(rows_cap=4096, width=12, n_rows=4000, B=1024, n_terms=4,
         u_req=1024),
    # the rungs past 256 (whole documents, PR 30), at the batch bucket
    # and capacity the doc cell runs: doc tile 512 to width 1024, 256
    # to 2048, 128 to 4096 (fewer rows: the XLA reference gathers
    # rows x width x B elements)
    *(dict(rows_cap=4096 if width <= 1024 else 1024, width=width,
           n_rows=4000 if width <= 1024 else 1000, B=512, n_terms=4,
           u_req=1024)
      for width in (384, 512, 768, 1024, 1536, 2048, 3072, 4096)),
]


# the same edges at a scale the Pallas interpreter can run: small block
# floor, rows_cap not a multiple of 512, the U1=1024 boundary, odd
# widths (lone last row), within-row ragged pads, a small vocabulary
INTERPRET_CASES = [
    dict(rows_cap=256, width=16, n_rows=200, B=64, n_terms=4,
         u_req=256),
    dict(rows_cap=768, width=32, n_rows=700, B=64, n_terms=4,
         u_req=256),
    dict(rows_cap=512, width=24, n_rows=512, B=128, n_terms=4,
         u_req=1024),
    dict(rows_cap=512, width=33, n_rows=400, B=64, n_terms=4,
         u_req=256),
    dict(rows_cap=512, width=48, n_rows=400, B=64, n_terms=4,
         u_req=256, ragged=True),
    dict(rows_cap=512, width=31, n_rows=300, B=64, n_terms=4,
         u_req=256, vocab=20_000, ragged=True),
    # past the 256 rung: the widths whole documents fill (PR 30)
    dict(rows_cap=256, width=384, n_rows=200, B=64, n_terms=4,
         u_req=256, ragged=True),
    dict(rows_cap=512, width=512, n_rows=400, B=64, n_terms=4,
         u_req=256),
]


def run_matrix(seed: int = 7, against=None) -> dict:
    """Every case of the matrix on the attached backend, as the record
    ``KERNEL_PARITY.json`` holds: ``CASES`` where the kernel is a
    Mosaic program, ``INTERPRET_CASES`` where it is interpreted; each
    with fractional weights (``caseN``) and with multiplicities
    (``caseN-mult``), then the pad trap (``trap``) and the long
    queries (``long``), the last two of ``cases``; then the top-k's one
    case (``topk``), the stretched
    step's (``stretch``) and, on the chip, the mesh step's on whole
    documents (``mesh``). ``against``: another checkout's kernel
    (:func:`kernel_of`), which every kernel case must then equal bit
    for bit."""
    rng = np.random.default_rng(seed)
    interpret = pallas_interpret()
    cases = INTERPRET_CASES if interpret else CASES
    results = [run_case(f"case{i}{'-mult' if mult else ''}", rng, against,
                        multiplicity=mult, **kw)
               for i, kw in enumerate(cases) for mult in (False, True)]
    results.append(run_trap_case(rng, against, **(
        TRAP_INTERPRET_CASE if interpret else TRAP_CASE)))
    results.append(run_long_query_case(rng, against, **(
        LONG_INTERPRET_CASE if interpret else LONG_CASE)))
    topk = run_topk_case(rng, **(TOPK_INTERPRET_CASE if interpret
                                 else TOPK_CASE))
    stretch = run_stretch_case(rng, **(STRETCH_INTERPRET_CASE if interpret
                                       else STRETCH_CASE))
    # on the chip only: interpreted, it is ``tests/test_kernel_parity.py``'s
    # own case, and a rehearsal of ``chip_smoke.py`` need not pay for it
    mesh = {"ok": True, "skipped": "interpreted"} if interpret \
        else run_mesh_case(rng, **MESH_CASE)
    dev = jax.devices()[0]
    out = {
        "backend": jax.default_backend(),
        "mosaic_compiled": not interpret,
        "device_kind": dev.device_kind,
        "jax": jax.__version__,
        "all_ok": all(r["ok"] for r in results) and topk["ok"]
        and stretch["ok"] and mesh["ok"],
        "cases": results,
        "topk": topk,
        "stretch": stretch,
        "mesh": mesh,
    }
    if against is not None:
        out["bit_equal_against"] = sum(r["bit_equal_against"]
                                       for r in results)
    return out


def main(argv) -> int:
    from tfidf_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    if pallas_interpret():
        log(f"no TPU (backend={jax.default_backend()!r}): the kernel "
            "would run in the Pallas interpreter, which is not a "
            "parity record — tests/test_kernel_parity.py covers that")
        return 1
    if argv and (len(argv) != 2 or argv[0] != "--against"):
        log("usage: kernel_parity.py [--against <checkout>]")
        return 2
    out = run_matrix(against=kernel_of(argv[1]) if argv else None)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "KERNEL_PARITY.json"), "w") as f:
        json.dump(out, f, indent=1)
    n = len(out["cases"])
    log(f"[done] all_ok={out['all_ok']}: "
        f"{sum(r['ok'] for r in out['cases'])} of {n} kernel cases ok"
        + (f", {out['bit_equal_against']} of {n} BIT-EQUAL to "
           f"{argv[1]}'s kernel" if argv else ""))
    return 0 if out["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
