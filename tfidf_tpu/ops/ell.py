"""Blocked-ELL postings layout + gather-based scoring.

The COO path (:mod:`tfidf_tpu.ops.scoring`) scores with per-chunk
``segment_sum`` — a *scatter*, the weakest memory op on TPU. This module is
the TPU-first alternative (SURVEY.md §7 "hard parts": padded ELL blocks,
bucketing by row length): postings are laid out as dense
blocks — one padded row of (term id, impact) pairs per document — so
scoring becomes *gathers* + a contraction the compiler fuses for the
VPU/MXU, with the output indexed directly by document row:

    scores[b, d] = sum_w  qc[b, slot_of[term[w, d]]] * impact[w, d]

A block is HELD width-major, ``[width, rows_cap]``: a document is a
COLUMN, its entries run down the width axis. That is how the fused
kernel reads it (``BlockSpec((width, td))``), so the commit writes it so
and nothing between the commit and the kernel turns it (from 128 wide a
``[rows, width]`` array is a physical copy away from it on a TPU: PR 43).

A single width would waste heavily on skewed corpora (a few long documents
force every row to their width), so documents are **sorted by distinct-term
count at commit** (``ShardIndex.to_coo``) and packed into width buckets
from ``ELL_WIDTH_LADDER`` (1.5x steps, 8..4096, cut at ``width_cap`` —
finer than powers of two because real corpora concentrate around their
mean distinct count); each bucket is its own dense block, and a rung
yields a block only where documents need it: passages fill rungs up to
64 or 128, whole documents of ~1,100 tokens the 384 and 512 rungs. Total
padded entries stay well within 2x of nnz regardless of skew. A residual
exists only where a document holds more distinct terms than the top rung
(4,096: a text of some tens of thousands of tokens) or than a lower
``width_cap`` (the mesh takes its buckets from the same ladder:
``parallel/mesh_ell.py``): those entries spill into a COO *residual*
scored by the chunked scatter path, and the partial score tensors add.

Row counts are power-of-two bucketed and widths come from the fixed
ladder, so the set of block shapes — and therefore XLA executables — is
reused as the shard grows. A block holds at most ``ELL_BLOCK_ROWS_MAX``
rows: a rung with more documents yields several blocks, so that a step
can score and rank the row axis in STRETCHES of whole blocks
(:func:`plan_stretches`) and its live score space is bounded by the
chip, not by the corpus.

Padding is inert: pad entries have impact 0 (tf=0); pad rows are all-pad.
Replaces the posting-list traversal inside Lucene's ``searcher.search``
(reference ``Worker.java:222-241``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tfidf_tpu.ops.csr import CooShard, next_capacity
from tfidf_tpu.ops.scoring import (QueryBatch, _compile_queries,
                                   bm25_weights, score_coo_compiled,
                                   score_coo_impl, tfidf_weights)


@dataclass
class EllBlock:
    tf: np.ndarray     # f32 [width, rows_cap]: a document is a column
    term: np.ndarray   # i32 [width, rows_cap] (pad id 0, pad tf 0; a
    #                    column's pads TRAIL its live entries)
    row0: int          # first shard doc row this block covers
    n_rows: int        # live rows (rows_cap - n_rows are padding)
    width: int


@dataclass
class EllShard:
    """Host-side blocked-ELL build product."""
    blocks: list[EllBlock]
    # residual COO for the entries of a document past the widest rung
    # (empty unless a document outgrows the ladder or ``width_cap``)
    res_tf: np.ndarray    # f32 [res_cap]
    res_term: np.ndarray  # i32 [res_cap]
    res_doc: np.ndarray   # i32 [res_cap], non-decreasing
    res_nnz: int


# Width ladder for the local blocked-ELL layout. Finer than powers of
# two (the 1.5x intermediate steps): real corpora concentrate around
# their mean distinct count, so pure power-of-two buckets waste ~13% of
# the A-build in pad entries (measured on the 1M-doc Zipf corpus:
# 86.2M -> 74.8M padded entries). The kernel takes any width; the top
# rung is the widest whose [width, td] posting blocks fit the kernel's
# VMEM at a doc tile of 128 for every batch bucket (``_pl_tiles``).
ELL_WIDTH_LADDER = (8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512,
                    768, 1024, 1536, 2048, 3072, 4096)
# Ceiling of a block's row capacity (a power of two). One ``[B, rows]``
# f32 score block of a 512-query batch is 2 GB at this size; without a
# ceiling a rung of 4.6M passages was ONE block of 8,388,608 rows, 17 GB
# of scores and past int32 elements. A million rows leave every block of
# a corpus to ~2M passages or ~1M documents as it was.
ELL_BLOCK_ROWS_MAX = 1 << 20


_FILL_ROWS = 512    # rows a line buffer of ``fill_width_major`` holds


def fill_width_major(dst: np.ndarray, values: np.ndarray,
                     sizes: np.ndarray) -> None:
    """Write the postings of consecutive rows into the zeroed block
    ``dst [width, rows_cap]``, row ``r`` as COLUMN ``r``: ``values`` are
    the rows' entries in row order, ``sizes[r] <= width`` of them row
    ``r``'s, which lead its column; its pads (``dst``'s zeros) trail
    them down the width, the select chain's order contract
    (``_pallas_kernel``). ``_FILL_ROWS`` rows at a time through a
    ``[rows, width]`` line buffer that stays in cache and is turned
    into place: a scatter straight into the width-major block misses
    the cache on every entry (3.5 s against 1.1 s for 38M entries in
    blocks of 512 and 384, on the sandbox's host: PR 43)."""
    width = dst.shape[0]
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    cols = np.arange(width)
    for lo in range(0, sizes.shape[0], _FILL_ROWS):
        top = min(lo + _FILL_ROWS, sizes.shape[0])
        lines = np.zeros((top - lo, width), dst.dtype)
        lines[cols < sizes[lo:top, None]] = values[bounds[lo]:bounds[top]]
        dst[:, lo:top] = lines.T


def build_ell_from_coo(coo: CooShard,
                       *,
                       width_cap: int | None = None,
                       min_width: int = 8,
                       min_rows: int = 256,
                       min_res_cap: int = 1 << 10,
                       max_rows: int | None = None) -> EllShard:
    """Vectorized COO → blocked ELL + residual (host side, commit time).

    Requires the COO invariants from ``ShardIndex.to_coo``: entries grouped
    by doc in increasing row order, rows sorted by distinct-term count
    descending, padding pointing at ``doc_cap - 1`` with tf=0.
    ``width_cap`` None: no ceiling under the ladder's top rung.
    A rung of more than ``max_rows`` rows (a power of two; None:
    ``ELL_BLOCK_ROWS_MAX``) yields full blocks of ``max_rows`` and a
    last one of the rest, in row order: a real row is still the running
    sum of the live counts before its block plus its column.
    A block is written width-major, ``[width, rows_cap]``: entry ``pos``
    of the block's row ``r`` is ``[pos, r]`` (:func:`fill_width_major`).
    """
    if width_cap is None:
        width_cap = ELL_WIDTH_LADDER[-1]
    if max_rows is None:
        max_rows = ELL_BLOCK_ROWS_MAX
    assert max_rows >= min_rows and max_rows & (max_rows - 1) == 0, max_rows
    nnz, n_live = coo.nnz, coo.num_docs
    doc_ids = coo.doc[:nnz]
    bounds = np.searchsorted(doc_ids, np.arange(n_live + 1))
    row_len = np.diff(bounds)
    assert (np.diff(row_len) <= 0).all(), \
        "blocked ELL requires rows sorted by length descending"
    pos = np.arange(nnz, dtype=np.int64) - bounds[:-1][doc_ids]

    # bucket width per row from the ladder (non-increasing because
    # row_len is); ladder entries below min_width / above width_cap
    # drop. The EFFECTIVE cap is the top ladder rung — the spill
    # boundary must match the widest bucket actually built, or entries
    # between rung and width_cap would land in neither a block nor the
    # residual (silently dropped) for non-ladder width_cap values.
    ladder = np.asarray(
        [w for w in ELL_WIDTH_LADDER if min_width <= w <= width_cap]
        or [min(max(min_width, 8), width_cap)], np.int64)
    eff_cap = int(ladder[-1])
    if n_live:
        idx = np.clip(np.searchsorted(ladder, np.minimum(row_len,
                                                         eff_cap)),
                      0, ladder.shape[0] - 1)
        widths = ladder[idx]
    else:
        widths = np.zeros(0, np.int64)
    blocks: list[EllBlock] = []
    row0 = 0
    while row0 < n_live:
        w = int(widths[row0])
        hi = min(int(np.searchsorted(-widths, -w, side="right")),
                 row0 + max_rows)
        n_rows = hi - row0
        rows_cap = next_capacity(n_rows, min_rows)
        tf = np.zeros((w, rows_cap), np.float32)
        term = np.zeros((w, rows_cap), np.int32)
        # the block's rows are one run of the COO (entries lie in row
        # order), so only that run is read, not the whole shard a block
        run = slice(int(bounds[row0]), int(bounds[hi]))
        sel = pos[run] < w
        sizes = np.minimum(row_len[row0:hi], w)
        fill_width_major(tf, coo.tf[run][sel], sizes)
        fill_width_major(term, coo.term[run][sel], sizes)
        blocks.append(EllBlock(tf=tf, term=term, row0=row0,
                               n_rows=n_rows, width=w))
        row0 = hi

    spill = pos >= eff_cap
    res_nnz = int(spill.sum())
    res_cap = next_capacity(max(res_nnz, 1), min_res_cap)
    res_tf = np.zeros(res_cap, np.float32)
    res_term = np.zeros(res_cap, np.int32)
    # pad rows point at doc_cap-1: keeps res_doc non-decreasing (the
    # indices_are_sorted contract of the residual's segment-sum)
    res_doc = np.full(res_cap, coo.doc_len.shape[0] - 1, np.int32)
    if res_nnz:
        res_tf[:res_nnz] = coo.tf[:nnz][spill]
        res_term[:res_nnz] = coo.term[:nnz][spill]
        res_doc[:res_nnz] = doc_ids[spill]
        # rows are sorted by length descending and only rows longer than
        # the top rung spill, so every spilled row lies in block 0, where
        # local column == real row: score_ell_with_residual adds the
        # residual to that block alone
        assert int(res_doc[:res_nnz].max()) < blocks[0].n_rows, \
            "COO residual rows must all lie in ELL block 0"
    return EllShard(blocks=blocks, res_tf=res_tf, res_term=res_term,
                    res_doc=res_doc, res_nnz=res_nnz)


def _entry_weights(model: str, tf, df_t, dl_col, n_docs, avgdl,
                   norms_col, k1: float, b: float):
    """Per-entry model weights for a block (dl_col/norms_col broadcast
    along its width axis: ``[1, rows]`` beside a width-major block,
    ``[rows, 1]`` beside a segment's ``[rows, width]``) — the single
    dispatch shared by the precomputed-impact and query-time paths."""
    if model == "bm25":
        return bm25_weights(tf, df_t, dl_col, n_docs, avgdl, k1=k1, b=b)
    if model == "tfidf":
        return tfidf_weights(tf, df_t, n_docs)
    if model == "tfidf_cosine":
        w = tfidf_weights(tf, df_t, n_docs)
        return w / jnp.where(norms_col > 0, norms_col, 1.0)
    raise ValueError(f"unknown model {model!r}")


def ell_impacts(tf: jax.Array,        # f32 [width, rows]
                term: jax.Array,      # i32 [width, rows]
                doc_len: jax.Array,   # f32 [rows] (this block's rows)
                df: jax.Array,        # f32 [vocab_cap]
                n_docs: jax.Array, avgdl: jax.Array,
                doc_norms: jax.Array | None = None,
                *, model: str = "bm25", k1: float = 1.2,
                b: float = 0.75) -> jax.Array:
    """Per-entry impact weights [width, rows] — everything about the score
    that does not depend on the query, precomputed once per commit
    (Lucene's "impacts" idea). The query path is then pure gather+contract."""
    norms_col = None if doc_norms is None else doc_norms[None, :]
    return _entry_weights(model, tf, df[term], doc_len[None, :],
                          n_docs, avgdl, norms_col, k1, b)


# one executable per (block shape, model): commit-time impact precompute
ell_impacts = jax.jit(ell_impacts, static_argnames=("model", "k1", "b"))


# --------------------------------------------------------------------------
# Pallas fused kernel — the TPU fast path for big blocks
# --------------------------------------------------------------------------
#
# The XLA path below is bound by per-element dynamic gathers
# (``qc_t[slot_of[term]]`` — measured ~10-25 gathered elements/cycle on
# v5e whatever the fusion). This kernel removes gathers entirely by
# factoring the score through the batch's compact term-slot space:
#
#     scores[b, d] = sum_u qc[b, u] * A[u, d]
#     A[u, d]      = sum_w imp[w, d] * (term[w, d] == uniq[u])
#
# A (the slot-impact matrix for a doc tile) is built with dense VPU
# compare+select against the batch's unique term ids — full-width vector
# ops, no gathers, B-independent — and the ``qc @ A`` contraction runs on
# the MXU. Everything lives in VMEM per tile; HBM traffic is postings in
# (8 bytes/entry) and scores out.
#
# Cost model per batch: nnz_padded * ceil(n_uniq/SU)*SU compare/select
# lane-ops for A plus 2*B*ceil(n_uniq/128)*128*rows MXU flops, THREE
# bf16 passes each when the batch's weights are exact in bfloat16 (term
# multiplicities: every batch the engine makes) and ``Precision.HIGHEST``'s
# six otherwise — vs the gather path's
# nnz_padded * B slow gathers. Wins whenever the batch's unique-term
# count is small relative to B * (gather-op slowdown ~40-100x), i.e.
# always for real query batches.
#
# The grid is (doc_tiles, uniq_tiles): for each doc tile the output
# block stays resident in VMEM while uniq tiles accumulate into it, and
# ``n_uniq`` arrives by scalar prefetch. INSIDE a grid step the loop
# nest is uniq sub-tiles outer, width inner (``_pallas_kernel``): a
# sub-tile of ``_PL_SU`` = 32 unique terms by TD documents is 16 vregs,
# lives in registers through the whole width loop and is stored once;
# the trip count of the sub-tile loop and the 128-row chunks the MXU
# contracts both come from ``n_uniq``. So work scales with the actual
# unique count, in steps of 32 lanes of A-build and 128 rows of
# contraction, not with the padded capacity: arbitrarily large u_cap
# costs nothing, and neither does the rest of a 512-lane tile that a
# batch only starts (PR 27; before it whole [TU, TD] tiles were built,
# their accumulator of 256 vregs read and written every width step).
#
# The A-build is ONE select chain down the width: a sub-tile's
# accumulator takes ``a = select(uniq == term_w, imp_w, a)`` an entry,
# 1 cmp + 1 sel = 2 vreg-ops an entry and no add; the rows' sublane
# broadcasts (8 ``vperm.slane`` a width row for the sub-tile's 16
# vregs, in the same four vector slots a bundle) make it 2.5 slot-ops.
# CONTRACT: within one document row the live term ids are
# DISTINCT (the ELL layout stores one posting per distinct term; ingest
# rejects duplicate or unsorted ids), pads carry impact 0 and TRAIL the
# live entries. So for one (uniq lane, document) at most one live entry
# matches, and selecting its impact is what adding it to 0.0 was, bit
# for bit. THE ORDER IS PART OF IT: a pad is ``term 0, impact 0``
# (``np.zeros`` in every builder) and term 0 is a real term, the most
# frequent of a Zipf vocabulary, so a pad MATCHES the lane of term 0;
# walked from the first row up, a trailing pad would overwrite a live
# term-0 impact with its 0.0. The chain therefore walks the width from
# its LAST row to its first, and a live entry is applied after every
# pad of its row (``tests/test_kernel_parity.py test_pad_trap`` and
# ``test_builders_trail_their_pads`` hold both halves). Term ids stay
# i32 even where the vocabulary fits 15 bits: Mosaic for v5e refuses a
# dynamic sublane load from an i16 tile ("cannot statically prove that
# index in dimension 0 is a multiple of 8") and, with the rows unrolled,
# an i16 compare mask feeding an f32 select ("Invalid relayout").
#
# The XLA reduce-fusion path (``_score_block``) stays untouched as the
# oracle. ``tests/test_kernel_compile.py`` compiles every shape class
# ``_pallas_eligible`` admits for v5e (compile-only, no chip needed).

_PL_TD = 512          # docs per grid tile (256 for small blocks)
_PL_TD_MIN = 128      # ... and no narrower than a lane tile, however wide
_PL_POSTINGS_VMEM = 10 << 20   # of the 16 MB, for the [width, td] blocks
_PL_ENTRY_VMEM = 16   # bytes of it an entry: term + impact, double-buffered
_PL_MAX_B = 2048      # VMEM: qc [B, TU] + out [B, TD] stay ~8MB
_PL_SU = 32           # uniq rows a register-resident A sub-tile holds
_PL_ROWS = 8          # width rows an A-build loop iteration loads: a sublane tile
_PL_TK = 128          # uniq rows an MXU contraction chunk holds
# the Pallas call's name starts with this constant, whatever jit encloses
# it: the device trace's event and the HLO instruction are named from it
# (PERF.md §3), followed by ``_w<block width>``
KERNEL_NAME = "ell_score_v4"


def pallas_interpret() -> bool:
    """Whether the fused kernel runs in the Pallas reference
    interpreter instead of lowering a Mosaic program: true on every
    backend but TPU (CPU tests). The ONE place that decides it, so the
    health surface (``Engine.compute_stats``) reports exactly what the
    kernel wrapper does."""
    return jax.default_backend() != "tpu"


def _pallas_kernel(lims_ref, uniq_ref, qc_ref, term_ref, imp_ref,
                   out_ref, a_ref, *, width: int, td: int, tu: int):
    """One (doc tile, uniq tile) grid step. Uniq SUB-TILES outer
    (``_PL_SU`` rows, trip count from ``n_uniq``), width inner: a
    sub-tile's accumulator ``[_PL_SU, td]`` stays in vector registers
    across the whole width loop — ONE select chain from the last width
    row to the first, a compare and a select an entry — and is written
    ONCE to the VMEM scratch ``a_ref [tu, td]``; then the MXU contracts
    the 128-row chunks of ``a_ref`` that hold a live term. Sub-tiles
    and chunks past the live unique terms are never built, compared or
    contracted.

    CONTRACT (the select chain's, see the notes above): within a
    document row the live term ids are distinct, pads carry impact 0
    and TRAIL the live entries — every ELL builder in this tree lays
    out one entry per distinct term from column 0 up over zeros
    (``build_ell_from_coo``; ``build_mesh_ell`` fills ``e.term_ids``,
    and the terms-axis width shard is a contiguous column slice, whose
    pads trail too). A row with a duplicate id would select once where
    the XLA path adds twice; a row with a ``term 0`` pad BEFORE a live
    term-0 entry would lose that entry."""
    d = pl.program_id(0)
    u = pl.program_id(1)
    su = _PL_SU

    @pl.when(u == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    # live unique terms of THIS uniq tile (<= 0: the tile is wholly
    # past them, its qc columns are zero). Bare lax primitives from
    # here down: a jnp wrapper is a nested jit to trace and lower, and
    # a worker traces this body for every block of every batch bucket
    # it warms up.
    n_live = lax.min(lims_ref[0] - u * tu, tu)

    # tiles past the live unique terms or past the block's live rows
    # (all-pad postings; power-of-two row caps leave up to 2x dead
    # rows, which the top-k masks) contribute nothing — skip them
    @pl.when(lax.bitwise_and(n_live > 0, d * td < lims_ref[1]))
    def _tile():
        zeros = lax.full((su, td), 0.0, jnp.float32)

        def build(s, carry):
            r0 = pl.multiple_of(s * su, su)
            # [su, 1] term ids across the lanes once a sub-tile, not
            # once a width step
            uniq = lax.broadcast_in_dim(uniq_ref[pl.ds(r0, su), :],
                                        (su, td), (0, 1))

            def rows(w0, n, a):
                """Width rows ``w0 .. w0 + n`` (n static) onto ``a``, the
                LAST row first: ONE load of the n rows of each array (a
                ref access is the dearest thing here to trace and
                lower: ~2 ms of a worker's warm-up each), every row
                then spread over the sub-tile's sublanes and selected
                into ``a`` where its term is the lane's: a compare and
                a select an entry, no add."""
                terms = term_ref[pl.ds(w0, n), :]    # [n, Td] i32
                imps = imp_ref[pl.ds(w0, n), :]      # [n, Td] f32

                def over_sublanes(x, j):             # row j as [su, Td]
                    return lax.broadcast_in_dim(
                        lax.slice_in_dim(x, j, j + 1), (su, td), (0, 1))

                for j in reversed(range(n)):
                    a = lax.select(lax.eq(uniq, over_sublanes(terms, j)),
                                   over_sublanes(imps, j), a)
                return a

            # ONE select chain down the width, from its last row to its
            # first (a pad must never be applied after a live entry of
            # its row: the notes above): the static tail of the width
            # first, then _PL_ROWS width rows a loop iteration from the
            # top group down (Mosaic unrolls a loop wholly or not at
            # all; wholly would grow with the width)
            a = zeros
            n_groups = width // _PL_ROWS
            if width % _PL_ROWS:
                a = rows(n_groups * _PL_ROWS, width % _PL_ROWS, a)
            if n_groups:             # (a zero-trip loop is still traced)
                a = lax.fori_loop(
                    0, n_groups,
                    lambda g, a: rows(
                        pl.multiple_of((n_groups - 1 - g) * _PL_ROWS,
                                       _PL_ROWS), _PL_ROWS, a),
                    a)
            a_ref[pl.ds(r0, su), :] = a
            return carry

        n_sub = lax.div(n_live + (su - 1), su)
        lax.fori_loop(0, n_sub, build, 0)

        # rows of the last live chunk past the last built sub-tile:
        # zeroed, not built (the scratch holds whatever ran before, and
        # 0 * NaN is not 0)
        def zero(s, carry):
            a_ref[pl.ds(pl.multiple_of(s * su, su), su), :] = zeros
            return carry

        n_chunks = lax.div(n_live + (_PL_TK - 1), _PL_TK)
        lax.fori_loop(n_sub, n_chunks * (_PL_TK // su), zero, 0)

        # the contraction rides the MXU at its own grain: [B, 128] @
        # [128, Td] per live chunk (qc arrives chunk-major, so a chunk
        # is a leading-axis index), f32-equivalent either way (ONE
        # default bf16 pass of the f32 operands costs ~0.4% relative
        # error, enough to flip top-k near-ties). The batch's own qc
        # decides how (lims_ref[2], see ``bf16_exact``): multiplicities
        # are exact in bfloat16, so of HIGHEST's six passes over the
        # bf16 pieces of both operands the three that take a lower
        # piece of qc multiply by zeros; the exact pieces of A against
        # bf16(qc), one native pass each, are the other three. Any
        # other weights take the HIGHEST dot.
        def contract(dot):
            def chunk(k, carry):
                a = a_ref[pl.ds(pl.multiple_of(k * _PL_TK, _PL_TK),
                                _PL_TK), :]
                out_ref[:] += dot(qc_ref[k], a)
                return carry
            lax.fori_loop(0, n_chunks, chunk, 0)

        @pl.when(lims_ref[2] != 0)
        def _three_passes():
            contract(_dot_bf16x3)

        @pl.when(lims_ref[2] == 0)
        def _six_passes():
            contract(_dot_highest)


def _mxu(q, a, precision=None):
    """``q [B, K] @ a [K, Td]`` accumulated in f32."""
    return lax.dot_general(q, a, (((1,), (0,)), ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def split_bf16x3(a):
    """f32 ``a`` as three bfloat16 pieces with ``hi + mid + lo == a``
    bit for bit (summed in f32, in that order): each piece takes the
    next 8 of the 24 significand bits, and each remainder is exact in
    f32. ~7 VPU ops a vreg of ``a``, hidden under the MXU's passes.
    For the kernel body: Mosaic keeps each round trip through bfloat16
    (as XLA does on the CPU, where the tests run it), but XLA for the
    TPU, allowed excess precision, folds ``a - f32(bf16(a))`` to zero
    and hands back ``hi`` alone (on the chip, PR 29)."""
    hi = lax.convert_element_type(a, jnp.bfloat16)
    rest = a - lax.convert_element_type(hi, jnp.float32)
    mid = lax.convert_element_type(rest, jnp.bfloat16)
    rest = rest - lax.convert_element_type(mid, jnp.float32)
    return hi, mid, lax.convert_element_type(rest, jnp.bfloat16)


def _dot_bf16x3(q, a):
    """``q @ a`` for a ``q`` that is exact in bfloat16: one native
    bf16 x bf16 -> f32 pass for each exact piece of ``a``."""
    q = lax.convert_element_type(q, jnp.bfloat16)
    hi, mid, lo = split_bf16x3(a)
    return _mxu(q, hi) + _mxu(q, mid) + _mxu(q, lo)


def _dot_highest(q, a):
    """``q @ a`` for any f32 ``q``: six passes over the pieces of both."""
    return _mxu(q, a, lax.Precision.HIGHEST)


def bf16_exact(x):
    """Whether every entry of f32 ``x`` (numpy or jax) is exact in
    bfloat16, as a term multiplicity up to 256 is and 0.37 or 257 is
    not: a bfloat16 is the upper half of a float32, so the lower 16
    bits are zero. On the bits, not ``x == f32(bf16(x))``: a compiler
    allowed excess precision may drop that round trip. The ONE
    predicate behind the kernel's three-pass contraction
    (``score_block_pallas``) and the counter that says how often it
    engages (``kernel_contract_chunks_bf16x3``)."""
    return ((x.view(np.uint32) & 0xFFFF) == 0).all()


def _pl_tiles(rows_cap: int, B: int, u_cap: int,
              width: int) -> tuple[int, int]:
    """(doc tile, uniq tile) for a block/batch shape. Bigger tiles
    amortize grid overhead; both tiles shrink as B grows so the
    multi-buffered qc [TU/128, B, 128] / out [B, TD] blocks plus the A
    accumulator and MXU temporaries stay inside the 16MB scoped-VMEM
    budget (Mosaic's buffering costs ~2x the naive block arithmetic,
    so the schedule is deliberately conservative): 512 tiles at B=1024
    or 256 at B=2048 ask the v5e compiler for 18.2 MB of scoped VMEM
    against its 16 MB.

    The doc tile also halves, down to 128 (a lane tile), until the term
    and impact blocks ``[width, td]`` fit ``_PL_POSTINGS_VMEM``: two
    arrays of 4 bytes, double-buffered, 16 bytes an entry. What the v5e
    compiler asked for where it refused (compile-only, PR 30) less
    those 16 bytes an entry is everything else, by (B, td, tu): 4.2 MB
    at (512, 512, 512), 1.5 at (512, 256, 512), 4.5 at (1024, 256,
    256), 5.25 at (2048, 128, 128); so 10 MB of postings leave the
    16 MB limit 0.75 MB at the worst. Widths to 256 (2 MB at td 512)
    never shrink the tile; 384 / 512 / 768 / 1024 take 3 / 4 / 6 / 8 MB
    at td 512; 1536 and 2048 run at td 256, 3072 and 4096 at 128 (at
    B <= 512; the larger buckets start lower). 4096 is the last rung
    every bucket to ``_PL_MAX_B`` compiles: 6144 fits at B <= 1024
    alone."""
    cap = 512 if B <= 512 else (256 if B <= 1024 else 128)
    td = min(cap, _PL_TD if rows_cap % _PL_TD == 0 else _PL_TD // 2)
    while td > _PL_TD_MIN and _PL_ENTRY_VMEM * width * td > _PL_POSTINGS_VMEM:
        td //= 2
    tu = min(cap, 512 if u_cap % 512 == 0 else 256, u_cap)
    return td, tu


def kernel_uniq_lanes(n_uniq: int) -> int:
    """The uniq lanes of A the kernel builds for a batch of ``n_uniq``
    distinct terms: whole sub-tiles of ``_PL_SU``. Host arithmetic for
    the ``kernel_uniq_built`` counter; the same for every block of a
    dispatch and every batch bucket."""
    return -(-n_uniq // _PL_SU) * _PL_SU


def ell_layout_gauges(shapes, live, res_doc: np.ndarray) -> dict[str, int]:
    """A committed layout as the ``ell_*`` gauges: ``shapes`` the
    blocks' own, ``(width, rows_cap)``, ``live`` their live rows, ``res_doc``
    the document row of every live entry of the COO residual, in its
    non-decreasing order.
    ``ell_rows_padded`` is the row axis of a step's score space (sum of
    rows_cap); ``ell_entries_padded`` what the blocks hold, live or pad
    (sum of rows_cap x width); ``ell_entries_live_tiles`` what a kernel call
    streams of it: the doc tiles that hold a live row (the rest are
    skipped), at the doc tile of a batch of up to 512 queries. Host
    arithmetic on the commit's own counts."""
    streamed = 0
    for (width, rows_cap), n_rows in zip(shapes, live):
        td, _tu = _pl_tiles(rows_cap, 1, _PL_TK, width)
        streamed += min(rows_cap, -(-int(n_rows) // td) * td) * width
    return {"ell_blocks": len(shapes),
            "ell_width_max": max((w for w, _r in shapes), default=0),
            "ell_rows_padded": sum(r for _w, r in shapes),
            "ell_entries_padded": sum(w * r for w, r in shapes),
            "ell_entries_live_tiles": streamed,
            "ell_residual_nnz": int(res_doc.shape[0]),
            "ell_residual_docs":
                int(np.count_nonzero(np.diff(res_doc))) + min(len(res_doc), 1)}


def plan_stretches(block_rows, B: int,
                   budget_bytes: int | None) -> list[tuple[int, int]]:
    """The blocks of a step as STRETCHES ``(first, past-the-last)``:
    each the longest run of whole blocks, in order, whose ``[B, rows]``
    f32 scores fit ``budget_bytes`` (a block over the budget alone is a
    stretch of its own; None: no bound, one stretch). A step scores and
    ranks one stretch, drops its scores and goes on to the next, so its
    live score space is a stretch's, whatever the corpus holds. Host
    arithmetic on static shapes: the same blocks, batch bucket and
    budget give the same stretches, and so the same compiled programs."""
    out, first, held = [], 0, 0
    for i, rows in enumerate(block_rows):
        need = 4 * B * int(rows)
        if budget_bytes is not None and i > first \
                and held + need > budget_bytes:
            out.append((first, i))
            first, held = i, 0
        held += need
    return out + [(first, len(block_rows))]


STRETCH_RESERVE = 8     # 1/8 of the device is left out of the budget


def stretch_budget(bytes_limit: int | None, index_bytes: int,
                   in_flight: int) -> int | None:
    """Bytes ONE stretch's scores may take: what the device holds
    (``memory_stats()["bytes_limit"]``; None where the backend does not
    say: no bound) less the committed index and an eighth for what
    this count leaves out (the programs' code and temporaries, query
    arrays, the allocator's fragmentation: a stretch's blocks are
    buffers of up to 2 GB each), shared by the ``in_flight`` stretches
    the dispatch lets be allocated at once. On a v5e (16,909,336,064
    bytes) that leaves 2M passages' whole 4.57 GB score space ONE
    stretch beside their 0.7 GB index, by 2.7%."""
    if bytes_limit is None:
        return None
    free = bytes_limit - bytes_limit // STRETCH_RESERVE - index_bytes
    return max(free, 0) // max(in_flight, 1)


def kernel_contract_chunks(n_uniq: int,
                           weights: np.ndarray) -> tuple[int, int]:
    """``(chunks, bf16x3)``: the live 128-row chunks of A the kernel
    contracts for a batch of ``n_uniq`` distinct terms, and those it
    contracts in three bf16 passes instead of HIGHEST's six: all of a
    batch whose host ``weights`` are exact in bfloat16, none of any
    other. Host arithmetic for the ``kernel_contract_chunks*``
    counters, through the predicate the kernel's own flag is made of."""
    chunks = -(-n_uniq // _PL_TK)
    return chunks, chunks if bf16_exact(weights) else 0


def score_block_pallas(impact: jax.Array,    # f32 [width, rows_cap]
                       term: jax.Array,      # i32 [width, rows_cap]
                       uniq: jax.Array,      # i32 [U_cap] batch term ids
                       n_uniq: jax.Array,    # i32 scalar (traced)
                       qc_ext: jax.Array,    # f32 [B, U_cap+1]
                       n_rows: jax.Array | None = None,  # i32 scalar
                       ) -> jax.Array:
    """Fused ELL-block scoring on TPU: ``[B, rows_cap]`` scores.

    ``n_rows`` (traced) is the block's live row count: doc tiles wholly
    past it skip the A-build and contraction (their scores are zeroed by
    the unconditional init, exactly what all-pad rows would score).
    The XLA reduce-fusion path is the oracle (``kernel_parity.py``).
    The block arrives as the index holds it and as the kernel's
    ``BlockSpec((width, td))`` reads it: nothing here turns it.
    """
    width, rows_cap = impact.shape
    B, _ = qc_ext.shape
    # the kernel contracts whole 128-row chunks: a capacity that is not
    # a multiple (no eligible shape; small direct callers) is padded
    # with more never-matching ids and zero weights
    u_cap = -(-uniq.shape[0] // _PL_TK) * _PL_TK
    grow = u_cap - uniq.shape[0]
    td, tu = _pl_tiles(rows_cap, B, u_cap, width)
    # the grid floor-divides: a non-multiple capacity would silently
    # drop the trailing tile (callers route through _pallas_eligible,
    # but direct callers must fail loudly, not score wrong); a width
    # past the ladder's top would ask Mosaic for more VMEM than it has
    assert rows_cap % td == 0 and u_cap % tu == 0 and tu % _PL_TK == 0 \
        and _PL_ENTRY_VMEM * width * td <= _PL_POSTINGS_VMEM, \
        (rows_cap, td, u_cap, tu, width)
    # pad entries of uniq must never match a real term id
    uniq_col = jnp.where(jnp.arange(u_cap) < n_uniq, jnp.pad(uniq, (0, grow)),
                         jnp.int32(-1))[:, None]     # [U1, 1]
    # drop the zero column; chunk-major [U1/128, B, 128], so the kernel
    # takes a contraction chunk by its leading index
    qc = jnp.pad(qc_ext[:, :uniq.shape[0]], ((0, 0), (0, grow))).reshape(
        B, u_cap // _PL_TK, _PL_TK).swapaxes(0, 1)
    if n_rows is None:
        n_rows = jnp.int32(rows_cap)
    # the scalars a grid step reads: live unique terms, live rows, and
    # whether this batch's weights let the contraction take three passes
    lims = jnp.stack([jnp.asarray(n_uniq, jnp.int32),
                      jnp.asarray(n_rows, jnp.int32),
                      bf16_exact(qc_ext).astype(jnp.int32)])

    kernel = functools.partial(_pallas_kernel, width=width, td=td, tu=tu)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # u is the INNER axis: the output block for a doc tile stays in
        # VMEM while uniq tiles accumulate into it ("arbitrary" marks
        # the accumulation-carried axis)
        grid=(rows_cap // td, u_cap // tu),
        in_specs=[
            pl.BlockSpec((tu, 1), lambda d, u, n: (u, 0)),    # uniq ids
            pl.BlockSpec((tu // _PL_TK, B, _PL_TK),
                         lambda d, u, n: (u, 0, 0)),          # query w
            pl.BlockSpec((width, td), lambda d, u, n: (0, d)),  # terms
            pl.BlockSpec((width, td), lambda d, u, n: (0, d)),  # impacts
        ],
        out_specs=pl.BlockSpec((B, td), lambda d, u, n: (0, d)),
        scratch_shapes=[pltpu.VMEM((tu, td), jnp.float32)],   # A
    )
    return pl.pallas_call(
        kernel,
        # the block's width after the constant, so that a device trace
        # tells a wide block's calls from a narrow one's
        name=f"{KERNEL_NAME}_w{width}",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows_cap), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )(lims, uniq_col, qc, term, impact)


def _pallas_eligible(rows_cap: int, B: int, u_cap: int) -> bool:
    """Big blocks only — small blocks stay on the XLA path where they
    are cheap. u_cap is unbounded (uniq tiles past ``n_uniq`` are
    skipped, so capacity padding is free); B is VMEM-bounded."""
    return (rows_cap % (_PL_TD // 2) == 0 and rows_cap >= _PL_TD // 2
            and B <= _PL_MAX_B and u_cap % 256 == 0)


def _pick_chunk(rows_cap: int, width: int, B: int, doc_chunk: int) -> int:
    """Row-chunk bounding the [Dc, W, B] gathered intermediate to ~32MB
    whatever the batch/width, shrunk to a divisor of rows_cap (power-of-two
    caps make that a no-op, but nothing forces callers to configure so)."""
    budget = max(64, (1 << 23) // max(1, width * B))
    chunk = min(doc_chunk, rows_cap, budget)
    while rows_cap % chunk:
        chunk -= 1
    return chunk


_RED_LANES = 8   # lane width of the explicit ELL reduction order


def _lane_sum_w(x: jax.Array) -> jax.Array:
    """Sum f32 ``x [Dc, W, B]`` over W with a PINNED addition order:
    strided ``_RED_LANES``-lane accumulation followed by a halving
    tree, written as explicit adds XLA will not reassociate.

    A plain ``.sum(axis=1)`` lowers to an XLA reduce whose association
    order is implementation- and shape-dependent (probe: W=8 matches a
    tree, W>=48 matches no simple order at all), so nothing off-device
    can reproduce its bits.  Fixing the order in the program costs
    nothing measurable — the adds still fuse with the gather+mul into
    one loop — and makes the host-fallback mirror
    (``engine.compute_health._lane_reduce``, same lane count and tree)
    bit-exact by construction on every backend."""
    dc, w, b = x.shape
    pad = (-w) % _RED_LANES
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((dc, pad, b), jnp.float32)], axis=1)
    lanes = jnp.zeros((dc, _RED_LANES, b), jnp.float32)
    for i in range(x.shape[1] // _RED_LANES):
        lanes = lanes + x[:, i * _RED_LANES:(i + 1) * _RED_LANES]
    v = _RED_LANES
    while v > 1:
        v //= 2
        lanes = lanes[:, :v] + lanes[:, v:2 * v]
    return lanes[:, 0]                                # [Dc, B]


def _score_block(impact: jax.Array, term: jax.Array,
                 slot_of: jax.Array, qc_t: jax.Array,
                 doc_chunk: int) -> jax.Array:
    """One ELL block ``[width, rows_cap]``: gathers + contraction,
    chunked over rows.

    Returns ``[B, rows_cap]``. The [Dc, W, B] gathered intermediate is
    bounded by the chunk size regardless of block size. The arithmetic
    and its pinned reduction order are written over ``[rows, width]``
    chunks (what the host mirror reproduces bit for bit), so this path
    turns its operands itself: it scores the blocks the kernel does not
    take (a few rows) and every block of a CPU test.
    """
    width, rows_cap = impact.shape
    B = qc_t.shape[1]
    chunk = _pick_chunk(rows_cap, width, B, doc_chunk)
    n_chunks = rows_cap // chunk

    def body(_, xs):
        imp_c, term_c = xs                            # [Dc, W]
        qg = qc_t[slot_of[term_c]]                    # [Dc, W, B] gathers
        # multiply + explicit-order lane reduce, NOT einsum/dot: dot
        # operands must materialize in HBM, so an einsum here forces
        # the [Dc, W, B] gather output through memory (measured 3.5x
        # slower at 200k docs); the elementwise adds keep
        # gather+mul+sum in one loop fusion AND pin the f32 addition
        # order the host fallback mirrors (see _lane_sum_w)
        prod = qg * imp_c[:, :, None]                 # [Dc, W, B]
        # contraction fence: without it the backend fuses this multiply
        # into _lane_sum_w's first add as an FMA (observed on XLA CPU,
        # 1-ULP drift vs round-then-add), which no host mirror can
        # reproduce. The select's predicate is runtime data (term ids),
        # so neither XLA nor LLVM can fold it away, and an add whose
        # operand is a select — not the multiply itself — is never
        # contracted. Term ids are always >= 0, so the value is
        # unchanged; the fence costs one compare+select in a
        # memory-bound loop.
        prod = jnp.where(term_c[:, :, None] >= 0, prod, 0.0)
        scores_c = _lane_sum_w(prod).T                # [B, Dc]
        return None, scores_c

    xs = (impact.T.reshape(n_chunks, chunk, width),
          term.T.reshape(n_chunks, chunk, width))
    _, chunks = jax.lax.scan(body, None, xs)          # [n, B, Dc]
    return jnp.moveaxis(chunks, 0, 1).reshape(B, rows_cap)


def _rearrange_to_real(parts, block_caps, block_live, doc_cap: int,
                       B: int) -> jax.Array:
    """Concatenate per-block padded scores and gather them into the real
    doc-id space [B, doc_cap].

    Real doc id d lives in block i at padded index pad0_i + (d - row0_i),
    where row0_i is the sum of (traced) live counts before block i; dead
    real rows gather from an explicit zero column at index P.
    """
    if not parts:
        return jnp.zeros((B, doc_cap), jnp.float32)
    padded = jnp.concatenate(
        parts + [jnp.zeros((B, 1), jnp.float32)], axis=1)   # [B, P+1]
    P = padded.shape[1] - 1
    real = jnp.arange(doc_cap, dtype=jnp.int32)
    row0 = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(block_live.astype(jnp.int32))])
    padded_of_real = jnp.full((doc_cap,), P, jnp.int32)
    pad0 = 0
    for i, cap in enumerate(block_caps):
        in_block = (real >= row0[i]) & (real < row0[i + 1])
        padded_of_real = jnp.where(
            in_block, pad0 + real - row0[i], padded_of_real)
        pad0 += cap
    return padded[:, padded_of_real]                  # [B, doc_cap]


def score_ell_impl(impacts,            # tuple of f32 [width_i, rows_cap_i]
                   terms,              # tuple of i32 [width_i, rows_cap_i]
                   block_live,         # i32 [n_blocks] — live rows (TRACED)
                   q: QueryBatch,
                   vocab_cap: int,
                   *, doc_chunk: int = 2048,
                   use_pallas: bool = False) -> tuple:
    """Gather-based scoring over all blocks: a tuple of per-block scores
    ``[B, rows_cap_i]``, each in its block's padded row space.

    Real doc id d lives in block i at column ``d - row0_i``, row0_i the
    sum of the live counts before block i; columns at or past
    ``block_live[i]`` are dead. The top-k reads the blocks in place
    (``ops.topk.packed_topk_chunked``); :func:`ell_scores_to_real` builds
    the ``[B, doc_cap]`` matrix for callers off the hot path. Live row
    counts are TRACED, so growing the corpus within the same capacity
    buckets reuses the executable — only the (static) block shapes key
    the compile cache. ``use_pallas`` routes big blocks through the
    fused compare/MXU kernel; the rest stay on the XLA path.
    """
    B = q.slots.shape[0]
    slot_of, qc_ext = _compile_queries(q, vocab_cap)
    qc_t = qc_ext.T                                   # [U_cap+1, B]
    u_cap = q.uniq.shape[0]
    with jax.named_scope("ell_blocks"):
        return tuple(
            score_block_pallas(imp, term, q.uniq, q.n_uniq, qc_ext,
                               block_live[i])
            if use_pallas and _pallas_eligible(imp.shape[1], B, u_cap)
            else _score_block(imp, term, slot_of, qc_t, doc_chunk)
            for i, (imp, term) in enumerate(zip(impacts, terms)))


def score_ell_with_residual(impacts, terms, block_live,
                            res_tf, res_term, res_doc,  # COO residual
                            doc_len, df, q: QueryBatch,
                            n_docs, avgdl, doc_norms=None,
                            *, model: str = "bm25", k1: float = 1.2,
                            b: float = 0.75, doc_chunk: int = 2048,
                            res_chunk: int = 1 << 10,
                            use_pallas: bool = False) -> tuple:
    """Full shard scores, per block (see :func:`score_ell_impl`): blocked
    ELL + COO residual (overlong docs).

    Pass ``res_tf=None`` when nothing spilled — the residual pass is
    skipped entirely instead of scanning guaranteed-zero padding. Every
    spilled row lies in block 0 (``build_ell_from_coo`` asserts it),
    whose columns ARE real rows, so the residual adds to that block
    alone.
    """
    parts = score_ell_impl(impacts, terms, block_live, q, df.shape[0],
                           doc_chunk=doc_chunk, use_pallas=use_pallas)
    if res_tf is not None:
        with jax.named_scope("coo_residual"):
            residual = score_coo_impl(
                res_tf, res_term, res_doc, doc_len, df, q,
                n_docs, avgdl, doc_norms, model=model, k1=k1, b=b,
                chunk=min(res_chunk, res_tf.shape[0]))    # [B, doc_cap]
            rows_cap0 = parts[0].shape[1]
            assert rows_cap0 <= residual.shape[1], (rows_cap0,
                                                    residual.shape)
            parts = (parts[0] + residual[:, :rows_cap0],) + parts[1:]
    return parts


def ell_scores_to_real(parts, block_live, doc_cap: int) -> jax.Array:
    """The ``[B, doc_cap]`` matrix in real doc-id order from per-block
    scores — for parity mode, probes and tests; no serving path builds
    it, on one chip or on the mesh (both rank the blocks in place:
    ``ops.topk.blocks_topk``)."""
    return _rearrange_to_real(list(parts), [p.shape[1] for p in parts],
                              block_live, doc_cap, parts[0].shape[0])


_score_ell_batch_jit = jax.jit(
    score_ell_with_residual,
    static_argnames=("model", "k1", "b", "doc_chunk", "res_chunk",
                     "use_pallas"))


def score_ell_batch(impacts, terms, block_live, res_tf, res_term,
                    res_doc, doc_len, df, q: QueryBatch, n_docs, avgdl,
                    doc_norms=None, **kw) -> tuple:
    """The ELL dispatch seam: the jitted scorer behind the device
    nemesis guard (``device.score_ell``). Unarmed cost is one attribute
    read; under an armed nemesis this is where injected OOM / compile /
    transient / sick faults surface and where a fired poison rule's NaN
    rows enter the output blocks (on device — detection happens at the
    fetch seam)."""
    from tfidf_tpu.utils.device_nemesis import device_guard, poison_scores
    rule = device_guard("score_ell", batch=int(q.slots.shape[0]),
                        uniq=int(q.uniq.shape[0]))
    scores = _score_ell_batch_jit(
        impacts, terms, block_live, res_tf, res_term, res_doc,
        doc_len, df, q, n_docs, avgdl, doc_norms, **kw)
    if rule is not None:
        scores = poison_scores(scores, q.weights, rule.min_uniq)
    return scores


def _score_block_tf(tf: jax.Array, term: jax.Array, dl: jax.Array,
                    df: jax.Array, slot_of: jax.Array, qc_t: jax.Array,
                    n_docs, avgdl, norms, doc_chunk: int,
                    *, model: str, k1: float, b: float) -> jax.Array:
    """ELL block scored with weights computed IN-KERNEL from the current
    global stats (df/N/avgdl) — the streaming-segment path, where
    precomputed impacts would go stale as the corpus grows. Lucene
    likewise scores old segments with current collectionStatistics."""
    rows_cap, width = tf.shape
    B = qc_t.shape[1]
    chunk = _pick_chunk(rows_cap, width, B, doc_chunk)
    n_chunks = rows_cap // chunk

    def body(_, xs):
        tf_c, term_c, dl_c, nrm_c = xs                # [Dc, W] / [Dc]
        w = _entry_weights(model, tf_c, df[term_c], dl_c[:, None],
                           n_docs, avgdl, nrm_c[:, None], k1, b)
        qg = qc_t[slot_of[term_c]]                    # [Dc, W, B]
        # reduce-fusion instead of einsum — see _score_block
        return None, (qg * w[:, :, None]).sum(axis=1).T

    xs = (tf.reshape(n_chunks, chunk, width),
          term.reshape(n_chunks, chunk, width),
          dl.reshape(n_chunks, chunk),
          norms.reshape(n_chunks, chunk))
    _, chunks = jax.lax.scan(body, None, xs)
    return jnp.moveaxis(chunks, 0, 1).reshape(B, rows_cap)


class SegmentView(NamedTuple):
    """Scoring-ready pytree for one streaming segment.

    Built at commit time (:meth:`SegmentedIndex.commit`); the snapshot —
    not the shared Segment object — owns the per-commit pieces
    (``live_mask``, cosine ``norms``), so an already-published snapshot
    never observes later deletes or df drift (snapshot isolation, the
    "fresh DirectoryReader" guarantee of ``Worker.java:223``).
    """
    # a segment's blocks stay [rows, width] (no kernel reads them):
    # the segmented index turns ``build_ell_from_coo``'s at ITS commit
    tfs: tuple            # f32 [rows_cap_i, width_i] blocks
    terms: tuple          # i32 [rows_cap_i, width_i]
    dls: tuple            # f32 [rows_cap_i] (model-transformed lengths)
    norms: tuple          # f32 [rows_cap_i] (zeros unless cosine)
    block_live: jax.Array # i32 [n_blocks] (traced)
    live_mask: jax.Array  # f32 [doc_cap] — 1=live, tombstones 0
    # COO residual for rows wider than the ELL width cap (None: no spill):
    # (res_tf, res_term, res_doc, res_dl [doc_cap], res_norms [doc_cap])
    res: tuple | None


def score_segment_ell(view: SegmentView, df, slot_of, qc_ext, qc_t,
                      n_docs, avgdl,
                      *, model: str = "bm25", k1: float = 1.2,
                      b: float = 0.75, doc_chunk: int = 2048) -> jax.Array:
    """One streaming segment: blocked ELL scored with current stats,
    rearranged to the segment's real doc space, plus the COO residual for
    over-wide documents, tombstones zeroed. Returns ``[B, doc_cap]``.
    ``slot_of``/``qc_ext``/``qc_t`` come from the caller's single
    per-batch ``_compile_queries``."""
    doc_cap = view.live_mask.shape[0]
    B = qc_t.shape[1]
    parts = [_score_block_tf(tf, term, dl, df, slot_of, qc_t,
                             n_docs, avgdl, nrm, doc_chunk,
                             model=model, k1=k1, b=b)
             for tf, term, dl, nrm in zip(view.tfs, view.terms,
                                          view.dls, view.norms)]
    scores = _rearrange_to_real(parts, [tf.shape[0] for tf in view.tfs],
                                view.block_live, doc_cap, B)
    if view.res is not None:
        # docs with more distinct terms than the width cap spill here —
        # scored by the chunked scatter path with the same in-kernel
        # current-stats weights (Lucene indexes arbitrarily wide docs,
        # Worker.java:190-220; streaming must too)
        res_tf, res_term, res_doc, res_dl, res_norms = view.res
        scores = scores + score_coo_compiled(
            res_tf, res_term, res_doc, res_dl, df, slot_of, qc_ext,
            n_docs, avgdl, res_norms, model=model, k1=k1, b=b,
            chunk=min(1 << 10, res_tf.shape[0]))
    return scores * view.live_mask[None, :]


def score_segments_impl(views, df, q: QueryBatch, n_docs, avgdl,
                        *, model: str = "bm25", k1: float = 1.2,
                        b: float = 0.75,
                        doc_chunk: int = 2048) -> jax.Array:
    """All streaming segments scored + concatenated: ``[B, sum(doc_cap)]``.

    ``views`` is a tuple of :class:`SegmentView` pytrees; the jit cache
    keys on the (static) segment shape structure, so repeated queries
    against the same segment set reuse one executable.
    """
    B = q.slots.shape[0]
    if not views:
        return jnp.zeros((B, 0), jnp.float32)
    slot_of, qc_ext = _compile_queries(q, df.shape[0])
    qc_t = qc_ext.T
    outs = [score_segment_ell(v, df, slot_of, qc_ext, qc_t, n_docs, avgdl,
                              model=model, k1=k1, b=b,
                              doc_chunk=doc_chunk)
            for v in views]
    return jnp.concatenate(outs, axis=1)


_score_segments_batch_jit = jax.jit(
    score_segments_impl,
    static_argnames=("model", "k1", "b", "doc_chunk"))


def score_segments_batch(views, df, q: QueryBatch, n_docs, avgdl,
                         **kw) -> jax.Array:
    """The segmented dispatch seam (``device.score_segments``): hot
    pass, cold walk, and the tier-bypass parity oracle all dispatch
    through here — see :func:`score_ell_batch` for the guard
    contract."""
    from tfidf_tpu.utils.device_nemesis import device_guard, poison_scores
    rule = device_guard("score_segments", batch=int(q.slots.shape[0]),
                        uniq=int(q.uniq.shape[0]))
    scores = _score_segments_batch_jit(views, df, q, n_docs, avgdl, **kw)
    if rule is not None:
        scores = poison_scores(scores, q.weights, rule.min_uniq)
    return scores


def cosine_norms_host(coo: CooShard, n_docs: float) -> np.ndarray:
    """Host-side per-doc L2 norms of the TF-IDF vectors (for the ELL
    layout, which never ships the COO to device)."""
    nnz = coo.nnz
    doc_cap = coo.doc_len.shape[0]
    df_t = coo.df[coo.term[:nnz]]
    w = coo.tf[:nnz] * (np.log((1.0 + n_docs) / (1.0 + df_t)) + 1.0)
    sq = np.bincount(coo.doc[:nnz], weights=w * w, minlength=doc_cap)
    return np.sqrt(sq[:doc_cap]).astype(np.float32)
