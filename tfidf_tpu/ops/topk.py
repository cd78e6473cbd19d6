"""Exact top-k and distributed top-k merge.

The reference returns *all* hits per worker (``Worker.java:230``:
``searcher.search(query, Integer.MAX_VALUE)``) and the leader sum-merges by
document name (``Leader.java:73-77``). On TPU we keep k static: each shard
produces an exact local top-k, shards are combined by concatenation +
re-top-k (associative, so it composes under ``all_gather``), and a
``full_ranking`` path covers the reference's unbounded-result behavior for
parity testing.

The serving top-k (:func:`packed_topk_chunked`) reads the scorer's blocks
where they lie, in chunks, and ranks a chunk in two stages: one reduce to
the maxima of groups of 128 contiguous columns, then only the ``k`` groups
that can hold a winner (:func:`_chunk_topk`, with the proof that it is
``lax.top_k``'s own answer, ties included). :func:`exact_topk`, the
unchunked form the mesh's shards use, is ``lax.top_k`` itself.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from tfidf_tpu.utils.tracing import trace_phase


@functools.partial(jax.jit, static_argnames=("k",))
def exact_topk(scores: jax.Array,     # f32 [B, doc_cap]
               num_docs: jax.Array,   # i32 scalar — live rows
               *, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k over live documents only; padded rows are masked to -inf.

    Ties break toward the lower document id (``lax.top_k`` semantics), the
    same order Lucene yields within a segment.
    """
    doc_cap = scores.shape[-1]
    live = jnp.arange(doc_cap, dtype=jnp.int32)[None, :] < num_docs
    masked = jnp.where(live, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, k)
    return vals, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(vals: jax.Array,   # f32 [..., n_parts, B, k]
               ids: jax.Array,    # i32 [..., n_parts, B, k] (global doc ids)
               *, k: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Merge per-shard top-k lists into a global top-k (same k).

    Inputs are stacked along a parts axis (e.g. the result of an
    ``all_gather`` over the docs mesh axis). Associative and exact: the
    global top-k is always contained in the union of per-shard top-ks.
    ``k`` (None: the parts' own) is the merged depth where it is not a
    part's: a request deeper than one mesh shard is wide takes every
    row of each shard and up to ``n_parts`` times that from here.
    """
    n_parts, B, part_k = vals.shape[-3:]
    flat_vals = jnp.moveaxis(vals, -3, -2).reshape(*vals.shape[:-3], B,
                                                   n_parts * part_k)
    flat_ids = jnp.moveaxis(ids, -3, -2).reshape(*ids.shape[:-3], B,
                                                 n_parts * part_k)
    top_vals, pos = jax.lax.top_k(flat_vals, part_k if k is None else k)
    top_ids = jnp.take_along_axis(flat_ids, pos, axis=-1)
    return top_vals, top_ids


def pack_topk(vals: jax.Array, ids: jax.Array) -> jax.Array:
    """Pack values + ids into ONE i32 ``[..., 2k]`` array — the
    single-transfer wire layout :func:`unpack_topk` inverts. Shared by
    every producer so the format lives in exactly one place.

    The packed dtype is INTEGER and the floats are bitcast INTO it —
    never ids into f32: an id below 2^23 bitcast to f32 is a denormal,
    and denormals get flushed to zero somewhere between the TPU and the
    host (seen on a v5e: ids came back 0 while values survived). Integer lanes have no denormal/NaN canonicalization
    hazards, so f32 bits ride them unharmed.
    """
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(vals, jnp.int32),
         ids.astype(jnp.int32)], axis=-1)


@functools.partial(jax.jit, static_argnames=("k",))
def packed_topk(scores: jax.Array, num_docs: jax.Array,
                *, k: int) -> jax.Array:
    """Top-k with values and indices packed into ONE i32 array
    ``[B, 2k]`` (float bits bitcast into the integer lanes — see
    :func:`pack_topk` for why the wire dtype must be integer) — a single
    device-to-host transfer fetches both, so a chunk pays the fixed
    per-transfer latency once; unpack with :func:`unpack_topk`."""
    vals, idx = exact_topk(scores, num_docs, k=k)
    return pack_topk(vals, idx)


def fetch_packed(packed):
    """The serving pipeline's FETCH stage: wait for the device to finish
    the packed ``[..., 2k]`` top-k buffer, then one device->host
    transfer of it, nothing else. Kept as a named function so the
    single d2h per chunk lives in exactly one place — the pipeline
    executor's fetch thread must do only this (hit assembly/unpacking
    happens later, on the caller's thread, so it never blocks the fetch
    stream). Wait and copy are two stages of the one timer
    (``device_wait``, ``d2h``): a lone ``np.asarray`` is both, unsplit.
    A host buffer (the tiered path's) is ready already and passes
    through both at no cost."""
    import numpy as np

    with trace_phase("device_wait"):
        jax.block_until_ready(packed)
    with trace_phase("d2h"):
        return np.asarray(packed)


def unpack_topk(packed) -> tuple:
    """Host-side inverse of :func:`pack_topk`. Accepts either a device
    array (fetches it — one np.asarray transfer) or the already-fetched
    numpy buffer from :func:`fetch_packed` (pure views, no copy of the
    ids lane)."""
    import numpy as np

    arr = np.asarray(packed)
    k = arr.shape[-1] // 2
    vals = np.ascontiguousarray(arr[..., :k]).view(np.float32)
    ids = arr[..., k:]
    return vals, ids


TOPK_GROUP = 128        # columns a group: one lane tile


def topk_grouped(cap: int, c: int, k: int) -> bool:
    """Whether the top-``k`` of a ``c``-column window of a ``cap``-column
    array goes by group maxima (:func:`_chunk_topk`): where the window
    holds at least eight times ``k`` groups, so that the ``k`` groups it
    ranks are well under it, and groups are whole lane tiles of the
    array. A block narrower than that, or a caller's ``k`` in the
    thousands, goes straight through ``lax.top_k``. Static shapes only:
    the device path and :func:`topk_chunk_counts` share it, so the
    host's count is the device's."""
    g = TOPK_GROUP
    return cap % g == 0 and c % g == 0 and c // g >= 8 * k


def _chunk_topk(x: jax.Array,      # f32 [B, cap] — one block's scores
                off, live,         # i32 scalars (TRACED)
                *, c: int, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact top-``k`` of the ``c`` columns of ``x`` from ``off``, those at
    or past ``live`` masked to -inf: values and columns of ``x``, ties to
    the lower column, as ``lax.top_k`` of the masked chunk gives them.
    Where :func:`topk_grouped` says so, in two stages:

    1. the maximum of each group of ``TOPK_GROUP`` CONTIGUOUS columns:
       the only pass over the chunk, a plain reduce with the mask fused
       into it, at the memory's rate;
    2. ``lax.top_k`` of the group maxima chooses ``k`` groups, ties to
       the lower group, and they are put in ascending order;
    3. only those groups' columns are gathered and ranked.

    Exact, ties included. Let element ``(v, i)`` lie in a group ``G``
    that stage 2 did not choose. Then ``k`` chosen groups ``j`` each
    order before ``G``: ``M_j > M_G``, or ``M_j = M_G`` and ``j < G``.
    Each holds an element of value ``M_j >= M_G >= v``; where ``M_j = v``
    it follows that ``M_j = M_G``, so ``j < G``, and because groups are
    contiguous every column of ``j`` is lower than ``i``: that element
    wins the tie. So ``k`` elements beat ``(v, i)`` and it is not in the
    top ``k``; the candidates lie in ascending column order, so the last
    ``lax.top_k`` breaks ties as one over the whole chunk would. (Strided
    groups would not carry this: a tie between two groups' maxima says
    nothing about the order of their columns. Scores that are exactly
    equal are the common case here: zeros, equal tf and length.)

    The chunk is a ``dynamic_slice``, NOT a ``[B, n, c]``
    reshape+transpose: that would materialize a second copy of the
    block, which at 1M docs and wide batches is the difference between
    fitting HBM and not. The last chunk's start is clamped to
    ``cap - c`` so every slice is full-width regardless of ``cap % c``;
    columns the clamp makes overlap the previous chunk (``< off``) are
    masked out so no doc can win twice in the merge. Likewise both the
    reduce and the gather read ``x`` through a view in ITS OWN tiling —
    ``[B/8, 8, cap/128, 128]`` is ``f32[B, cap]{T(8,128)}`` as it lies
    in HBM — so neither makes XLA relay a chunk: written
    ``reshape(B, c/128, 128).max(-1)`` the reduce costs two chunk-sized
    copies, and a gather of ``[1, 128]`` slices of the 2-D array expands
    to a ``while`` of ``B * k`` steps (PERF.md section 6, PR 31).
    """
    B, cap = x.shape
    g = TOPK_GROUP
    start = jnp.minimum(off, cap - c)

    def mask(scores, col):
        return jnp.where((col >= off) & (col < live), scores, -jnp.inf)

    masked = mask(jax.lax.dynamic_slice_in_dim(x, start, c, axis=1),
                  jnp.arange(c, dtype=jnp.int32)[None, :] + start)
    if not topk_grouped(cap, c, k):
        v, i = jax.lax.top_k(masked, k)
        return v, i.astype(jnp.int32) + start
    s = math.gcd(B, 8)      # rows a sublane tile
    gmax = masked.reshape(B // s, s, c // g, g).max(-1).reshape(B, c // g)
    _, grp = jax.lax.top_k(gmax, k)
    grp = jnp.sort(grp.astype(jnp.int32), axis=-1) + start // g    # [B, k]
    tiles = x.reshape(B // s, s, cap // g, g).transpose(0, 2, 1, 3)
    row = jax.lax.broadcasted_iota(jnp.int32, (B, k), 0)
    cand = jax.lax.gather(
        tiles, jnp.stack([row // s, grp, row % s], axis=-1),
        jax.lax.GatherDimensionNumbers(
            offset_dims=(2,), collapsed_slice_dims=(0, 1, 2),
            start_index_map=(0, 1, 2)),
        (1, 1, 1, g), mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    cand = mask(cand, grp[:, :, None] * g + jnp.arange(g, dtype=jnp.int32))
    v, p = jax.lax.top_k(cand.reshape(B, k * g), k)
    # a winner's column, through its group: a [B, k, k] select, no gather
    slot = (p // g)[:, :, None] == jnp.arange(k, dtype=jnp.int32)
    return v, jnp.sum(jnp.where(slot, grp[:, None, :], 0), -1) * g + p % g


TOPK_CHUNK = 1 << 17    # doc columns one step of the scan sees


def _chunk_starts(cap: int, chunk: int) -> tuple[int, list[int]]:
    """``(width, nominal chunk starts)`` of a ``cap``-column block. The
    device scan and :func:`topk_chunk_counts` share it, so the host's
    count of skipped chunks is the device's."""
    c = min(chunk, cap)
    return c, [j * c for j in range(-(-cap // c))]   # ceil: tail is clamped


def topk_chunk_counts(block_caps, block_live, chunk: int = TOPK_CHUNK,
                      *, k: int) -> tuple[int, int, int]:
    """``(chunks, skipped, grouped)`` of one :func:`packed_topk_chunked`
    call for the top ``k`` over blocks of ``block_caps`` columns holding
    ``block_live`` live ones — host integers only (what a commit already
    knows), no device read. A chunk is skipped when it starts at or past
    its block's live count; of the others, those wide enough for
    :func:`topk_grouped` go by group maxima and the rest straight through
    ``lax.top_k``."""
    total = skipped = grouped = 0
    for cap, live in zip(block_caps, block_live):
        c, starts = _chunk_starts(int(cap), chunk)
        dead = sum(off >= int(live) for off in starts)
        total += len(starts)
        skipped += dead
        if topk_grouped(int(cap), c, min(k, c)):
            grouped += len(starts) - dead
    return total, skipped, grouped


def _block_topk(x: jax.Array,      # f32 [B, cap] — one block's scores
                live: jax.Array,   # i32 scalar — its live columns (TRACED)
                *, k: int, chunk: int) -> tuple[jax.Array, jax.Array]:
    """Per-chunk winners of one block: ``[n, B, k]`` values and their
    columns IN THE BLOCK, columns at or past ``live`` masked to -inf."""
    B, cap = x.shape
    c, starts = _chunk_starts(cap, chunk)
    kc = min(k, c)

    def scan_chunk(off):
        return _chunk_topk(x, off, live, c=c, k=kc)

    def dead_chunk(off):
        # what scan_chunk yields when every column is masked: -inf at
        # the chunk's first kc columns
        first = jnp.arange(kc, dtype=jnp.int32) + jnp.minimum(off, cap - c)
        return (jnp.full((B, kc), -jnp.inf, x.dtype),
                jnp.broadcast_to(first[None, :], (B, kc)))

    def one_chunk(off):
        return jax.lax.cond(off < live, scan_chunk, dead_chunk, off)

    if len(starts) == 1:
        vals, ids = (a[None] for a in one_chunk(jnp.int32(0)))
    else:
        _, (vals, ids) = jax.lax.scan(
            lambda _, off: (None, one_chunk(off)), None,
            jnp.asarray(starts, jnp.int32))
    if kc < k:   # a block narrower than k: the pad lanes never win
        pad = ((0, 0), (0, 0), (0, k - kc))
        vals = jnp.pad(vals, pad, constant_values=-jnp.inf)
        ids = jnp.pad(ids, pad)
    return vals, ids


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def packed_topk_chunked(scores, num_docs: jax.Array,
                        base: jax.Array | None = None,
                        *, k: int, chunk: int = TOPK_CHUNK) -> jax.Array:
    """:func:`packed_topk` over score BLOCKS, read where the scorer wrote
    them, the doc axis scanned in chunks.

    ``scores`` is a tuple of ``[B, cap_i]`` blocks and ``num_docs`` their
    ``[n_blocks]`` live counts (traced): block ``i`` holds real rows
    ``row0_i .. row0_i + live_i`` (``row0`` the running sum of the live
    counts) in its first ``live_i`` columns. Its dead tail is masked to
    -inf and a winner's id is ``row0_i + column``, so no ``[B, doc_cap]``
    matrix in document order is ever built. One ``[B, n]`` array with a
    scalar ``num_docs`` is the one-block case. ``base`` (i32 scalar,
    traced; None: 0, and no parameter of the program) is the real row of
    the first block's first column where ``scores`` is a STRETCH of a
    longer block list: the live rows of the blocks before it
    (:func:`merge_packed` joins the stretches). Block-then-column order
    IS real-row order, a chunk's top-k (:func:`_chunk_topk`) breaks ties
    toward the lower column and :func:`merge_topk` toward the earlier
    chunk, so ties resolve to the lower document id whatever the
    blocking.

    A chunk wide enough is read ONCE, by a reduce to its group maxima,
    and only the ``k`` groups that can hold a winner are ranked
    (:func:`topk_grouped`); a narrow block goes straight through
    ``lax.top_k``, whose k-deep selection over every column costs ten
    times the read. Either way the chunks bound the temporaries at
    O(B * chunk / 128) — ``lax.top_k`` over a whole [B, doc_cap] row
    allocates value+index temporaries proportional to its input — and
    per-chunk winners merge exactly (the global top-k is contained in
    the union of chunk top-ks). A chunk that starts at or past its
    block's live count is SKIPPED (the padded space is up to 1.5x the
    live one; :func:`topk_chunk_counts`).
    """
    with jax.named_scope("topk_chunked"):
        blocks = scores if isinstance(scores, (tuple, list)) else (scores,)
        lives = jnp.reshape(num_docs, (-1,)).astype(jnp.int32)
        row0s = jnp.cumsum(lives) - lives
        if base is not None:
            row0s = row0s + base
        vals, ids = [], []
        for i, x in enumerate(blocks):
            v, local = _block_topk(x, lives[i], k=k, chunk=chunk)
            vals.append(v)
            ids.append(local + row0s[i])
        vals, ids = jnp.concatenate(vals), jnp.concatenate(ids)
        if vals.shape[0] > 1:       # [n_chunks, B, k], real-row order
            return pack_topk(*merge_topk(vals, ids))
        return pack_topk(vals[0], ids[0])


@jax.jit
def merge_packed(parts) -> jax.Array:
    """The packed top-k ``[B, 2k]`` of a step from those of its
    stretches (a tuple, in row order: each a
    :func:`packed_topk_chunked` with its ``base``). Exact as
    :func:`merge_topk` is, and a tie goes to the earlier stretch, whose
    documents are the lower ones."""
    stacked = jnp.stack(parts)                       # [n, B, 2k]
    k = stacked.shape[-1] // 2
    vals = jax.lax.bitcast_convert_type(stacked[..., :k], jnp.float32)
    return pack_topk(*merge_topk(vals, stacked[..., k:]))


def full_ranking(scores: jax.Array, num_docs: int) -> tuple[jax.Array, jax.Array]:
    """All live documents sorted by descending score — the parity-mode analog
    of the reference's unbounded result set (host-side use only)."""
    s = scores[..., :num_docs]
    order = jnp.argsort(-s, axis=-1, stable=True)
    return jnp.take_along_axis(s, order, axis=-1), order.astype(jnp.int32)
