"""Exact top-k and distributed top-k merge.

The reference returns *all* hits per worker (``Worker.java:230``:
``searcher.search(query, Integer.MAX_VALUE)``) and the leader sum-merges by
document name (``Leader.java:73-77``). On TPU we keep k static: each shard
produces an exact local top-k, shards are combined by concatenation +
re-top-k (associative, so it composes under ``all_gather``), and a
``full_ranking`` path covers the reference's unbounded-result behavior for
parity testing.

The serving top-k (:func:`packed_topk_chunked`) reads the scorer's blocks
where they lie, in windows, and ranks a window by its candidates: one
reduce to the maxima of groups of 128 contiguous columns, then only the
``k`` groups that can hold a winner, and at a depth past 128 the same step
again inside them, on sub-groups of 8 (:func:`_chunk_topk`, with the proof
that it is ``lax.top_k``'s own answer, ties included;
:func:`topk_widths`, the route a shape and a depth take).
A mesh shard ranks its buckets and its delta the same way
(:func:`blocks_topk`, the part of it that both share).
:func:`exact_topk`, the unchunked form (the COO mesh's shards, parity
tests), is ``lax.top_k`` itself.
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
TOPK_SUBGROUP = 8       # columns a sub-group: the second level's width
TOPK_SORT_WIDTH = 1 << 14   # the widest row a deep selection sorts


def topk_widths(cap: int, c: int, k: int) -> tuple[int, ...]:
    """The group widths, level by level, by which the top-``k`` of a
    ``c``-column window of a ``cap``-column array is selected
    (:func:`_chunk_topk`); ``()``: straight through ``lax.top_k``.

    * ``(TOPK_GROUP,)`` where the window holds at least eight times
      ``k`` groups, so that the ``k`` groups it ranks are well under it,
      and groups are whole lane tiles of the array;
    * ``(TOPK_GROUP, TOPK_SUBGROUP)`` where those ``k`` groups' columns
      are more than ``TOPK_SORT_WIDTH`` (a ``k`` past 128): the same
      step again inside them, sixteen sub-groups a group, of which
      ``k`` (in whole lane tiles) are ranked;
    * ``(TOPK_SUBGROUP,)`` where the window's groups do not outnumber
      the depth but its sub-groups do, and the window is wider than
      ``TOPK_SORT_WIDTH`` (a narrower one is one small sort: straight).

    Every row a deep selection sorts is thereby ``TOPK_SORT_WIDTH`` wide
    or less, or sixteen times ``k`` in whole lane tiles if that is more.
    Static shapes and ``k`` alone: the device path and
    :func:`topk_chunk_counts` share it, so the host's count is the
    device's."""
    g, w = TOPK_GROUP, TOPK_SUBGROUP
    if cap % g or c % g:
        return ()
    if c // g >= 8 * k:
        return (g, w) if k * g > TOPK_SORT_WIDTH else (g,)
    if c > TOPK_SORT_WIDTH and c // w >= 8 * k:
        return (w,)
    return ()


def topk_grouped(cap: int, c: int, k: int) -> bool:
    """Whether the top-``k`` of a ``c``-column window of a ``cap``-column
    array goes by group maxima, at one level or two
    (:func:`topk_widths`), and not straight through ``lax.top_k``."""
    return bool(topk_widths(cap, c, k))


def _top_by(vals: jax.Array, ids: jax.Array,
            k: int) -> tuple[jax.Array, jax.Array]:
    """The ``k`` largest of each row of ``vals`` with their ``ids``
    (distinct within a row): by value descending, then by id ascending
    -- ``lax.top_k``'s order wherever ids ascend with position, in
    whatever order the operands lie. The ids ride the sort as its SECOND
    KEY: a stable sort on the value alone carries the positions for a
    third operand and takes twice the time (11.0 against 6.0 ms for
    ``[512, 16000]`` on the v5e), and a gather by ``lax.top_k``'s
    positions takes as long again as the sort (PERF.md section 6,
    PR 39). Nor does the deep selection lean on ``lax.top_k`` for a tie:
    where the v5e's compiler splits a wide row it returns equal values
    in either order of their columns (same section)."""
    nv, ni = jax.lax.sort((-vals, ids), dimension=1, num_keys=2,
                          is_stable=False)
    return -nv[:, :k], ni[:, :k]


def _chunk_topk(x: jax.Array,      # f32 [B, cap] — one block's scores
                off, live,         # i32 scalars (TRACED)
                *, c: int, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact top-``k`` of the ``c`` columns of ``x`` from ``off``, those at
    or past ``live`` masked to -inf: values and columns of ``x``, ties to
    the lower column, as ``lax.top_k`` of the masked chunk gives them.
    Where :func:`topk_widths` says so, by group maxima:

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

    A deep ``k`` takes the step TWICE (:func:`_subgroup_topk`): 128
    columns a chosen group are 128,000 a query at ``k`` = 1,000, a sort
    that costs what the chunk's own did. The candidates are cut into
    sub-groups of ``TOPK_SUBGROUP`` contiguous columns, and the proof
    above holds of them word for word with "the candidates" for "the
    chunk": an element of a sub-group not chosen is beaten by one
    element of each of ``k`` that were, on value or on the lower column.
    What is ranked at the end is ``8 * k`` columns. A window whose
    groups do not outnumber ``k`` but whose sub-groups do enters at the
    second level, with every one of its groups for a candidate. At
    depth the groups, the sub-groups and the columns are ranked by
    :func:`_top_by`, value then NAME, so their order in memory is free
    and no tie hangs on ``lax.top_k``; and each level keeps ``k``
    rounded up to whole lane tiles (:func:`_whole_tiles`: keeping more
    is as exact, the proof wants at least ``k``).

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
    widths = topk_widths(cap, c, k)
    if not widths:
        v, i = jax.lax.top_k(masked, k)
        return v, i.astype(jnp.int32) + start
    s = math.gcd(B, 8)      # rows a sublane tile

    def group_columns(grp):
        """The columns of groups ``grp`` ``[B, n]`` of ``x``, masked:
        ``[B, n, g]``, one lane tile a group."""
        tiles = x.reshape(B // s, s, cap // g, g).transpose(0, 2, 1, 3)
        row = jax.lax.broadcasted_iota(jnp.int32, grp.shape, 0)
        cand = jax.lax.gather(
            tiles, jnp.stack([row // s, grp, row % s], axis=-1),
            jax.lax.GatherDimensionNumbers(
                offset_dims=(2,), collapsed_slice_dims=(0, 1, 2),
                start_index_map=(0, 1, 2)),
            (1, 1, 1, g), mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        return mask(cand,
                    grp[:, :, None] * g + jnp.arange(g, dtype=jnp.int32))

    def every_group():      # the window's groups by name: [B, c / g]
        return jnp.broadcast_to(
            jnp.arange(c // g, dtype=jnp.int32) + start // g, (B, c // g))

    if widths[0] != g:      # every group of the window is a candidate
        return _subgroup_topk(masked.reshape(B, c // g, g), every_group(),
                              group_columns, k)
    gmax = masked.reshape(B // s, s, c // g, g).max(-1).reshape(B, c // g)
    if len(widths) > 1:
        _, grp = _top_by(gmax, every_group(), _whole_tiles(k))
        return _subgroup_topk(group_columns(grp), grp, group_columns, k)
    _, grp = jax.lax.top_k(gmax, k)
    grp = jnp.sort(grp.astype(jnp.int32), axis=-1) + start // g    # [B, k]
    v, p = jax.lax.top_k(group_columns(grp).reshape(B, k * g), k)
    # a winner's column, through its group: a [B, k, k] select, no gather
    slot = (p // g)[:, :, None] == jnp.arange(k, dtype=jnp.int32)
    return v, jnp.sum(jnp.where(slot, grp[:, None, :], 0), -1) * g + p % g


def _whole_tiles(k: int) -> int:
    """``k`` rounded up to whole lane tiles: how many groups, and how
    many sub-groups, a deep selection keeps for a depth of ``k`` (more
    than ``k`` is as exact). ``[B, n, 128]`` candidates reduce to their
    ``[B, n, 16]`` sub-group maxima in one fusion behind one transposing
    copy where ``n`` is whole tiles; at ``n`` = 1,000 the v5e's compiler
    re-tiles them first, at four times the copy's cost (36.8 against
    29.8 ms a 2^20-column block: PERF.md section 6, PR 39)."""
    return -(-k // TOPK_GROUP) * TOPK_GROUP


def _subgroup_topk(cand: jax.Array,    # f32 [B, n, g], masked
                   grp: jax.Array,     # i32 [B, n], their groups of x
                   group_columns, k: int) -> tuple[jax.Array, jax.Array]:
    """The second level of :func:`_chunk_topk`: the top ``k`` of the
    columns ``cand`` of groups ``grp``, with their columns in the block.
    Maxima of the ``n * 16`` sub-groups, the ``k`` of them (in whole
    tiles) that can hold a winner, their lane tiles fetched again
    (``group_columns``) and all but the sub-group's eight lanes dropped
    by a masked maximum over the tile's sixteen sub-groups; sub-groups
    and columns ride the sorts by name (:func:`_top_by`)."""
    B, n, g = cand.shape
    w = TOPK_SUBGROUP
    m = g // w
    kk = _whole_tiles(k)
    each = jnp.arange(m, dtype=jnp.int32)
    _, sub = _top_by(cand.reshape(B, n, m, w).max(-1).reshape(B, n * m),
                     (grp[:, :, None] * m + each).reshape(B, n * m), kk)
    lane = jnp.arange(g, dtype=jnp.int32)       # sub: [B, kk] sub-groups of x
    fine = jnp.where(lane // w == (sub % m)[:, :, None],
                     group_columns(sub // m), -jnp.inf)
    fine = fine.reshape(B, kk, m, w).max(2)     # [B, kk, w]
    col = sub[:, :, None] * w + jnp.arange(w, dtype=jnp.int32)
    return _top_by(fine.reshape(B, kk * w), col.reshape(B, kk * w), k)


TOPK_CHUNK = 1 << 17    # doc columns one step of the scan sees


def _chunk_starts(cap: int, chunk: int, k: int) -> tuple[int, list[int]]:
    """``(width, nominal window starts)`` of a ``cap``-column block for
    the top ``k``. A window is a chunk, except where a chunk's groups do
    not outnumber the depth and a wider window's do: then it is the
    block, up to ``TOPK_GROUP * TOPK_SORT_WIDTH`` columns (the chunks
    bound ``lax.top_k``'s temporaries; a reduce to ``[B, c / 128]``
    maxima needs no such bound). The device scan and
    :func:`topk_chunk_counts` share it, so the host's count of skipped
    windows is the device's."""
    c = min(chunk, cap)
    wide = min(cap, TOPK_GROUP * TOPK_SORT_WIDTH)
    if (wide > c and TOPK_GROUP not in topk_widths(cap, c, min(k, c))
            and TOPK_GROUP in topk_widths(cap, wide, min(k, wide))):
        c = wide
    return c, [j * c for j in range(-(-cap // c))]   # ceil: tail is clamped


def topk_chunk_counts(block_caps, block_live, chunk: int = TOPK_CHUNK,
                      *, k: int) -> tuple[int, int, int]:
    """``(chunks, skipped, grouped)`` of one :func:`packed_topk_chunked`
    call for the top ``k`` over blocks of ``block_caps`` columns holding
    ``block_live`` live ones — host integers only (what a commit already
    knows), no device read. What is counted is a WINDOW of
    :func:`_chunk_starts`: a chunk of ``chunk`` columns, or at a depth
    past 128 a whole block of up to 2,097,152 (one count, however many
    chunks it spans). A window is skipped when it starts at or past its
    block's live count; of the others, those :func:`topk_grouped` go by
    group maxima, at one level or two, and the rest straight through
    ``lax.top_k``."""
    total = skipped = grouped = 0
    for cap, live in zip(block_caps, block_live):
        c, starts = _chunk_starts(int(cap), chunk, k)
        dead = sum(off >= int(live) for off in starts)
        total += len(starts)
        skipped += dead
        if topk_grouped(int(cap), c, min(k, c)):
            grouped += len(starts) - dead
    return total, skipped, grouped


def _block_topk(x: jax.Array,      # f32 [B, cap] — one block's scores
                live: jax.Array,   # i32 scalar — its live columns (TRACED)
                *, k: int, chunk: int) -> tuple[jax.Array, jax.Array]:
    """Per-window winners of one block: ``[n, B, k]`` values and their
    columns IN THE BLOCK, columns at or past ``live`` masked to -inf."""
    B, cap = x.shape
    c, starts = _chunk_starts(cap, chunk, k)
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


def blocks_topk(blocks, lives: jax.Array, row0s: jax.Array,
                *, k: int, chunk: int = TOPK_CHUNK
                ) -> tuple[jax.Array, jax.Array]:
    """The exact top ``k`` ``[B, k]`` (values, ids) over score blocks
    read where they lie: block ``i`` ``[B, cap_i]`` holds ids ``row0s[i]
    .. row0s[i] + lives[i]`` in its first ``lives[i]`` columns (both i32
    ``[n_blocks]``, TRACED), the rest of it dead. Blocks in ascending id
    order: a window breaks ties toward its lower column and
    :func:`merge_topk` toward the earlier window, so a tie goes to the
    lower id. What :func:`packed_topk_chunked` packs, and what a mesh
    shard ranks its buckets and its delta by
    (``parallel.mesh_ell.make_mesh_ell_search``)."""
    vals, ids = [], []
    for i, x in enumerate(blocks):
        v, local = _block_topk(x, lives[i], k=k, chunk=chunk)
        vals.append(v)
        ids.append(local + row0s[i])
    vals, ids = jnp.concatenate(vals), jnp.concatenate(ids)
    if vals.shape[0] > 1:       # [n_chunks, B, k], ascending id order
        return merge_topk(vals, ids)
    return vals[0], ids[0]


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def packed_topk_chunked(scores, num_docs: jax.Array,
                        base: jax.Array | None = None,
                        *, k: int, chunk: int = TOPK_CHUNK) -> jax.Array:
    """:func:`packed_topk` over score BLOCKS, read where the scorer wrote
    them, the doc axis scanned in windows.

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
    IS real-row order, a window's top-k (:func:`_chunk_topk`) breaks ties
    toward the lower column and :func:`merge_topk` toward the earlier
    window, so ties resolve to the lower document id whatever the
    blocking.

    A window wide enough is read ONCE, by a reduce to its group maxima,
    and only the ``k`` groups that can hold a winner are ranked, at a
    depth past 128 by their sub-groups' maxima first
    (:func:`topk_widths`): what is sorted follows the candidates, tens
    of ``k`` a query, and not the columns. A narrow block goes straight
    through ``lax.top_k``, as does a depth past 2,048, whose k-deep
    selection over every column costs ten times the read at ``k`` = 10
    and three hundred times at 1,000. A window is a chunk of ``chunk``
    columns — the chunks bound ``lax.top_k``'s temporaries, which are
    proportional to its input, and the group maxima's at O(B * chunk /
    128) — or, where only a wider one's groups outnumber the depth, the
    block (:func:`_chunk_starts`): the reduce's output is ``[B, cap /
    128]``, and the candidates' ``[B, k, 128]`` follows ``k``, 262 MB at
    B = 512 and ``k`` = 1,000, held twice. Per-window winners merge
    exactly (the global top-k is contained in the union of the windows'
    top-ks). A window that starts at or past its block's live count is
    SKIPPED (the padded space is up to 1.5x the live one;
    :func:`topk_chunk_counts`).
    """
    with jax.named_scope("topk_chunked"):
        blocks = scores if isinstance(scores, (tuple, list)) else (scores,)
        lives = jnp.reshape(num_docs, (-1,)).astype(jnp.int32)
        row0s = jnp.cumsum(lives) - lives
        if base is not None:
            row0s = row0s + base
        return pack_topk(*blocks_topk(blocks, lives, row0s, k=k,
                                      chunk=chunk))


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
