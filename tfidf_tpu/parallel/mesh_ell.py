"""Blocked-ELL base layout for the mesh — the distributed fast path.

The COO ``shard_map`` step (:mod:`tfidf_tpu.parallel.sharded`) scores via
chunked ``segment_sum`` — a scatter, measured ~5x slower than the
single-device blocked-ELL path at equal scale. This module gives the mesh
the same layout the single-device engine uses (``ops/ell.py``), organized
for SPMD:

* per docs-shard, live documents are laid out as blocked ELL in width
  buckets taken from the ONE ladder, ``ops.ell.ELL_WIDTH_LADDER``
  (:func:`mesh_ell_widths`): always the ten rungs to 256, above them
  every rung up to the one that holds the widest row of ANY shard
  (whole documents fill 384 and 512), under ``width_cap``. Every shard
  has the same buckets, their row capacities padded to the max across
  shards — every device slice has identical static shapes, as
  ``shard_map`` requires — and only a row past the ladder's top or the
  cap spills into the COO residual;
* a bucket is held width-major, ``[D, width, rows_cap]`` under
  ``P("docs", "terms", None)``: a shard's slice is the ``[width,
  rows_cap]`` block the kernel reads (``ops/ell.py EllBlock``), and
  nothing in the step turns it;
* the ``terms`` axis shards each block's WIDTH axis: one document row
  keeps its entries split across terms-devices, partial scores
  ``psum``-reduce exactly like the COO path (entries are disjoint across
  slices; scores and df are additive);
* per-entry IMPACTS are (re)computed at every commit from the
  then-current global statistics (df summed over live host postings, N,
  avgdl) — appends between re-shards land in the COO *delta*
  (:class:`~tfidf_tpu.parallel.sharded.ShardedArrays`) and the next
  commit refreshes base impacts, so IDF never goes stale (the same
  current-stats contract as streaming segments / Lucene
  collectionStatistics);
* scoring uses the same compare/MXU Pallas kernel as the single-device
  path (``score_block_pallas``) inside ``shard_map`` — per-device
  kernels compose with collectives.

The ELL row order per shard is width-sorted, i.e. a PERMUTATION of the
shard's insertion-local ids; ``perm[s]`` maps ELL row -> insertion-local
id so the searcher can translate top-k ids back to names.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tfidf_tpu.ops.csr import next_capacity
from tfidf_tpu.ops.ell import (ELL_WIDTH_LADDER, _pallas_eligible,
                               _score_block, ell_layout_gauges,
                               fill_width_major, score_block_pallas)
from tfidf_tpu.ops.scoring import (QueryBatch, _compile_queries,
                                   bm25_weights, score_coo_compiled,
                                   tfidf_weights)
from tfidf_tpu.ops.topk import blocks_topk, merge_topk, pack_topk
from tfidf_tpu.utils.metrics import global_metrics

# the rungs EVERY mesh index has, whatever its documents: a corpus of
# passages commits the same ten buckets (and so runs the same compiled
# step) whether or not one of them reaches the 192 or the 256 rung
MESH_ELL_BASE_WIDTH = 256


def mesh_ell_widths(widest_row: int = 0,
                    width_cap: int | None = None) -> tuple[int, ...]:
    """The mesh's bucket widths, widest first, for a corpus whose
    longest row holds ``widest_row`` distinct terms: the rungs of
    ``ops.ell.ELL_WIDTH_LADDER`` that are multiples of 8 (so the terms
    axis, up to 8-way, shards the width columns evenly) — all of them
    to ``MESH_ELL_BASE_WIDTH``, and above it up to the rung that holds
    ``widest_row`` — under ``width_cap`` (None: the ladder's top)."""
    rungs = [w for w in ELL_WIDTH_LADDER
             if w % 8 == 0 and (width_cap is None or w <= width_cap)]
    top = next((w for w in rungs if w >= widest_row), rungs[-1])
    return tuple(w for w in reversed(rungs)
                 if w <= max(top, MESH_ELL_BASE_WIDTH))


@dataclass
class MeshEllArrays:
    """Device-resident ELL base for the whole mesh.

    Per width bucket b: ``tf[b] [D, W_b, rows_cap_b]`` etc., sharded
    ``P("docs", "terms", None)``. ``doc_cap`` is the per-shard ELL doc
    space (block rows concatenated); ``live`` masks tombstones in that
    space.
    """

    tf: tuple            # per bucket f32 [D, W_b, rows_cap_b]
    term: tuple          # per bucket i32 [D, W_b, rows_cap_b]
    impact: tuple        # per bucket f32 [D, W_b, rows_cap_b]
    dl: tuple            # per bucket f32 [D, rows_cap_b]
    block_live: jax.Array  # i32 [D, n_buckets] live rows per block
    live: jax.Array      # f32 [D, doc_cap] in ELL row space
    # residual COO (over-wide docs), split over terms like the delta
    res_tf: jax.Array    # f32 [D, T, res_cap]
    res_term: jax.Array  # i32 [D, T, res_cap]
    res_doc: jax.Array   # i32 [D, T, res_cap] (ELL row ids)
    res_dl: jax.Array    # f32 [D, doc_cap] (model-transformed lengths)
    doc_cap: int

    @property
    def n_buckets(self) -> int:
        return len(self.tf)


jax.tree_util.register_dataclass(
    MeshEllArrays,
    data_fields=["tf", "term", "impact", "dl", "block_live", "live",
                 "res_tf", "res_term", "res_doc", "res_dl"],
    meta_fields=["doc_cap"],
)


class MeshEllHost(NamedTuple):
    """:class:`MeshEllArrays` as :func:`build_mesh_ell` leaves it on the
    host: numpy arrays of the same names and shapes, ``impact`` left out
    (zeros of ``tf``'s shapes)."""

    tf: list
    term: list
    dl: list
    block_live: np.ndarray
    live: np.ndarray
    res_tf: np.ndarray
    res_term: np.ndarray
    res_doc: np.ndarray
    res_dl: np.ndarray
    doc_cap: int
    res_nnz: int         # live residual entries, all shards


def build_mesh_ell(entries_per_shard: list[list],   # list[DocEntry]/shard
                   mesh: Mesh,
                   transform_len,                   # model.transform_doc_len
                   *,
                   width_cap: int | None = None,
                   min_rows: int = 256,
                   min_res_cap: int = 1 << 10
                   ) -> tuple[MeshEllHost, list[np.ndarray]]:
    """Host-side build: per-shard blocked ELL with uniform buckets.

    Returns ``(host, perm)``: ``host`` for :func:`place_mesh_ell` to put
    on the mesh — two steps, so that a commit times the host's loops
    and the upload apart; ``perm[s][ell_row] = insertion-local id`` in
    shard s (for name lookup). Impacts are left zero — call
    :func:`make_impact_refresh` after placing the arrays.
    """
    D = mesh.shape["docs"]
    T = mesh.shape["terms"]
    # one structure for every shard: the widest row of ANY of them
    widths = mesh_ell_widths(
        max((e.term_ids.shape[0] for entries in entries_per_shard
             for e in entries), default=0), width_cap)
    assert T <= min(widths), "terms axis cannot exceed the narrowest bucket"

    # per shard: rows sorted by distinct-term count, descending, so a
    # bucket is one run of them; only the widest bucket can spill
    asc = np.asarray(widths[::-1], np.int64)
    nb = len(widths)
    per_shard = []
    rows_need = np.zeros((D, nb), np.int64)
    res_need = np.zeros(D, np.int64)
    for s, entries in enumerate(entries_per_shard):
        sizes = np.fromiter((e.term_ids.shape[0] for e in entries),
                            np.int64, len(entries))
        order = np.argsort(-sizes, kind="stable")
        sizes = sizes[order]
        bucket = nb - 1 - np.minimum(np.searchsorted(asc, sizes), nb - 1)
        per_shard.append((order, sizes))
        rows_need[s] = np.bincount(bucket, minlength=nb)
        res_need[s] = np.maximum(sizes - widths[0], 0).sum()
    doc_cap = next_capacity(
        max(max((len(e) for e in entries_per_shard), default=1), 1),
        min_rows)
    rows_cap = [next_capacity(int(rows_need[:, b].max()) or 1, min_rows)
                for b in range(nb)]
    res_cap = next_capacity(int(res_need.max()) or 1, min_res_cap)
    res_chunk = -(-res_cap // T)

    g_tf = [np.zeros((D, widths[b], rows_cap[b]), np.float32)
            for b in range(nb)]
    g_term = [np.zeros((D, widths[b], rows_cap[b]), np.int32)
              for b in range(nb)]
    g_dl = [np.zeros((D, rows_cap[b]), np.float32) for b in range(nb)]
    g_bl = rows_need.astype(np.int32)
    g_live = np.zeros((D, doc_cap), np.float32)
    g_res_tf = np.zeros((D, T, res_chunk), np.float32)
    g_res_term = np.zeros((D, T, res_chunk), np.int32)
    g_res_doc = np.full((D, T, res_chunk), doc_cap - 1, np.int32)
    g_res_dl = np.zeros((D, doc_cap), np.float32)
    perms = [order for order, _sizes in per_shard]
    res_rows = []        # the residual's rows, shard after shard
    for s, entries in enumerate(entries_per_shard):
        order, sizes = per_shard[s]
        n = len(entries)
        if not n:
            continue
        raw = np.fromiter((e.length for e in entries), np.float32, n)
        kdl = transform_len(raw[order]).astype(np.float32)
        g_live[s, :n] = 1.0
        g_res_dl[s, :n] = kdl
        # the shard's postings in ELL row order: a row's entries are
        # the first of its block column (its pads trail them down the
        # width), so a block's live cells, taken row by row, are its
        # run of postings in their own order
        tfs = np.concatenate([entries[i].tfs for i in order])
        terms = np.concatenate([entries[i].term_ids for i in order])
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        row0 = np.cumsum(rows_need[s]) - rows_need[s]
        res = (tfs[:0], terms[:0], np.zeros(0, np.int32))
        for b in range(nb):
            lo, hi = int(row0[b]), int(row0[b] + rows_need[s, b])
            if hi == lo:
                continue
            run = slice(int(bounds[lo]), int(bounds[hi]))
            size = sizes[lo:hi]
            run_tfs, run_terms = tfs[run], terms[run]
            if size[0] > widths[b]:
                # only the widest bucket can spill: what lies past its
                # width is the residual, in row order
                first = np.repeat(bounds[lo:hi] - bounds[lo], size)
                keep = np.arange(first.shape[0]) - first < widths[b]
                rows = np.repeat(np.arange(lo, hi, dtype=np.int32), size)
                res = (run_tfs[~keep], run_terms[~keep], rows[~keep])
                run_tfs, run_terms = run_tfs[keep], run_terms[keep]
            kept = np.minimum(size, widths[b])
            fill_width_major(g_tf[b][s], run_tfs, kept)
            fill_width_major(g_term[b][s], run_terms, kept)
            g_dl[b][s, :hi - lo] = kdl[lo:hi]
        # the residual cut in T even runs
        step = -(-res[2].shape[0] // T)
        for t in range(T):
            for g, a in zip((g_res_tf, g_res_term, g_res_doc), res):
                part = a[t * step:(t + 1) * step]
                g[s, t, :part.shape[0]] = part
        res_rows.append(res[2].astype(np.int64) + s * doc_cap)
    # the ``ell_*`` gauges of the local commit, summed over the shards
    # (``ell_width_max``: the widest bucket)
    gauges = ell_layout_gauges(
        list(zip(widths, rows_cap)) * D, rows_need.ravel(),
        np.concatenate(res_rows) if res_rows else np.zeros(0, np.int64))
    for name, value in gauges.items():
        global_metrics.set_gauge(name, value)

    # device-residency accounting (ISSUE 18): the mesh base is always
    # fully resident (no cold tier on the mesh path), so publish its
    # HBM footprint on the same gauge family the tiered single-device
    # engine reports under — capacity dashboards read one bytes number
    # per node regardless of layout. tf counts twice: the impact plane
    # is a same-shape f32 copy.
    dev_bytes = (sum(a.nbytes for a in g_tf) * 2
                 + sum(a.nbytes for a in g_term)
                 + sum(a.nbytes for a in g_dl)
                 + g_bl.nbytes + g_live.nbytes + g_res_tf.nbytes
                 + g_res_term.nbytes + g_res_doc.nbytes
                 + g_res_dl.nbytes)
    global_metrics.set_gauge("mesh_ell_device_bytes", float(dev_bytes))
    return MeshEllHost(tf=g_tf, term=g_term, dl=g_dl, block_live=g_bl,
                       live=g_live, res_tf=g_res_tf, res_term=g_res_term,
                       res_doc=g_res_doc, res_dl=g_res_dl,
                       doc_cap=doc_cap,
                       res_nnz=gauges["ell_residual_nnz"]), perms


def place_mesh_ell(host: MeshEllHost, mesh: Mesh) -> MeshEllArrays:
    """:func:`build_mesh_ell`'s arrays onto the mesh (``jax.device_put``
    returns before the copy ends; a caller that times the upload waits
    for the arrays)."""

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    # the width axis shards over "terms": entries of one row split across
    # terms-devices; contributions are additive, like the COO split
    return MeshEllArrays(
        tf=tuple(put(a, P("docs", "terms", None)) for a in host.tf),
        term=tuple(put(a, P("docs", "terms", None)) for a in host.term),
        impact=tuple(put(np.zeros_like(a), P("docs", "terms", None))
                     for a in host.tf),
        dl=tuple(put(a, P("docs", None)) for a in host.dl),
        block_live=put(host.block_live, P("docs", None)),
        live=put(host.live, P("docs", None)),
        res_tf=put(host.res_tf, P("docs", "terms", None)),
        res_term=put(host.res_term, P("docs", "terms", None)),
        res_doc=put(host.res_doc, P("docs", "terms", None)),
        res_dl=put(host.res_dl, P("docs", None)),
        doc_cap=host.doc_cap,
    )


def make_impact_refresh(mesh: Mesh, *, model: str = "bm25",
                        k1: float = 1.2, b: float = 0.75):
    """Commit-time impact recompute from CURRENT global stats.

    ``refresh(arrays, df_g [vocab], n, avgdl) -> MeshEllArrays`` — df_g
    is replicated; each slice re-derives its impacts from its raw tf, so
    appends (which move df/N/avgdl) never leave stale IDF in the base.
    """

    def step(df_g, n_docs, avgdl, *flat):
        k = len(flat) // 3
        tfs, terms, dls = flat[:k], flat[k:2 * k], flat[2 * k:]
        out = []
        for tf, term, dl in zip(tfs, terms, dls):
            tf = tf.reshape(tf.shape[1:])            # [Wt, rows]
            term = term.reshape(term.shape[1:])
            dl = dl.reshape(dl.shape[-1])            # [rows]
            df_t = df_g[term]
            if model == "bm25":
                imp = bm25_weights(tf, df_t, dl[None, :], n_docs, avgdl,
                                   k1=k1, b=b)
            elif model == "tfidf":
                imp = tfidf_weights(tf, df_t, n_docs)
            else:
                raise ValueError(f"mesh ELL does not support {model!r}")
            out.append(imp[None])
        return tuple(out)

    def n_in(k):
        return ((P(None),) + (P(),) * 2
                + (P("docs", "terms", None),) * k * 2
                + (P("docs", None),) * k)

    def refresh(arrays: MeshEllArrays, df_g, n_docs, avgdl):
        import dataclasses
        k = arrays.n_buckets
        sharded = jax.shard_map(
            step, mesh=mesh, in_specs=n_in(k),
            out_specs=(P("docs", "terms", None),) * k,
            check_vma=False)
        impacts = sharded(df_g, n_docs, avgdl,
                          *arrays.tf, *arrays.term, *arrays.dl)
        return dataclasses.replace(arrays, impact=tuple(impacts))

    return jax.jit(refresh)


def make_mesh_ell_search(mesh: Mesh,
                         delta_chunk: int = 1 << 17,
                         *,
                         k: int,
                         model: str = "bm25",
                         k1: float = 1.2,
                         b: float = 0.75,
                         use_pallas: bool = True,
                         packed: bool = False,
                         depth: int | None = None):
    """Distributed search over ELL base + COO delta: ONE ``shard_map``
    program a batch. A docs-shard scores each width bucket into its own
    ``[B, rows_cap]`` block (the kernel, as on one chip) and its top-k
    READS THE BLOCKS WHERE THEY LIE (``ops.topk.blocks_topk``, what the
    one-chip step's ``packed_topk_chunked`` runs): a shard's rows are
    sorted by width, so block-then-column order is ELL-row order; the
    residual (rows of the widest bucket only) adds to block 0, the
    ``"terms"`` psum runs over the blocks, the tombstone mask reaches a
    block by a slice at its first row, and the delta is one more block
    behind them. No ``[B, doc_cap]`` matrix in row order is built and
    nothing is gathered. Then ``all_gather`` + ``merge_topk`` over the
    docs axis: ties go to the lower ELL row within a shard and to the
    lower shard across them.

    Returned callable:
        search(base: MeshEllArrays, delta: ShardedArrays, df_g, n, avgdl,
               q: QueryBatch) -> (top_vals [B,k], gids [B,k])

    ``gids`` encode shard * (doc_cap_ell + doc_cap_delta) + local, where
    local < doc_cap_ell is an ELL row and local >= doc_cap_ell is a
    delta slot. Global stats arrive precomputed (the engine refreshes
    them at commit), so the step needs no df psum. ``k`` is a SHARD's
    depth; ``depth`` (None: ``k``) the merged reply's, for a request
    deeper than one shard is wide.

    ``packed=True`` returns ONE i32 ``[B, 2k]`` array (values bitcast) so
    the caller fetches values and ids in a single device->host
    transfer: at k=10 the fixed cost of a second fetch dwarfs the
    payload.
    """

    def step(df_g, n_docs, avgdl, base_live, block_live,
             res_tf, res_term, res_doc, res_dl,
             d_tf, d_term, d_doc, d_len, d_n, d_live,
             q_uniq, q_n_uniq, q_slots, q_weights, *blocks):
        q = QueryBatch(q_uniq, q_n_uniq, q_slots, q_weights)
        nb = len(blocks) // 2
        impacts = [x.reshape(x.shape[1:]) for x in blocks[:nb]]
        terms = [x.reshape(x.shape[1:]) for x in blocks[nb:]]
        base_live = base_live.reshape(base_live.shape[-1])
        block_live = block_live.reshape(block_live.shape[-1])
        res_tf = res_tf.reshape(res_tf.shape[-1])
        res_term = res_term.reshape(res_term.shape[-1])
        res_doc = res_doc.reshape(res_doc.shape[-1])
        res_dl = res_dl.reshape(res_dl.shape[-1])
        d_tf = d_tf.reshape(d_tf.shape[-1])
        d_term = d_term.reshape(d_term.shape[-1])
        d_doc = d_doc.reshape(d_doc.shape[-1])
        d_len = d_len.reshape(d_len.shape[-1])
        d_n = d_n.reshape(())
        d_live = d_live.reshape(d_live.shape[-1])

        B = q.slots.shape[0]
        vocab_cap = df_g.shape[0]
        doc_cap_ell = base_live.shape[0]
        doc_cap_delta = d_live.shape[0]
        slot_of, qc_ext = _compile_queries(q, vocab_cap)
        qc_t = qc_ext.T
        u_cap = q.uniq.shape[0]

        # the scopes name the step's parts in the compiled HLO's
        # ``op_name`` (a device trace's events carry the HLO text only)
        # --- ELL base: same per-block scorers as single-device ---
        parts = []
        with jax.named_scope("ell_blocks"):
            for i, (imp, term) in enumerate(zip(impacts, terms)):
                if use_pallas and _pallas_eligible(imp.shape[1], B, u_cap):
                    parts.append(score_block_pallas(
                        imp, term, q.uniq, q.n_uniq, qc_ext,
                        block_live[i]))
                else:
                    parts.append(_score_block(imp, term, slot_of, qc_t,
                                              2048))
        # The scores STAY where the scorers wrote them, a block a
        # bucket. Rows are sorted by width, descending, so block i's
        # first block_live[i] columns are the ELL rows from row0s[i]
        # on: what lives in row space reaches a block by a slice there,
        # and no [B, doc_cap] matrix in row order is built.
        caps = [p.shape[1] for p in parts]
        row0s = jnp.cumsum(block_live) - block_live
        with jax.named_scope("coo_residual"):
            # only the widest bucket spills (build_mesh_ell), and its
            # columns ARE ELL rows 0 ..: the residual is scored in that
            # block's row space (a pad entry's row, doc_cap - 1, falls
            # outside it and is dropped by the segment sum)
            assert caps[0] <= doc_cap_ell, (caps[0], doc_cap_ell)
            parts[0] = parts[0] + score_coo_compiled(
                res_tf, res_term, res_doc, res_dl[:caps[0]], df_g,
                slot_of, qc_ext, n_docs, avgdl, None, model=model, k1=k1,
                b=b, chunk=min(1 << 10, res_tf.shape[0]))
        parts = jax.lax.psum(tuple(parts), "terms")
        with jax.named_scope("live_mask"):
            # tombstones score 0: the mask padded by the widest
            # capacity, so that no block's slice is clamped
            live_rows = jnp.pad(base_live, (0, max(caps)))
            parts = [p * jax.lax.dynamic_slice_in_dim(
                live_rows, row0s[i], cap)[None, :]
                for i, (p, cap) in enumerate(zip(parts, caps))]

        # --- COO delta (appends since the last re-shard) ---
        with jax.named_scope("delta"):
            delta_scores = score_coo_compiled(
                d_tf, d_term, d_doc, d_len, df_g, slot_of, qc_ext,
                n_docs, avgdl, None, model=model, k1=k1, b=b,
                chunk=min(delta_chunk, d_tf.shape[0]))
            delta_scores = jax.lax.psum(delta_scores, "terms")
            delta_scores = delta_scores * d_live[None, :]

        with jax.named_scope("shard_topk"):
            # every bucket and, behind them, the delta as one more
            # block (its slots' ids from doc_cap_ell on), each ranked
            # in place as the one-chip step ranks its blocks: a block's
            # dead tail (past block_live[i]; past d_n in the delta) is
            # masked to -inf there, and a tombstone inside it scores 0
            vals, ids = blocks_topk(
                (*parts, delta_scores),
                jnp.concatenate([block_live, d_n[None]]),
                jnp.concatenate([row0s,
                                 jnp.full((1,), doc_cap_ell, jnp.int32)]),
                k=k)
        with jax.named_scope("gather_merge"):
            shard_idx = jax.lax.axis_index("docs").astype(jnp.int32)
            gids = (shard_idx * jnp.int32(doc_cap_ell + doc_cap_delta)
                    + ids)
            all_vals = jax.lax.all_gather(vals, "docs")
            all_ids = jax.lax.all_gather(gids, "docs")
            return merge_topk(all_vals, all_ids, k=depth)

    def in_specs(nb):
        return ((P(None), P(), P(),
                 P("docs", None), P("docs", None),
                 P("docs", "terms", None), P("docs", "terms", None),
                 P("docs", "terms", None), P("docs", None),
                 P("docs", "terms", None), P("docs", "terms", None),
                 P("docs", "terms", None), P("docs", None), P("docs"),
                 P("docs", None),
                 P(None), P(), P(None, None), P(None, None))
                + (P("docs", "terms", None),) * nb * 2)

    # the function's name is the program's in a device trace
    # (``jit_mesh_ell_search(<fingerprint>)`` on the ``XLA Modules`` line)
    @jax.jit
    def mesh_ell_search(base: MeshEllArrays, delta, df_g, n_docs, avgdl,
                        q: QueryBatch):
        nb = base.n_buckets
        sharded = jax.shard_map(
            step, mesh=mesh, in_specs=in_specs(nb),
            out_specs=(P(), P()), check_vma=False)
        vals, gids = sharded(
            df_g, n_docs, avgdl, base.live, base.block_live,
            base.res_tf, base.res_term, base.res_doc, base.res_dl,
            delta.tf, delta.term, delta.doc, delta.doc_len,
            delta.n_live, delta.live,
            jnp.asarray(q.uniq), jnp.asarray(q.n_uniq),
            jnp.asarray(q.slots), jnp.asarray(q.weights),
            *base.impact, *base.term)
        if packed:
            return pack_topk(vals, gids)
        return vals, gids

    return mesh_ell_search


def with_ell_live(mesh: Mesh, arrays: MeshEllArrays,
                  live_host: np.ndarray) -> MeshEllArrays:
    """Tombstone update in ELL row space (host-rebuilt, like the delta's
    :func:`~tfidf_tpu.parallel.sharded.with_live_mask`)."""
    import dataclasses
    live = jax.device_put(live_host.astype(np.float32),
                          NamedSharding(mesh, P("docs", None)))
    return dataclasses.replace(arrays, live=live)
