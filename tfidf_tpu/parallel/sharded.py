"""Sharded scoring over the device mesh — the distributed forward step.

This subsumes the reference's entire scatter-gather data path
(``leader/Leader.java:39-92``: serial HTTP fan-out to every worker, JSON
score lists back, ``Map.merge`` sum at the leader) with one ``shard_map``
program over a ``("docs", "terms")`` mesh:

    scatter  -> the query batch is replicated to every device by sharding
    per-shard scoring -> local COO postings scored on-device
    global IDF        -> ``psum`` of per-shard document frequencies over the
                         whole mesh (the reference never globalizes IDF —
                         each Lucene worker scores against local stats; we
                         expose that behavior as parity mode and global IDF
                         as the default, SURVEY.md §7 Phase B)
    score reduce      -> ``psum`` of partial scores over the ``terms`` axis
    gather   -> per-docs-shard exact top-k, ``all_gather`` over ``docs``,
                associative re-top-k; every device ends with the answer

Collectives ride ICI inside one jitted program — there is no host round-trip
per worker, which is where the >=50x headroom over the Java system lives.

Host-side layout (``build_sharded_arrays``): documents are dealt
round-robin into ``D`` docs-shards (upload balancing is handled upstream by
the engine); each shard's row-sorted COO is split into ``T`` contiguous
chunks along nnz. Any disjoint partition of entries is correct because both
df and scores are additive over entries; contiguous chunking keeps the
partition balanced to within one entry.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tfidf_tpu.ops.csr import CooShard, next_capacity
from tfidf_tpu.ops.scoring import (QueryBatch, cosine_norms,
                                   score_coo_impl)
from tfidf_tpu.ops.topk import exact_topk, merge_topk, pack_topk


@dataclass
class ShardedArrays:
    """Global (addressable-on-mesh) arrays for the whole corpus.

    Leading axes: D = docs shards, T = terms shards.
    """

    tf: jax.Array        # f32 [D, T, chunk_cap]
    term: jax.Array      # i32 [D, T, chunk_cap]
    doc: jax.Array       # i32 [D, T, chunk_cap]
    doc_len: jax.Array   # f32 [D, doc_cap]
    df: jax.Array        # f32 [D, T, vocab_cap] (per-shard partial df)
    n_live: jax.Array    # i32 [D] occupied doc slots (append cursor)
    nnz_used: jax.Array  # i32 [D, T] entries in use per block (append cursor)
    # Tombstone mask, Lucene's deleted-docs bitmap at mesh scale: deleted
    # docs keep their postings (and stay in df/avgdl until a re-shard
    # compaction, like Lucene until merge) but score 0.
    live: jax.Array      # f32 [D, doc_cap] — 1=live, 0=tombstone/pad
    # Sum of RAW (pre-norm-quantization) lengths per shard: avgdl must be
    # computed from exact lengths (Lucene: sumTotalTermFreq / docCount)
    # even when doc_len holds SmallFloat-quantized values (parity mode).
    len_sum: jax.Array   # f32 [D]
    doc_cap: int
    vocab_cap: int



jax.tree_util.register_dataclass(
    ShardedArrays,
    data_fields=["tf", "term", "doc", "doc_len", "df", "n_live", "nnz_used",
                 "live", "len_sum"],
    meta_fields=["doc_cap", "vocab_cap"],
)


def host_value(x) -> np.ndarray:
    """Fetch a (small) device array to host, multi-process-safe.

    Single-controller: a plain fetch. Under ``jax.distributed`` a
    sharded array spans non-addressable devices, so the fetch is a
    ``process_allgather`` collective — EVERY process must reach this
    call in the same program order (the SPMD discipline mesh commits
    already require: all processes ingest and commit identically)."""
    if jax.process_count() == 1:
        return np.asarray(x)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def _split_ranges(k: int, t_parts: int) -> list[tuple[int, int]]:
    """Contiguous ceil-split of k entries over t_parts terms blocks — the
    single source of truth for the entry partition (build and ingest must
    agree or append cursors desync from the layout)."""
    step = -(-k // t_parts) if k else 0
    return [(min(t * step, k), min((t + 1) * step, k))
            for t in range(t_parts)]


def shard_documents(n_docs: int, n_shards: int) -> np.ndarray:
    """Round-robin placement: doc i -> shard i % D (balanced, deterministic).

    The engine's least-loaded placement (reference ``Leader.java:168-189``)
    applies at ingest; this is the static layout for mesh-resident scoring.
    """
    return np.arange(n_docs, dtype=np.int64) % n_shards


def build_sharded_arrays(shard: CooShard,
                         mesh: Mesh,
                         min_chunk_cap: int = 1 << 14,
                         min_doc_cap: int = 1024,
                         headroom: float = 0.25,
                         raw_doc_len: np.ndarray | None = None
                         ) -> ShardedArrays:
    """Partition one host COO shard across a (docs, terms) mesh.

    Returns device arrays placed with NamedShardings so each mesh slice
    holds exactly its block. ``headroom`` over-allocates the capacity
    buckets so subsequent on-device appends have a free tail even when the
    exact need lands on a power-of-two boundary (otherwise a rebuild right
    at a boundary would overflow on the very next commit).

    ``raw_doc_len`` (defaults to ``shard.doc_len``): exact pre-quantization
    lengths, used only for the per-shard avgdl sums — pass it when
    ``shard.doc_len`` holds norm-transformed values (Lucene parity).
    """
    D = mesh.shape["docs"]
    T = mesh.shape["terms"]
    nnz, n_docs = shard.nnz, shard.num_docs
    tf = np.asarray(shard.tf)[:nnz]
    term = np.asarray(shard.term)[:nnz]
    doc = np.asarray(shard.doc)[:nnz].astype(np.int64)
    doc_len_src = np.asarray(shard.doc_len)
    vocab_cap = shard.vocab_cap

    assign = shard_documents(n_docs, D)          # global doc -> docs shard
    local_id = np.zeros(n_docs, np.int64)
    counts = np.zeros(D, np.int64)
    for s in range(D):
        mask = assign == s
        local_id[mask] = np.arange(mask.sum())
        counts[s] = mask.sum()
    grow = 1.0 + max(headroom, 0.0)
    doc_cap = next_capacity(
        int(max(int(counts.max()) if D else 1, 1) * grow) + 1, min_doc_cap)

    entry_shard = assign[doc]                    # nnz -> docs shard
    chunk_caps = []
    per_shard = []
    for s in range(D):
        m = entry_shard == s
        k = int(m.sum())
        per_shard.append((tf[m], term[m], local_id[doc[m]].astype(np.int32)))
        chunk_caps.append(-(-k // T))            # ceil split over terms
    chunk_cap = next_capacity(
        int(max(max(chunk_caps, default=1), 1) * grow) + 1, min_chunk_cap)

    g_tf = np.zeros((D, T, chunk_cap), np.float32)
    g_term = np.zeros((D, T, chunk_cap), np.int32)
    # sorted-padding: free entries point at the last row (zero contribution)
    g_doc = np.full((D, T, chunk_cap), doc_cap - 1, np.int32)
    g_len = np.zeros((D, doc_cap), np.float32)
    g_df = np.zeros((D, T, vocab_cap), np.float32)
    g_used = np.zeros((D, T), np.int32)
    for s in range(D):
        stf, sterm, sdoc = per_shard[s]
        for t, (lo, hi) in enumerate(_split_ranges(stf.shape[0], T)):
            n = hi - lo
            g_used[s, t] = n
            if n > 0:
                g_tf[s, t, :n] = stf[lo:hi]
                g_term[s, t, :n] = sterm[lo:hi]
                g_doc[s, t, :n] = sdoc[lo:hi]
                # df is additive over any disjoint entry partition, but must
                # count each (doc, term) pair once — COO entries are unique
                # pairs, so counting entries is exactly df.
                np.add.at(g_df[s, t], sterm[lo:hi], 1.0)
        live = assign == s
        g_len[s, :int(counts[s])] = doc_len_src[:n_docs][live]

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    g_live = (np.arange(doc_cap)[None, :]
              < counts[:, None]).astype(np.float32)
    raw = (np.asarray(raw_doc_len) if raw_doc_len is not None
           else doc_len_src)[:n_docs]
    g_len_sum = np.zeros(D, np.float32)
    for s in range(D):
        g_len_sum[s] = float(raw[assign == s].sum())
    return ShardedArrays(
        tf=put(g_tf, P("docs", "terms", None)),
        term=put(g_term, P("docs", "terms", None)),
        doc=put(g_doc, P("docs", "terms", None)),
        doc_len=put(g_len, P("docs", None)),
        df=put(g_df, P("docs", "terms", None)),
        n_live=put(counts.astype(np.int32), P("docs")),
        nnz_used=put(g_used, P("docs", "terms")),
        live=put(g_live, P("docs", None)),
        len_sum=put(g_len_sum, P("docs")),
        doc_cap=doc_cap,
        vocab_cap=vocab_cap,
    )


def global_stats(arrays: ShardedArrays) -> tuple[jax.Array, jax.Array]:
    """(N, avgdl) over the whole mesh — host-visible scalars."""
    n = jnp.sum(arrays.n_live).astype(jnp.float32)
    total = jnp.sum(arrays.doc_len)
    return n, total / jnp.maximum(n, 1.0)


def make_sharded_search(mesh: Mesh,
                        *,
                        k: int,
                        model: str = "bm25",
                        k1: float = 1.2,
                        b: float = 0.75,
                        global_idf: bool = True,
                        chunk: int = 1 << 17,
                        packed: bool = False,
                        depth: int | None = None):
    """Build the jitted distributed search step for a fixed mesh/model.

    Returned callable:
        step(arrays: ShardedArrays, q_terms [B,T_q], q_weights [B,T_q])
            -> (top_vals [B,k], top_global_ids [B,k])

    ``top_global_ids`` encode (docs_shard, local_id) as shard * doc_cap + id;
    the engine maps them back to document names. ``k`` is a SHARD's
    depth; ``depth`` (None: ``k``) the merged reply's, for a request
    deeper than one shard is wide.

    ``global_idf=False`` reproduces the reference's per-worker statistics
    (each Lucene shard scores against local df/N — ``Worker.java:222-241``)
    for parity testing.
    """

    def step(tf, term, doc, doc_len, df, n_live, live, len_sum,
             q_uniq, q_n_uniq, q_slots, q_weights):
        q = QueryBatch(q_uniq, q_n_uniq, q_slots, q_weights)
        tf = tf.reshape(tf.shape[-1])
        term = term.reshape(term.shape[-1])
        doc = doc.reshape(doc.shape[-1])
        doc_len = doc_len.reshape(doc_len.shape[-1])
        df_local = df.reshape(df.shape[-1])
        n_local = n_live.reshape(())
        live = live.reshape(live.shape[-1])
        len_local = len_sum.reshape(())

        doc_cap = doc_len.shape[0]

        if global_idf:
            # THE collective the north star names: global document frequency
            # via psum over the whole mesh (entries are disjoint across both
            # axes, so summing both is exact).
            df_eff = jax.lax.psum(df_local, ("docs", "terms"))
            n_eff = jax.lax.psum(n_local.astype(jnp.float32), "docs")
            total_len = jax.lax.psum(len_local, "docs")
            avgdl = total_len / jnp.maximum(n_eff, 1.0)
        else:
            # Parity mode: per-docs-shard stats, as each Java worker sees.
            df_eff = jax.lax.psum(df_local, "terms")
            n_eff = n_local.astype(jnp.float32)
            avgdl = len_local / jnp.maximum(n_eff, 1.0)

        doc_norms = None
        if model == "tfidf_cosine":
            # Norms depend on (global) df, so they are computed in-step:
            # per-entry squared weights segment-summed locally, then reduced
            # over the terms axis (a document's entries span terms shards).
            sq = cosine_norms(tf, term, doc, df_eff, n_eff, doc_cap) ** 2
            doc_norms = jnp.sqrt(jax.lax.psum(sq, "terms"))

        partial = score_coo_impl(
            tf, term, doc, doc_len, df_eff, q,
            n_eff, avgdl, doc_norms, model=model, k1=k1, b=b, chunk=chunk)

        scores = jax.lax.psum(partial, "terms")        # [B, doc_cap]
        scores = scores * live[None, :]                # zero tombstones
        vals, ids = exact_topk(scores, n_local, k=k)
        shard_idx = jax.lax.axis_index("docs").astype(jnp.int32)
        gids = shard_idx * jnp.int32(doc_cap) + ids

        all_vals = jax.lax.all_gather(vals, "docs")    # [D, B, k]
        all_ids = jax.lax.all_gather(gids, "docs")
        top_vals, top_ids = merge_topk(all_vals, all_ids, k=depth)
        return top_vals, top_ids

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P("docs", "terms", None), P("docs", "terms", None),
                  P("docs", "terms", None), P("docs", None),
                  P("docs", "terms", None), P("docs"), P("docs", None),
                  P("docs"),
                  P(None), P(), P(None, None), P(None, None)),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @jax.jit
    def search(arrays: ShardedArrays, q: QueryBatch):
        vals, gids = sharded(
            arrays.tf, arrays.term, arrays.doc, arrays.doc_len,
            arrays.df, arrays.n_live, arrays.live,
            arrays.len_sum,
            jnp.asarray(q.uniq), jnp.asarray(q.n_uniq),
            jnp.asarray(q.slots), jnp.asarray(q.weights))
        if packed:
            # one [B, 2k] i32 buffer: bitcast values + ids fetched in a
            # single device->host transfer
            return pack_topk(vals, gids)
        return vals, gids

    return search


def make_sharded_scores(mesh: Mesh,
                        *,
                        model: str = "bm25",
                        k1: float = 1.2,
                        b: float = 0.75,
                        global_idf: bool = True,
                        chunk: int = 1 << 17):
    """Full per-shard score matrices — the parity-mode (unbounded) path.

    Returned callable:
        step(arrays, q...) -> scores [D, B, doc_cap], sharded over docs.

    The host ranks the full matrix (the reference's ``Integer.MAX_VALUE``
    behavior, ``Worker.java:230``); O(corpus) per query by definition, so
    this never rides the serving fast path.
    """

    def step(tf, term, doc, doc_len, df, n_live, live, len_sum,
             q_uniq, q_n_uniq, q_slots, q_weights):
        q = QueryBatch(q_uniq, q_n_uniq, q_slots, q_weights)
        tf = tf.reshape(tf.shape[-1])
        term = term.reshape(term.shape[-1])
        doc = doc.reshape(doc.shape[-1])
        doc_len = doc_len.reshape(doc_len.shape[-1])
        df_local = df.reshape(df.shape[-1])
        n_local = n_live.reshape(())
        live = live.reshape(live.shape[-1])
        len_local = len_sum.reshape(())
        doc_cap = doc_len.shape[0]

        if global_idf:
            df_eff = jax.lax.psum(df_local, ("docs", "terms"))
            n_eff = jax.lax.psum(n_local.astype(jnp.float32), "docs")
            total_len = jax.lax.psum(len_local, "docs")
            avgdl = total_len / jnp.maximum(n_eff, 1.0)
        else:
            df_eff = jax.lax.psum(df_local, "terms")
            n_eff = n_local.astype(jnp.float32)
            avgdl = len_local / jnp.maximum(n_eff, 1.0)

        doc_norms = None
        if model == "tfidf_cosine":
            sq = cosine_norms(tf, term, doc, df_eff, n_eff, doc_cap) ** 2
            doc_norms = jnp.sqrt(jax.lax.psum(sq, "terms"))

        partial = score_coo_impl(
            tf, term, doc, doc_len, df_eff, q,
            n_eff, avgdl, doc_norms, model=model, k1=k1, b=b, chunk=chunk)
        scores = jax.lax.psum(partial, "terms")
        return (scores * live[None, :])[None]           # [1, B, doc_cap]

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P("docs", "terms", None), P("docs", "terms", None),
                  P("docs", "terms", None), P("docs", None),
                  P("docs", "terms", None), P("docs"), P("docs", None),
                  P("docs"),
                  P(None), P(), P(None, None), P(None, None)),
        out_specs=P("docs", None, None),
        check_vma=False,
    )

    @jax.jit
    def scores(arrays: ShardedArrays, q: QueryBatch):
        return sharded(arrays.tf, arrays.term, arrays.doc, arrays.doc_len,
                       arrays.df, arrays.n_live, arrays.live,
                       arrays.len_sum,
                       jnp.asarray(q.uniq), jnp.asarray(q.n_uniq),
                       jnp.asarray(q.slots), jnp.asarray(q.weights))

    return scores


def build_ingest_batch(mesh: Mesh,
                       arrays: ShardedArrays,
                       new_docs_per_shard: list[list[dict[int, int]]],
                       lengths_per_shard: list[list[float]],
                       batch_chunk_cap: int,
                       raw_lengths_per_shard: list[list[float]] | None
                       = None):
    """Vectorize new documents into a device-ready ingest batch.

    ``new_docs_per_shard[d]`` holds the new docs placed on docs-shard d
    (already chosen by the balancer); they get local ids continuing after
    the shard's current live count. Entries are split over the terms axis
    the same way as the initial build (contiguous chunks).

    Raises if any block's free tail cannot hold a full batch window —
    ``dynamic_update_slice`` silently clamps out-of-range starts, so an
    oversized append would otherwise corrupt the front of the arrays.
    """
    D = mesh.shape["docs"]
    T = mesh.shape["terms"]
    C = batch_chunk_cap
    doc_cap = arrays.doc_cap
    chunk_cap = arrays.tf.shape[-1]
    used_now = host_value(arrays.nnz_used)
    if int(used_now.max()) + C > chunk_cap:
        raise ValueError(
            f"ingest batch (cap {C}) does not fit free tail "
            f"(used max {int(used_now.max())} of {chunk_cap}); "
            "compact/re-shard with a larger nnz capacity first")
    n_live_before = [int(x) for x in host_value(arrays.n_live)]
    max_new = max((len(d) for d in new_docs_per_shard), default=0)
    L = next_capacity(max(max_new, 1), 8)   # O(batch), not O(doc_cap)
    if max(n_live_before) + L > doc_cap:
        # the padded window would spill past the capacity even though the
        # real docs fit — retry with the tightest bucket before giving up
        L = next_capacity(max(max_new, 1), 1)
    if max(n_live_before) + L > doc_cap:
        raise ValueError("docs-shard over doc capacity; re-shard")
    new_tf = np.zeros((D, T, C), np.float32)
    new_term = np.zeros((D, T, C), np.int32)
    new_doc = np.full((D, T, C), doc_cap - 1, np.int32)   # sorted-padding
    new_count = np.zeros((D, T), np.int32)
    new_len = np.zeros((D, L), np.float32)
    new_docs = np.zeros(D, np.int32)
    # avgdl delta uses RAW lengths (doc_len may hold quantized values)
    raws = (raw_lengths_per_shard if raw_lengths_per_shard is not None
            else lengths_per_shard)
    new_len_sum = np.asarray([float(sum(r)) for r in raws], np.float32)
    for d in range(D):
        docs = new_docs_per_shard[d]
        lens = lengths_per_shard[d]
        tfs, terms, rows = [], [], []
        for i, counts in enumerate(docs):
            local = n_live_before[d] + i
            new_len[d, i] = lens[i]
            for t, f in sorted(counts.items()):
                if not 0 <= t < arrays.vocab_cap:
                    # the sharded path has no vocab growth; an out-of-range
                    # id would be clamped by XLA's gather at search time and
                    # silently score against another term's df
                    raise ValueError(
                        f"term id {t} outside vocab capacity "
                        f"{arrays.vocab_cap}; grow the vocabulary and "
                        "rebuild the sharded arrays first")
                terms.append(t)
                tfs.append(float(f))
                rows.append(local)
        new_docs[d] = len(docs)
        for t, (lo, hi) in enumerate(_split_ranges(len(tfs), T)):
            n = hi - lo
            if n > C:
                raise ValueError("ingest batch over chunk capacity")
            if n:
                new_tf[d, t, :n] = tfs[lo:hi]
                new_term[d, t, :n] = terms[lo:hi]
                new_doc[d, t, :n] = rows[lo:hi]
            new_count[d, t] = n

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return (put(new_tf, P("docs", "terms", None)),
            put(new_term, P("docs", "terms", None)),
            put(new_doc, P("docs", "terms", None)),
            put(new_count, P("docs", "terms")),
            put(new_len, P("docs", None)),
            put(new_docs, P("docs")),
            put(new_len_sum, P("docs")))


def make_sharded_ingest(mesh: Mesh):
    """Build the jitted distributed ingest step — on-device index growth.

    The streaming analog of the reference's upload path (file -> chosen
    worker -> index + commit, ``Leader.java:153-207`` / ``Worker.java:125-
    146``), but batched: each docs-shard receives a block of new postings
    (host-vectorized, already placed by the balancer) and appends them into
    its device arrays without recompilation or host round-trips:

        tf/term/doc: dynamic-update-slice at the shard's append cursor
        df:          += segment-sum of the new entries
        doc_len:     new lengths written at the live cursor (new local ids
                     are contiguous from n_live, so the delta is O(batch))
        n_live:      += new document count

    New-entry padding must be tf 0 / term 0 / doc ``doc_cap - 1`` (the
    sorted-padding convention) — writing those into the free region is a
    no-op by construction. Overflowing a capacity bucket is the host's job
    to detect (re-shard with bigger caps).

    Returned callable:
        ingest(arrays, new_tf [D,T,C], new_term, new_doc, new_count [D,T],
               new_len [D,L], new_docs [D]) -> ShardedArrays
    """

    def step(tf, term, doc, doc_len, df, n_live, nnz_used, live, len_sum,
             new_tf, new_term, new_doc, new_count, new_len, new_docs,
             new_len_sum):
        tf = tf.reshape(tf.shape[-1])
        term = term.reshape(term.shape[-1])
        doc = doc.reshape(doc.shape[-1])
        doc_len = doc_len.reshape(doc_len.shape[-1])
        df = df.reshape(df.shape[-1])
        n_live = n_live.reshape(())
        used = nnz_used.reshape(())
        live = live.reshape(live.shape[-1])
        len_sum = len_sum.reshape(())
        new_tf = new_tf.reshape(new_tf.shape[-1])
        new_term = new_term.reshape(new_term.shape[-1])
        new_doc = new_doc.reshape(new_doc.shape[-1])
        new_count = new_count.reshape(())
        new_len = new_len.reshape(new_len.shape[-1])
        new_docs = new_docs.reshape(())
        new_len_sum = new_len_sum.reshape(())

        vocab_cap = df.shape[0]
        tf2 = jax.lax.dynamic_update_slice(tf, new_tf, (used,))
        term2 = jax.lax.dynamic_update_slice(term, new_term, (used,))
        doc2 = jax.lax.dynamic_update_slice(doc, new_doc, (used,))
        df2 = df + jax.ops.segment_sum(
            (new_tf > 0).astype(jnp.float32), new_term,
            num_segments=vocab_cap)
        # new docs occupy the contiguous range starting at the live cursor;
        # their prior lengths are zero, so an overwrite == an add
        doc_len2 = jax.lax.dynamic_update_slice(doc_len, new_len, (n_live,))
        # newly appended slots become live (the batch window may be wider
        # than the real doc count, so mark exactly [n_live, n_live+new))
        slot = jnp.arange(live.shape[0], dtype=jnp.int32)
        live2 = jnp.where((slot >= n_live) & (slot < n_live + new_docs),
                          jnp.float32(1.0), live)
        n2 = n_live + new_docs
        used2 = used + new_count
        return (tf2[None, None], term2[None, None], doc2[None, None],
                doc_len2[None], df2[None, None], n2[None],
                used2[None, None], live2[None],
                (len_sum + new_len_sum)[None])

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P("docs", "terms", None), P("docs", "terms", None),
                  P("docs", "terms", None), P("docs", None),
                  P("docs", "terms", None), P("docs"), P("docs", "terms"),
                  P("docs", None), P("docs"),
                  P("docs", "terms", None), P("docs", "terms", None),
                  P("docs", "terms", None), P("docs", "terms"),
                  P("docs", None), P("docs"), P("docs")),
        out_specs=(P("docs", "terms", None), P("docs", "terms", None),
                   P("docs", "terms", None), P("docs", None),
                   P("docs", "terms", None), P("docs"),
                   P("docs", "terms"), P("docs", None), P("docs")),
        check_vma=False,
    )

    @jax.jit
    def ingest(arrays: ShardedArrays, new_tf, new_term, new_doc, new_count,
               new_len, new_docs, new_len_sum):
        (tf, term, doc, doc_len, df, n_live, nnz_used, live,
         len_sum) = sharded(
            arrays.tf, arrays.term, arrays.doc, arrays.doc_len, arrays.df,
            arrays.n_live, arrays.nnz_used, arrays.live, arrays.len_sum,
            new_tf, new_term, new_doc, new_count, new_len, new_docs,
            new_len_sum)
        return ShardedArrays(
            tf=tf, term=term, doc=doc, doc_len=doc_len, df=df,
            n_live=n_live, nnz_used=nnz_used, live=live, len_sum=len_sum,
            doc_cap=arrays.doc_cap, vocab_cap=arrays.vocab_cap)

    return ingest


def with_live_mask(mesh: Mesh, arrays: ShardedArrays,
                   live_host: np.ndarray) -> ShardedArrays:
    """Replace the tombstone mask from a host [D, doc_cap] f32 array.

    Deletes are rare next to queries, so the mask is rebuilt host-side and
    re-placed (one [D, doc_cap] transfer) rather than scattered on device —
    the postings arrays are untouched, exactly like flipping bits in
    Lucene's deleted-docs bitmap without rewriting segments.
    """
    import dataclasses
    live = jax.device_put(live_host.astype(np.float32),
                          NamedSharding(mesh, P("docs", None)))
    return dataclasses.replace(arrays, live=live)


# ---- ShardedArrays checkpoint (mesh-scale Worker.java:88 commit) ----

_CKPT_FIELDS = ("tf", "term", "doc", "doc_len", "df", "n_live",
                "nnz_used", "live", "len_sum")
_CKPT_SPECS = {
    "tf": P("docs", "terms", None), "term": P("docs", "terms", None),
    "doc": P("docs", "terms", None), "doc_len": P("docs", None),
    "df": P("docs", "terms", None), "n_live": P("docs"),
    "nnz_used": P("docs", "terms"), "live": P("docs", None),
    "len_sum": P("docs"),
}


def save_sharded_arrays(arrays: ShardedArrays, path: str) -> None:
    """Write the full device state to one ``.npz`` (atomic via rename).

    The host copy of every field is fetched once; restore re-places the
    blocks on any mesh with the same (D, T) shape.
    """
    from tfidf_tpu.utils import storage
    data = {f: np.asarray(getattr(arrays, f)) for f in _CKPT_FIELDS}
    data["meta"] = np.asarray([arrays.doc_cap, arrays.vocab_cap], np.int64)
    tmp = path + ".part"
    storage.savez(tmp, **data)
    storage.replace(tmp, path)


def load_sharded_arrays(path: str, mesh: Mesh) -> ShardedArrays:
    """Restore a :func:`save_sharded_arrays` checkpoint onto ``mesh``.

    The mesh must have the same (docs, terms) shape the checkpoint was
    taken with (the leading axes of the saved blocks).
    """
    data = np.load(path)
    D, T = data["tf"].shape[:2]
    if (mesh.shape["docs"], mesh.shape["terms"]) != (D, T):
        raise ValueError(
            f"checkpoint was taken on a ({D}, {T}) mesh; restoring onto "
            f"{dict(mesh.shape)} requires a rebuild from documents")
    doc_cap, vocab_cap = (int(x) for x in data["meta"])
    kw = {f: jax.device_put(data[f], NamedSharding(mesh, _CKPT_SPECS[f]))
          for f in _CKPT_FIELDS}
    return ShardedArrays(doc_cap=doc_cap, vocab_cap=vocab_cap, **kw)
