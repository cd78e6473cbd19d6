"""Mesh-sharded dense top-k: the embedding column over the ``docs`` axis.

Single-process analog of the cluster's two-stage plan: the embedding
rows are sharded over the ``docs`` mesh axis by the SAME placement the
sparse postings use (each docs slice owns a disjoint, contiguous row
range — ``base`` carries each shard's global row offset, playing the
role of the owner map), every device computes its local blocked matmul
top-k (``ops/dense.py`` work, MXU-shaped per shard), and one k-sized
``all_gather`` + exact merge produces the global list.  Exact by the
same argument as the sparse gather: the global top-k is contained in
the union of per-shard top-ks.

Collective cost per query batch is O(D * B * k) — the k-sized gather
only, never the embeddings — so the ``docs`` axis rides DCN fine,
mirroring ``parallel/sharded.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tfidf_tpu.ops.topk import merge_topk, pack_topk


def shard_dense_column(mesh: Mesh, rows_per_shard: list,
                       dim_pad: int) -> tuple:
    """Place per-shard embedding rows onto the ``docs`` axis.

    Every shard is padded to the widest shard's row count (static
    shapes, as shard_map requires); ``num_live`` masks the padding and
    ``base`` maps local row ids back to global ids in the concatenated
    (shard-major) order — the order the caller's name table uses.
    Returns (emb, num_live, base) device arrays.
    """
    n_shards = int(mesh.shape["docs"])
    if len(rows_per_shard) != n_shards:
        raise ValueError(f"{len(rows_per_shard)} shards for a "
                         f"{n_shards}-wide docs axis")
    cap = max(1, max(r.shape[0] for r in rows_per_shard))
    emb = np.zeros((n_shards * cap, dim_pad), dtype=np.float32)
    live = np.zeros(n_shards, dtype=np.int32)
    base = np.zeros(n_shards, dtype=np.int32)
    off = 0
    for s, rows in enumerate(rows_per_shard):
        n = rows.shape[0]
        emb[s * cap:s * cap + n, :rows.shape[1]] = rows
        live[s] = n
        base[s] = off
        off += n
    dev = jax.device_put(emb, NamedSharding(mesh, P("docs", None)))
    live_d = jax.device_put(live, NamedSharding(mesh, P("docs")))
    base_d = jax.device_put(base, NamedSharding(mesh, P("docs")))
    return dev, live_d, base_d


def make_mesh_dense_search(mesh: Mesh, *, k: int):
    """Build the jitted sharded search: (queries [B, dim_pad]
    replicated, emb/num_live/base from :func:`shard_dense_column`) ->
    packed global top-k [B, 2k] replicated (``ops/topk.pack_topk``
    layout, ids in concatenated shard-major order)."""

    def step(queries, emb, num_live, base):
        cap = emb.shape[0]                      # per-shard rows
        scores = jax.lax.dot_general(
            queries, emb,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        idx = jnp.arange(cap, dtype=jnp.int32)[None, :]
        masked = jnp.where(idx < num_live[0], scores, -jnp.inf)
        kk = min(k, cap)
        vals, ids = jax.lax.top_k(masked, kk)
        gids = ids.astype(jnp.int32) + base[0]
        all_vals = jax.lax.all_gather(vals, "docs")     # [D, B, kk]
        all_ids = jax.lax.all_gather(gids, "docs")
        top_vals, top_ids = merge_topk(all_vals, all_ids)
        return pack_topk(top_vals, top_ids)

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(None, None), P("docs", None), P("docs"), P("docs")),
        out_specs=P(None, None), check_vma=False)
    return jax.jit(sharded)
