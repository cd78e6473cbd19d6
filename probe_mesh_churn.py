"""Mesh churn correctness past toy scale (VERDICT r3 #2).

An 8-virtual-device CPU mesh runs >=10k documents through randomized
upsert / delete / commit churn — the differential test's loop at ~1000x
the corpus — with scipy-oracle top-10 parity checked after EVERY commit,
for both mesh layouts:

* ``ell``: global stats (df, N, avgdl) are recomputed over the LIVE
  corpus at each commit (mesh_ell_index.py docstring), so the oracle is
  fully independent: BM25 over the live shadow corpus.
* ``coo``: df/N/avgdl count tombstones until the next re-shard
  (mesh_index.py docstring — Lucene's docFreq-until-merge semantics), so
  the oracle models exactly that: stats over every entry PLACED since
  the last re-shard (live + tombstoned), scores over live docs only.
  Re-shards are detected via the observable ``rebuilds`` counter.

Emits MESH_CHURN.json with docs/devices/commits/parity evidence.
"""

from __future__ import annotations

import os

from tfidf_tpu.utils.compile_cache import configure_compile_cache

# a churn probe of the mesh code path on 8 virtual CPU devices (the
# package import above already loaded jax, so the config API, not env)
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import json
import sys
import time

import numpy as np
import scipy.sparse as sp

from bench import make_doc_arrays

SEED = 42
V = 15_000
BASE_DOCS = 25_000
AVG_LEN = 40
ROUNDS = 8
NEW_PER_ROUND = 1500
REUP_PER_ROUND = 600
DEL_PER_ROUND = 900
QUERIES_PER_CHECK = 48
TOP_K = 10
K1, B = 1.2, 0.75


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def random_doc(rng):
    n = int(rng.integers(8, 2 * AVG_LEN))
    terms = (rng.zipf(1.25, size=n) % V).astype(np.int64)
    ids, tfs = np.unique(terms, return_counts=True)
    return ids.astype(np.int32), tfs.astype(np.float32), float(n)


def make_query(rng) -> str:
    k = int(rng.integers(2, 5))
    ids = rng.zipf(1.25, size=k) % V
    return " ".join(f"t{w}" for w in ids)


def oracle_check(engine, committed: dict, dead: list, queries, vocab_map,
                 *, live_stats: bool) -> None:
    """Exact top-10 parity vs a scipy-CSR BM25 oracle.

    ``committed``: name -> (ids, tfs, length) of the device-live docs.
    ``dead``: [(ids, length)] tombstoned since the last re-shard — they
    join the stats corpus when ``live_stats`` is False (COO layout).
    ``vocab_map``: corpus term id -> engine vocab id (identity here, but
    asserted at registration)."""
    names = sorted(committed)
    n_live = len(names)
    stats_lengths = [committed[n][2] for n in names]
    df = np.zeros(V + 1, np.float64)
    for n in names:
        df[committed[n][0]] += 1.0
    if not live_stats:
        for ids, length in dead:
            df[ids] += 1.0
            stats_lengths.append(length)
    N = float(n_live + (0 if live_stats else len(dead)))
    avgdl = float(np.mean(stats_lengths)) if stats_lengths else 1.0
    idf = np.log1p((N - df + 0.5) / (df + 0.5))

    row_parts, col_parts, val_parts = [], [], []
    for i, n in enumerate(names):
        ids, tfs, length = committed[n]
        denom = tfs + K1 * (1 - B + B * length / avgdl)
        row_parts.append(np.full(ids.shape[0], i, np.int64))
        col_parts.append(ids.astype(np.int64))
        val_parts.append(idf[ids] * tfs / denom)
    M = sp.csr_matrix(
        (np.concatenate(val_parts), (np.concatenate(row_parts),
                                     np.concatenate(col_parts))),
        shape=(n_live, V + 1))
    name_row = {n: i for i, n in enumerate(names)}

    got = engine.search_batch(queries, k=TOP_K)
    for qi, (q, hits) in enumerate(zip(queries, got)):
        qv = np.zeros(V + 1, np.float32)
        for tok in q.split():
            qv[int(tok[1:])] += 1.0
        scores = np.asarray(M @ qv).ravel()
        want = np.sort(scores)[::-1][:TOP_K]
        want = want[want > 0]
        have = np.asarray([h.score for h in hits], np.float32)
        hit_names = [h.name for h in hits]
        assert len(set(hit_names)) == len(hit_names), \
            f"duplicate hits: {hit_names}"
        assert all(n in committed for n in hit_names), \
            f"dead/unknown doc returned: {hit_names}"
        assert have.shape[0] == want.shape[0], \
            (qi, q, have.shape, want.shape)
        np.testing.assert_allclose(have, want, rtol=2e-3, atol=1e-4,
                                   err_msg=f"query {qi} {q!r} top-k")
        for h in hits:   # each returned doc scores what the oracle says
            np.testing.assert_allclose(
                h.score, scores[name_row[h.name]], rtol=2e-3, atol=1e-4,
                err_msg=f"query {qi} {q!r} doc {h.name}")


def run_layout(layout: str) -> dict:
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    rng = np.random.default_rng(SEED)
    engine = Engine(Config(engine_mode="mesh", mesh_layout=layout,
                           query_batch=QUERIES_PER_CHECK,
                           max_query_terms=8))
    import jax
    n_dev = len(jax.devices())
    assert n_dev == 8, f"expected 8 virtual devices, got {n_dev}"
    live_stats = layout == "ell"

    for i in range(V):
        vid = engine.vocab.add(f"t{i}")
        assert vid == i, "vocab ids must mirror corpus term ids"

    committed: dict[str, tuple] = {}   # device-live version per name
    dead: list[tuple] = []             # tombstoned since last re-shard
    pending: dict[str, tuple | None] = {}
    last_rebuilds = -1

    def apply_pending_and_commit():
        nonlocal last_rebuilds
        engine.commit()
        for name, doc in pending.items():
            if name in committed:
                old = committed.pop(name)
                dead.append((old[0], old[2]))
            if doc is not None:
                committed[name] = doc
        pending.clear()
        rb = engine.index.rebuilds
        if rb != last_rebuilds:
            dead.clear()   # re-shard drops tombstones from the stats
            last_rebuilds = rb

    t0 = time.perf_counter()
    offsets, ids, tfs, lengths = make_doc_arrays(rng, BASE_DOCS, V,
                                                 AVG_LEN)
    add = engine.index.add_document_arrays
    for i in range(BASE_DOCS):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i:06d}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
        pending[f"d{i:06d}"] = (ids[lo:hi].astype(np.int32),
                                tfs[lo:hi], float(lengths[i]))
    last_rebuilds = engine.index.rebuilds
    apply_pending_and_commit()
    base_commit_s = time.perf_counter() - t0
    log(f"[{layout}] base: {BASE_DOCS} docs committed on {8} devices "
        f"in {base_commit_s:.0f}s (rebuilds={engine.index.rebuilds})")

    queries = [make_query(rng) for _ in range(QUERIES_PER_CHECK)]
    oracle_check(engine, committed, dead, queries, None,
                 live_stats=live_stats)
    log(f"[{layout}] base parity OK ({QUERIES_PER_CHECK} queries, "
        f"top-{TOP_K})")

    next_id = BASE_DOCS
    commits = 1
    checks = 1
    for rnd in range(ROUNDS):
        t0 = time.perf_counter()
        ops = []
        for _ in range(NEW_PER_ROUND):
            ops.append(("up", f"d{next_id:06d}"))
            next_id += 1
        live_names = sorted(set(committed) | {
            n for n, d in pending.items() if d is not None})
        for n in rng.choice(live_names, size=REUP_PER_ROUND,
                            replace=False):
            ops.append(("up", str(n)))
        for n in rng.choice(live_names, size=DEL_PER_ROUND,
                            replace=False):
            ops.append(("del", str(n)))
        rng.shuffle(ops)
        for op, name in ops:
            if op == "up":
                dids, dtfs, dlen = random_doc(rng)
                engine.index.add_document_arrays(name, dids, dtfs, dlen)
                pending[name] = (dids, dtfs, dlen)
            else:
                existed = engine.delete(name)
                assert existed == (name in committed or
                                   pending.get(name) is not None), name
                pending[name] = None
        apply_pending_and_commit()
        commit_s = time.perf_counter() - t0
        queries = [make_query(rng) for _ in range(QUERIES_PER_CHECK)]
        oracle_check(engine, committed, dead, queries, None,
                     live_stats=live_stats)
        commits += 1
        checks += 1
        log(f"[{layout}] round {rnd}: {len(ops)} ops, commit+churn "
            f"{commit_s:.1f}s, live={len(committed)}, "
            f"dead={len(dead)}, rebuilds={engine.index.rebuilds}, "
            f"parity OK")

    return {"layout": layout, "devices": 8,
            "base_docs": BASE_DOCS,
            "final_live_docs": len(committed),
            "rounds": ROUNDS, "commits": commits,
            "ops_per_round": NEW_PER_ROUND + REUP_PER_ROUND
            + DEL_PER_ROUND,
            "queries_per_check": QUERIES_PER_CHECK,
            "parity_checks": checks, "top_k": TOP_K,
            "rebuilds": int(engine.index.rebuilds),
            "appends": int(engine.index.appends),
            "base_commit_s": round(base_commit_s, 1),
            "parity_checked": True}


def main() -> None:
    out = {"layouts": {}}
    for layout in ("ell", "coo"):
        out["layouts"][layout] = run_layout(layout)
    out["parity_checked"] = all(
        v["parity_checked"] for v in out["layouts"].values())
    out["devices"] = 8
    with open(os.path.join(os.path.dirname(__file__),
                           "MESH_CHURN.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    configure_compile_cache()
    main()
