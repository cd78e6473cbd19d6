"""Durable checkpoint of a shard index (postings + vocabulary).

The reference's "checkpoint" is its Lucene index directory on a persistent
volume, committed after boot and after every upload (``Worker.java:88,138``);
resume is a re-walk of the raw documents with idempotent upserts. We keep
that property — ``Engine.build_from_directory`` always works — and add an
explicit, atomic checkpoint that restores the exact index state (postings,
lengths, vocabulary, ingest order) much faster than re-analyzing the corpus.

Format: ``<path>`` is a symlink to a versioned sibling ``<path>.v<N>``
containing:
    vocab.txt     one term per line, line number = id
    docs.npz      offsets[n+1], term_ids[nnz], tfs[nnz], lengths[n]
    names.json    document names, aligned with offsets
    meta.json     model kind, counts, format version
    MANIFEST.json CRC32 + size of every file above (utils/storage.py)

Crash consistency (the storage-seam contract): every file is built in a
temp sibling ``<path>.build.*``, covered by a checksummed manifest,
fsynced, and the whole directory is atomically renamed into its
``.v<N>`` name — so a version dir either exists complete or not at all,
and a crash mid-save can never make the NEWEST version the torn one.
Publish is then a single atomic ``os.replace`` of the symlink, so at
every instant ``<path>`` resolves to a complete checkpoint. Older
``.v<N>`` dirs are pruned only after a successful publish, keeping
``config.storage_keep_versions`` of them as fallbacks:
:func:`restore_checkpoint` verifies the manifest before trusting a
version and falls back to the newest INTACT one, quarantining the
corrupt dir (metric + trace event) — corruption is recovery or loud
refusal, never silently wrong scores.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.ops import ell
from tfidf_tpu.utils import storage
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.faults import fault_point
from tfidf_tpu.utils.logging import get_logger
from tfidf_tpu.utils.metrics import global_metrics
from tfidf_tpu.utils.tracing import span_event

log = get_logger("engine.checkpoint")

# 2 (PR 43): ``snapshot.npz`` holds the ELL blocks width-major,
# ``ell_imp_i`` / ``ell_term_i`` ``[width, rows_cap]``, as the index
# holds them. A version 1 directory (``[rows_cap, width]``) still
# loads: its blocks are turned on the way in (``_snapshot_arrays``), so
# a restored index never serves a transposed block. Nothing else moved.
FORMAT_VERSION = 2
_ROW_MAJOR_VERSION = 1


def _score_signature(engine: Engine) -> list:
    """Everything the precomputed snapshot arrays depend on: restoring
    them under a different scoring config would silently serve wrong
    scores, so load falls back to a full commit on any mismatch. The
    ELL blocks' row ceiling belongs to it: blocks cut under another one
    are not those a step's stretches are planned over."""
    c = engine.config
    return [engine.model.kind, c.bm25_k1, c.bm25_b, c.lucene_parity,
            c.scoring_layout, c.ell_width_cap, ell.ELL_BLOCK_ROWS_MAX]


def save_checkpoint(engine: Engine, directory: str) -> None:
    if hasattr(engine.index, "live_entries_and_gen"):
        entries, entries_gen = engine.index.live_entries_and_gen()
    else:
        entries, entries_gen = engine.index.live_entries(), None
    n = len(entries)
    offsets = np.zeros(n + 1, np.int64)
    for i, d in enumerate(entries):
        offsets[i + 1] = offsets[i] + d.term_ids.shape[0]
    nnz = int(offsets[-1])
    term_ids = np.zeros(nnz, np.int32)
    tfs = np.zeros(nnz, np.float32)
    lengths = np.zeros(n, np.float32)
    for i, d in enumerate(entries):
        term_ids[offsets[i]:offsets[i + 1]] = d.term_ids
        tfs[offsets[i]:offsets[i + 1]] = d.tfs
        lengths[i] = d.length

    base = directory.rstrip("/")
    parent = os.path.dirname(os.path.abspath(base)) or "."
    os.makedirs(parent, exist_ok=True)
    prefix = os.path.basename(base) + ".v"
    existing = sorted(int(d[len(prefix):]) for d in os.listdir(parent)
                      if d.startswith(prefix)
                      and d[len(prefix):].isdigit())
    version = (existing[-1] + 1) if existing else 1
    vdir = f"{base}.v{version}"
    if os.path.exists(vdir):
        shutil.rmtree(vdir)
    # build in a temp sibling — the version NAME only ever appears via
    # one atomic rename of a complete, manifested, fsynced directory
    # (storage.publish_dir), so a crash anywhere in here leaves stale
    # ``.build`` garbage, never a torn ``.v<N>``
    for d in os.listdir(parent):
        if d.startswith(os.path.basename(base) + ".build."):
            shutil.rmtree(os.path.join(parent, d), ignore_errors=True)
    build = f"{base}.build.{os.getpid()}"
    os.makedirs(build)
    engine.vocab.save(os.path.join(build, "vocab.txt"))
    storage.savez(os.path.join(build, "docs.npz"),
                  offsets=offsets, term_ids=term_ids, tfs=tfs,
                  lengths=lengths)
    storage.write_bytes(os.path.join(build, "names.json"),
                        json.dumps([d.name for d in entries]).encode())
    # dense plane (ISSUE 17): the embedding column rides the same build
    # dir, so the manifest + publish_dir discipline covers it for free
    # (a torn embeddings.npz is caught by the same CRC pass as a torn
    # docs.npz). Rows are stored with an index into names.json instead
    # of duplicating the name strings.
    emb_meta = None
    if engine.dense is not None:
        rows, dnames = engine.dense.export_arrays()
        pos = {name: i for i, name in enumerate(d.name for d in entries)}
        if all(nm in pos for nm in dnames):
            storage.savez(
                os.path.join(build, "embeddings.npz"), rows=rows,
                name_idx=np.fromiter((pos[nm] for nm in dnames),
                                     np.int64, len(dnames)))
            emb_meta = engine.dense.embedder.signature()
    # fast-restore payload: the committed snapshot's device arrays, so
    # load skips the O(corpus) host COO/ELL re-layout (VERDICT r3 #5).
    # The snapshot's doc order is its own (width-sorted); store it as a
    # permutation into names.json instead of duplicating 1M names.
    snap_meta = None
    exported = (engine.index.export_snapshot_arrays()
                if engine.config.checkpoint_snapshot_arrays
                and hasattr(engine.index, "export_snapshot_arrays")
                and entries_gen is not None
                else None)
    if exported is not None:
        arrays, snap_names, snap_gen = exported
        pos = {name: i for i, name in enumerate(d.name for d in entries)}
        # the gen token proves the doc table (docs.npz) and the exported
        # snapshot describe the SAME corpus: a concurrent re-ingest of
        # an existing name + commit between the two reads would pass the
        # name-set guard while the contents diverged
        if (snap_gen == entries_gen and len(snap_names) == n
                and all(nm in pos for nm in snap_names)):
            arrays["name_order"] = np.fromiter(
                (pos[nm] for nm in snap_names), np.int64, n)
            storage.savez(os.path.join(build, "snapshot.npz"), **arrays)
            snap_meta = {"score_signature": _score_signature(engine),
                         "kind": "shard"}
    # segment-level full-state payload (streaming mode fast restore,
    # VERDICT r4 #5): same gen-token consistency discipline
    full = (engine.index.export_full_state()
            if engine.config.checkpoint_snapshot_arrays
            and hasattr(engine.index, "export_full_state")
            and entries_gen is not None
            else None)
    if full is not None:
        arrays, full_gen = full
        if full_gen == entries_gen:
            storage.savez(os.path.join(build, "segstate.npz"), **arrays)
            snap_meta = {"score_signature": _score_signature(engine),
                         "kind": "segments"}
    storage.write_bytes(os.path.join(build, "meta.json"), json.dumps({
        "format_version": FORMAT_VERSION,
        "model": engine.model.kind,
        "num_docs": n,
        "nnz": nnz,
        "vocab_size": len(engine.vocab),
        "snapshot": snap_meta,
        "embedding": emb_meta,
        # tier residency at save time (ISSUE 18) — informational: a
        # restore reinstalls everything resident and the first tier
        # rebalance re-spills to whatever budget the RUNNING config
        # sets; the checkpoint never pins the old residency split
        "tier": engine.tier_stats(),
        # wall-clock save time: serve's boot re-walk only re-ingests
        # files modified after this (minus slack), keeping the
        # reference's rebuild-from-documents property without paying
        # a full re-analysis after every restart
        "created_at": time.time(),
    }).encode())
    # seal + publish the version dir: manifest, fsync everything,
    # atomic rename build -> .v<N> (crash => complete-or-absent)
    storage.write_manifest(build, fsync=False)   # publish_dir fsyncs all
    storage.publish_dir(build, vdir)
    fault_point("checkpoint.pre_publish")   # crash window for fault tests
    # Atomic publish: swing the symlink in one os.replace. <base> always
    # resolves to a complete checkpoint, before and after.
    link_tmp = f"{base}.lnk.tmp"
    if os.path.lexists(link_tmp):
        os.remove(link_tmp)
    os.symlink(os.path.basename(vdir), link_tmp)
    if os.path.isdir(base) and not os.path.islink(base):
        # migrate a pre-symlink-format checkpoint out of the way first
        storage.replace(base, f"{base}.v0")
        existing.insert(0, 0)
    storage.replace(link_tmp, base)
    storage.fsync_dir(parent)
    # prune superseded versions only after a successful publish —
    # keeping storage_keep_versions total (the fresh one + fallbacks
    # restore_checkpoint can quarantine into)
    keep = max(1, engine.config.storage_keep_versions)
    prune = existing[:-(keep - 1)] if keep > 1 else existing
    for v in prune:
        shutil.rmtree(f"{base}.v{v}", ignore_errors=True)
    log.info("checkpoint saved", dir=directory, docs=n, nnz=nnz,
             version=version)


def _restore_dense(engine: Engine, directory: str, meta: dict,
                   names: list, offsets, term_ids, tfs) -> None:
    """Repopulate the embedding column. Fast path: install the stored
    rows when the checkpoint's embedding signature (model, dim) matches
    the running config. Fallback (legacy checkpoint, signature change):
    re-embed every document from the checkpoint's own term table —
    ``vocab.txt`` line ``i`` IS term id ``i``, so ``term_ids``/``tfs``
    reconstruct exactly the analyzer's token->tf counts the embedder
    consumed at ingest. Either way the column is rebuilt, never
    silently stale."""
    if engine.dense is None:
        return
    emb_path = os.path.join(directory, "embeddings.npz")
    want = engine.dense.embedder.signature()
    if meta.get("embedding") == want and os.path.exists(emb_path):
        data = np.load(emb_path)
        engine.dense.install_arrays(
            data["rows"], [names[i] for i in data["name_idx"]])
        engine.dense.commit()
        return
    global_metrics.inc("checkpoint_dense_reembeds")
    with open(os.path.join(directory, "vocab.txt"),
              encoding="utf-8") as f:
        terms = f.read().splitlines()
    lo_list = offsets[:-1].tolist()
    hi_list = offsets[1:].tolist()
    for i, name in enumerate(names):
        ids = term_ids[lo_list[i]:hi_list[i]]
        weights = tfs[lo_list[i]:hi_list[i]]
        engine.dense.upsert(
            name, {terms[int(t)]: float(w)
                   for t, w in zip(ids, weights)})
    engine.dense.commit()


def _snapshot_arrays(path: str, format_version: int):
    """``snapshot.npz`` as ``install_snapshot_arrays`` takes it: the
    file itself, or for a version 1 checkpoint its arrays with every
    ELL block turned from ``[rows_cap, width]`` to width-major."""
    data = np.load(path)
    if format_version != _ROW_MAJOR_VERSION or "n_blocks" not in data:
        return data
    arrays = {name: data[name] for name in data.files}
    for i in range(int(arrays["n_blocks"])):
        for name in (f"ell_imp_{i}", f"ell_term_{i}"):
            arrays[name] = np.ascontiguousarray(arrays[name].T)
    return arrays


def load_checkpoint(directory: str, config: Config | None = None,
                    verify: bool = True) -> Engine:
    """Load one checkpoint version (``directory`` may be the published
    symlink). ``verify`` gates the manifest integrity check — a torn or
    bit-rotted file raises :class:`~tfidf_tpu.utils.storage.
    StorageCorruption` instead of restoring silently wrong state; use
    :func:`restore_checkpoint` for the fallback-aware boot path."""
    if verify:
        problems = storage.verify_manifest(directory)
        if problems:
            raise storage.StorageCorruption(
                f"checkpoint {directory} failed integrity check: "
                + "; ".join(problems))
    with open(os.path.join(directory, "meta.json"), encoding="utf-8") as f:
        meta = json.load(f)
    if meta["format_version"] not in (_ROW_MAJOR_VERSION, FORMAT_VERSION):
        raise ValueError(f"unknown checkpoint format {meta['format_version']}")
    config = config or Config()
    if meta["model"] != config.model:
        config = config.replace(model=meta["model"])
    engine = Engine(config)
    # populate the engine's OWN vocabulary (which may be native-backed) so
    # later ingests through either path see the restored terms
    engine.vocab.load_into(os.path.join(directory, "vocab.txt"))
    data = np.load(os.path.join(directory, "docs.npz"))
    with open(os.path.join(directory, "names.json"), encoding="utf-8") as f:
        names = json.load(f)
    offsets = data["offsets"]
    term_ids = data["term_ids"]
    tfs = data["tfs"]
    lengths = data["lengths"]
    # segment-level fast path (streaming mode): rebuild the committed
    # segment list from segstate.npz — device work is pure uploads, no
    # O(corpus) host re-layout, no per-doc replay
    seg_path = os.path.join(directory, "segstate.npz")
    snap_meta_pre = meta.get("snapshot") or {}
    if (snap_meta_pre.get("kind") == "segments"
            and os.path.exists(seg_path)
            and hasattr(engine.index, "install_full_state")
            and snap_meta_pre.get("score_signature")
            == _score_signature(engine)):
        from tfidf_tpu.engine.index import entries_from_packed
        entries, _arrays = entries_from_packed(names, offsets, term_ids,
                                               tfs, lengths)
        engine.index.install_full_state(np.load(seg_path), entries)
        engine.commit()
        _restore_dense(engine, directory, meta, names, offsets,
                       term_ids, tfs)
        log.info("checkpoint loaded", dir=directory, docs=len(names),
                 fast_snapshot="segments")
        return engine
    # bulk restore: docs.npz already stores exactly the packed arrays
    # the index wants. Indexes with a packed loader (ShardIndex) take
    # them whole — no per-document Python loop, and the following
    # commit builds its COO vectorized from the same arrays
    # (VERDICT r2 #8a, r3 #5); other index kinds replay per-doc views
    # through the array-ingest path.
    if hasattr(engine.index, "bulk_load_packed"):
        engine.index.bulk_load_packed(names, offsets, term_ids, tfs,
                                      lengths)
    else:
        add = engine.index.add_document_arrays
        lo_list = offsets[:-1].tolist()
        hi_list = offsets[1:].tolist()
        len_list = lengths.tolist()
        for i, name in enumerate(names):
            add(name, term_ids[lo_list[i]:hi_list[i]],
                tfs[lo_list[i]:hi_list[i]], len_list[i])
    # fast path: re-upload the checkpointed snapshot arrays instead of
    # re-running the O(corpus) host layout — only when the scoring
    # config matches what the arrays were built under, and the vocab
    # capacity agrees with the stored df (a bigger live vocab needs a
    # rebuilt snapshot)
    snap_path = os.path.join(directory, "snapshot.npz")
    installed = False
    snap_meta = meta.get("snapshot")
    if (snap_meta is not None and os.path.exists(snap_path)
            and hasattr(engine.index, "install_snapshot_arrays")
            and snap_meta.get("score_signature")
            == _score_signature(engine)):
        data = _snapshot_arrays(snap_path, meta["format_version"])
        if int(data["df"].shape[0]) == engine.vocab.capacity():
            snap_names = [names[i] for i in data["name_order"]]
            engine.index.install_snapshot_arrays(data, snap_names)
            installed = True
    if not installed:
        engine.commit()
    _restore_dense(engine, directory, meta, names, offsets, term_ids,
                   tfs)
    log.info("checkpoint loaded", dir=directory, docs=len(names),
             fast_snapshot=installed)
    return engine


def checkpoint_versions(base: str) -> list[str]:
    """Candidate version dirs for ``base``, newest-first: the published
    symlink target leads (the save order's source of truth), then the
    remaining ``.v<N>`` siblings by descending version."""
    base = base.rstrip("/")
    parent = os.path.dirname(os.path.abspath(base)) or "."
    prefix = os.path.basename(base) + ".v"
    out: list[str] = []
    if os.path.islink(base):
        target = os.path.join(parent, os.readlink(base))
        if os.path.isdir(target):
            out.append(target)
    elif os.path.isdir(base):
        out.append(base)   # pre-symlink-format checkpoint
    if os.path.isdir(parent):
        versions = sorted(
            (int(d[len(prefix):]) for d in os.listdir(parent)
             if d.startswith(prefix) and d[len(prefix):].isdigit()),
            reverse=True)
        for v in versions:
            vdir = os.path.join(parent, f"{os.path.basename(base)}.v{v}")
            if vdir not in out:
                out.append(vdir)
    return out


def quarantine_version(vdir: str) -> str:
    """Move a corrupt version dir aside (never delete — the operator
    may want the evidence) so boot, fallback, and pruning stop seeing
    it. Returns the quarantine path."""
    qdir = f"{vdir}.quarantine"
    n = 1
    while os.path.exists(qdir):
        qdir = f"{vdir}.quarantine.{n}"
        n += 1
    os.rename(vdir, qdir)
    global_metrics.inc("checkpoint_quarantined")
    log.warning("checkpoint version quarantined", dir=vdir, moved_to=qdir)
    return qdir


def restore_checkpoint(base: str,
                       config: Config | None = None
                       ) -> tuple[Engine, dict]:
    """Fallback-aware restore: verify and load the newest INTACT
    checkpoint version of ``base``, quarantining every corrupt one
    encountered on the way (metric + trace event). Returns
    ``(engine, meta)``; raises :class:`~tfidf_tpu.utils.storage.
    StorageCorruption` when no intact version exists — a loud refusal,
    never a silent wrong restore (the caller falls back to the
    reference's full re-walk, which needs no checkpoint at all)."""
    candidates = checkpoint_versions(base)
    if not candidates:
        raise FileNotFoundError(f"no checkpoint versions under {base}")
    legacy: list[str] = []
    for vdir in candidates:
        problems = storage.verify_manifest(vdir)
        if problems:
            if all("manifest missing" in p for p in problems):
                # pre-manifest-format checkpoint (in-place upgrade):
                # unverifiable, not evidence of corruption — held as a
                # LAST-RESORT candidate rather than condemned, so an
                # upgrade never quarantines every valid checkpoint and
                # forces a full re-walk
                legacy.append(vdir)
                continue
            global_metrics.inc("checkpoint_fallbacks")
            span_event("checkpoint_fallback", dir=os.path.basename(vdir),
                       problems=len(problems))
            log.warning("checkpoint version corrupt; falling back",
                        dir=vdir, problems=problems[:3])
            quarantine_version(vdir)
            continue
        try:
            with open(os.path.join(vdir, "meta.json"),
                      encoding="utf-8") as f:
                meta = json.load(f)
            return load_checkpoint(vdir, config, verify=False), meta
        except storage.StorageCorruption:
            quarantine_version(vdir)
            continue
    for vdir in legacy:
        try:
            with open(os.path.join(vdir, "meta.json"),
                      encoding="utf-8") as f:
                meta = json.load(f)
            global_metrics.inc("checkpoint_legacy_loads")
            log.warning("loading pre-manifest (unverifiable) legacy "
                        "checkpoint; the next save writes a manifested "
                        "version", dir=vdir)
            return load_checkpoint(vdir, config, verify=False), meta
        except (OSError, ValueError):
            continue
    raise storage.StorageCorruption(
        f"no intact checkpoint version under {base} "
        f"({len(candidates)} candidate(s) quarantined, corrupt, or "
        f"unloadable)")
