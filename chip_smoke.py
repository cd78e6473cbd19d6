#!/usr/bin/env python3
"""chip_smoke.py — does the system still start, and stay, on the chip?

One command that drives the real path on the real device and FAILS when
any quiet way off it was taken. Two stages in turn, one chip-owning
process alive at a time:

* **served** — ``BASELINE.json`` config 2 (100k docs, 200k vocab; cut
  from 2 workers to 1 because there is one chip): ``python -m tfidf_tpu``
  coordinator + leader + router on ``JAX_PLATFORMS=cpu``, one worker on
  ``JAX_PLATFORMS=tpu``. Documents go in through ``/leader/upload-batch``,
  queries through ``/leader/start`` (half via the router) from enough
  concurrent clients that the coalescer ships device-sized batches; then
  the worker is SIGTERMed, started again and asked again — the second
  start must find its executables in the persistent compile cache.
* **engine** — ``BASELINE.json`` config 3 (1M docs, 500k vocab, batched
  top-k) through the library surface in one child: ``Engine``,
  ``add_document_arrays``, ``commit``, ``search_batch``, plus
  ``kernel_parity.py``'s matrix under Mosaic, and peak HBM.

Both stages check top-10 against a plain numpy BM25 computed here, in
the parent, on the same seeded data. The parent never imports jax: a
process that touches jax holds the chip. Everything the verdict rests on
is READ from the worker's ``/api/health`` and ``/api/metrics`` (platform,
device kind, kernel-eligible blocks, interpret flag, fallback / OOM-ladder
/ compile-retry / scatter-failure counters, compiles inside the query
window) or from the engine child's own report — never inferred.

    python chip_smoke.py                 # one chip; exits non-zero without one
    python chip_smoke.py --chips 4       # one mesh worker over four chips
    python chip_smoke.py --rehearse      # tiny sizes on the CPU: debugs this
                                         # script; stamped, never a pass

Stdout is two lines. The last is the verdict, one JSON object with
exactly these keys: ``{"ok": true|false, "device": {"platform": ...,
"kind": ..., "count": ...}}``, the device as jax reports it. The line
before it is the record (stages, ``reduced``, ``failures``, ...,
``"claim": null``), also written whole to ``chiprun_out/``. Seconds in
it are facts of a set-up run on a shared host, not rates. Without an
accelerator, or alone in a directory, nothing is printed to stdout.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from http import client as httplib

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TOP_K = 10
BM25_K1, BM25_B = 1.2, 0.75          # Config defaults (Lucene's)
BUDGET_S = 1150.0                    # the contract allows 1200
STOP_TIMEOUT_S = 60.0                # SIGTERM -> exit, per process

# BASELINE.json configs 2 and 3; corpus shapes: Zipf 1.25 tokens,
# Poisson lengths
FULL = dict(
    served_docs=100_000, served_vocab=200_000, served_len=80,
    served_queries=8192, client_procs=4, clients_per_proc=128,
    query_batch=512,
    engine_docs=1_000_000, engine_vocab=500_000, engine_len=120,
    engine_batches=4, parity_queries=64)
REHEARSAL = dict(
    served_docs=3_000, served_vocab=8_000, served_len=40,
    served_queries=256, client_procs=2, clients_per_proc=16,
    query_batch=32,
    engine_docs=20_000, engine_vocab=20_000, engine_len=40,
    engine_batches=2, parity_queries=16)


def log(msg: str) -> None:
    print(f"[smoke {time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


T0 = time.monotonic()


class SmokeFailure(Exception):
    """A phase could not run to its end; checks that merely came out
    wrong are collected in ``failures`` so one run reports them all."""


def remaining() -> float:
    left = BUDGET_S - (time.monotonic() - T0)
    if left <= 0:
        raise SmokeFailure("out of time budget")
    return left


# --------------------------------------------------------------------------
# seeded data and the plain reference (numpy only)
# --------------------------------------------------------------------------

class Corpus:
    """Documents as a Zipf token stream cut at Poisson lengths, plus the
    same documents as sorted unique (term id, tf) slices — exactly what
    ``add_document_arrays`` takes and what the analyzer makes of the
    ``t<id>`` text."""

    def __init__(self, rng, n_docs: int, vocab: int, avg_len: int) -> None:
        self.n_docs, self.vocab = n_docs, vocab
        lengths = np.clip(rng.poisson(avg_len, n_docs), 5, None) \
            .astype(np.int64)
        total = int(lengths.sum())
        self.tokens = rng.zipf(1.25, size=total) % vocab
        self.tok_off = np.concatenate([[0], np.cumsum(lengths)])
        key = np.repeat(np.arange(n_docs, dtype=np.int64), lengths) \
            * vocab + self.tokens
        key.sort()
        first = np.ones(total, bool)
        first[1:] = key[1:] != key[:-1]
        idx = np.flatnonzero(first)
        self.tfs = np.diff(np.append(idx, total)).astype(np.float32)
        ukey = key[idx]
        self.ids = (ukey % vocab).astype(np.int32)
        self.offsets = np.searchsorted(ukey // vocab,
                                       np.arange(n_docs + 1))
        self.lengths = lengths.astype(np.float32)

    def texts(self) -> list[str]:
        tok = np.array([f"t{i}" for i in range(self.vocab)], dtype=object)
        off = self.tok_off
        return [" ".join(tok[self.tokens[off[i]:off[i + 1]]])
                for i in range(self.n_docs)]


def make_queries(rng, vocab: int, n: int) -> list[str]:
    """``n`` DISTINCT queries of 2-4 Zipf terms (distinct so neither
    the leader's nor the router's result cache answers one)."""
    seen: dict[str, None] = {}
    while len(seen) < n:
        k = int(rng.integers(2, 5))
        seen[" ".join(f"t{w}" for w in rng.zipf(1.25, size=k) % vocab)] \
            = None
    return list(seen)


class Oracle:
    """BM25 top-k by the textbook formula on the same corpus: idf =
    ln(1 + (N - df + .5) / (df + .5)), impact = idf * tf / (tf + k1 *
    (1 - b + b * dl / avgdl)), query weight = term multiplicity.
    Independent of the code under test; holds postings only for the
    terms its queries use."""

    def __init__(self, corpus: Corpus, queries: list[str]) -> None:
        self.n_docs = n = corpus.n_docs
        self.queries = [self._parse(q) for q in queries]
        needed = np.unique(np.concatenate(
            [np.fromiter(q, np.int64) for q in self.queries]))
        df = np.bincount(corpus.ids, minlength=corpus.vocab) \
            .astype(np.float64)
        idf = np.log1p((n - df + 0.5) / (df + 0.5))
        sel = np.flatnonzero(np.isin(corpus.ids, needed))
        row = np.searchsorted(corpus.offsets, sel, side="right") - 1
        term = corpus.ids[sel]
        tf = corpus.tfs[sel].astype(np.float64)
        dl = corpus.lengths[row].astype(np.float64)
        avgdl = float(corpus.lengths.mean(dtype=np.float64))
        impact = idf[term] * tf / (
            tf + BM25_K1 * (1 - BM25_B + BM25_B * dl / avgdl))
        order = np.argsort(term, kind="stable")
        self._term = term[order]
        self._row = row[order]
        self._impact = impact[order]

    @staticmethod
    def _parse(q: str) -> dict[int, int]:
        counts: dict[int, int] = {}
        for tok in q.split():
            counts[int(tok[1:])] = counts.get(int(tok[1:]), 0) + 1
        return counts

    def scores(self, qi: int) -> np.ndarray:
        out = np.zeros(self.n_docs, np.float64)
        for t, c in self.queries[qi].items():
            lo, hi = np.searchsorted(self._term, [t, t + 1])
            out[self._row[lo:hi]] += c * self._impact[lo:hi]
        return out

    def mismatch(self, qi: int, hits: list[tuple[str, float]]) -> str | None:
        """The parity rule: the returned score SET equals the
        oracle's top-k positive scores (tie order free), and every
        returned document scores what the oracle says it scores — at
        f32-vs-f64 tolerance (real bugs are orders of magnitude)."""
        scores = self.scores(qi)
        k = min(TOP_K, self.n_docs)
        want = np.sort(np.partition(scores, -k)[-k:])[::-1]
        want = want[want > 0]
        have = np.asarray([s for _n, s in hits], np.float64)
        if have.shape != want.shape:
            return f"{have.shape[0]} hits, oracle has {want.shape[0]}"
        if not np.allclose(np.sort(have)[::-1], want, rtol=2e-3,
                           atol=1e-4):
            return f"scores {have[:3]} vs oracle {want[:3]}"
        for name, s in hits:
            if not np.isclose(s, scores[int(name[1:])], rtol=2e-3,
                              atol=1e-4):
                return f"doc {name}: {s} vs oracle " \
                       f"{scores[int(name[1:])]}"
        return None


def check_parity(oracle: Oracle, results, what: str,
                 failures: list[str]) -> int:
    bad = [(i, m) for i, hits in enumerate(results)
           if (m := oracle.mismatch(i, hits)) is not None]
    if bad:
        failures.append(f"{what}: top-{TOP_K} differs from the oracle on "
                        f"{len(bad)}/{len(results)} queries, first: "
                        f"query {bad[0][0]}: {bad[0][1]}")
    if not any(results):
        failures.append(f"{what}: every parity query came back empty")
    return len(results) - len(bad)


# --------------------------------------------------------------------------
# children: processes and HTTP
# --------------------------------------------------------------------------

class Fleet:
    """The processes this run starts. Each gets its own log file; each
    is ended with SIGTERM and waited for, and one that needs SIGKILL is
    a failure (a killed worker can leave the chip locked for the next
    stage, and a hang at exit is a bug the chip run exists to find)."""

    def __init__(self, workdir: str, failures: list[str]) -> None:
        self.workdir = workdir
        self.failures = failures
        self.procs: dict[str, subprocess.Popen] = {}

    def _log_path(self, tag: str) -> str:
        return os.path.join(self.workdir, f"{tag}.log")

    def spawn(self, tag: str, argv: list[str], env: dict) -> None:
        with open(self._log_path(tag), "ab") as logf:
            self.procs[tag] = subprocess.Popen(
                argv, env=env, cwd=HERE, stdout=logf, stderr=logf)

    def alive(self, tag: str) -> bool:
        return self.procs[tag].poll() is None

    def tail(self, tag: str, n: int = 25) -> str:
        with open(self._log_path(tag), "rb") as f:
            lines = f.read().decode("utf-8", "replace").splitlines()
        return "\n".join(lines[-n:])

    def wait(self, tag: str) -> None:
        """For a child that ends by itself: wait out the time budget
        for it; anything but exit 0 ends the stage."""
        p = self.procs.pop(tag)
        try:
            rc = p.wait(timeout=remaining())
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            raise SmokeFailure(f"{tag} ran out of the time budget:\n"
                               + self.tail(tag)) from None
        if rc != 0:
            raise SmokeFailure(f"{tag} exited {rc}:\n"
                               + self.tail(tag, 40))

    def stop(self, tag: str) -> float:
        p = self.procs.pop(tag)
        t0 = time.monotonic()
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=STOP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            self.failures.append(
                f"{tag} did not exit within {STOP_TIMEOUT_S:.0f}s of "
                "SIGTERM and was killed")
        return time.monotonic() - t0

    def stop_all(self) -> None:
        for tag in list(self.procs)[::-1]:
            self.stop(tag)


_conns = threading.local()


def call(hp: tuple[str, int], method: str, path: str,
         body: bytes | None = None, timeout: float = 60.0):
    """One request on this thread's keep-alive connection to ``hp``;
    returns (status, headers, body). A connection the server closed is
    reopened once."""
    pool = _conns.__dict__.setdefault("pool", {})
    for attempt in (0, 1):
        conn = pool.get(hp)
        if conn is None:
            conn = pool[hp] = httplib.HTTPConnection(*hp, timeout=timeout)
        try:
            conn.timeout = timeout
            if conn.sock is not None:
                conn.sock.settimeout(timeout)
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), resp.read()
        except (httplib.HTTPException, OSError) as e:
            conn.close()
            pool.pop(hp, None)
            if attempt or isinstance(e, TimeoutError):
                raise
    raise AssertionError("unreachable")


def hits_of(reply: tuple) -> list[tuple[str, float]]:
    """A ``/leader/start`` reply (a doc -> score map) as a hit list,
    best first; a reply that is not a 200 holds no hits."""
    status, _headers, body = reply
    if status != 200:
        return []
    return sorted(json.loads(body).items(), key=lambda kv: -kv[1])


def get_json(hp, path: str, timeout: float = 30.0):
    status, _h, body = call(hp, "GET", path, timeout=timeout)
    if status != 200:
        raise SmokeFailure(f"GET {path} on {hp[1]}: {status} "
                           f"{body[:200]!r}")
    return json.loads(body)


def count(metrics: dict, name: str) -> int:
    """A counter of an ``/api/metrics`` snapshot (absent = never hit)."""
    return int(metrics.get(name, 0))


def wait_until(what: str, pred, timeout: float = 120.0,
               interval: float = 0.25, watch=()) -> None:
    """``watch``: (fleet, tag) pairs that must stay alive meanwhile."""
    deadline = time.monotonic() + min(timeout, remaining())
    last: object = None
    while time.monotonic() < deadline:
        for fleet, tag in watch:
            if not fleet.alive(tag):
                raise SmokeFailure(
                    f"{tag} exited while waiting for {what}:\n"
                    + fleet.tail(tag))
        try:
            if pred():
                return
        except (OSError, httplib.HTTPException, SmokeFailure,
                ValueError) as e:
            last = e
        time.sleep(interval)
    raise SmokeFailure(f"timed out waiting for {what} (last: {last!r})")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def device_platform(rehearse: bool) -> str:
    """The platform every chip-owning child is PINNED to, so that a
    chip it cannot get is fatal instead of a quiet CPU fallback."""
    return "cpu" if rehearse else "tpu"


def child_env(platform: str, **extra: str) -> dict:
    env = dict(os.environ)
    for k in ("XLA_FLAGS", "TFIDF_JAX_PLATFORM", "TFIDF_CPU_DEVICES"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = platform
    env.update(extra)
    return env


def probe_device(platform: str) -> dict:
    """What jax finds, asked of a child that is gone before the next
    one starts. ``platform`` "tpu" makes a missing chip an error."""
    code = ("import jax, json; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    p = subprocess.run([sys.executable, "-c", code],
                       env=child_env(platform), capture_output=True,
                       text=True, timeout=min(300.0, remaining()))
    if p.returncode != 0:
        reason = (p.stderr.strip().splitlines() or ["no output"])[-1]
        raise SmokeFailure(f"no accelerator: {reason[:300]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


# --------------------------------------------------------------------------
# what a worker says about itself
# --------------------------------------------------------------------------

# what /api/health's "compute" block (Engine.compute_stats) says about
# where the compute runs, copied into the record as read
COMPUTE_FACTS = ("platform", "device_kind", "device_count",
                 "kernel_blocks", "posting_blocks", "kernel_interpret",
                 "index_devices", "device_memory")


def check_worker_health(health: dict, metrics: dict, *, rehearse: bool,
                        chips: int, what: str,
                        failures: list[str]) -> None:
    c = health["compute"]
    if c["platform"] != device_platform(rehearse):
        failures.append(f"{what}: platform {c['platform']!r}, not "
                        f"{device_platform(rehearse)!r}")
    if c["kernel_blocks"] < 1:
        failures.append(f"{what}: no committed block rides the Pallas "
                        f"kernel ({c['posting_blocks']} blocks)")
    if c["kernel_interpret"] != rehearse:
        failures.append(f"{what}: kernel_interpret="
                        f"{c['kernel_interpret']}")
    if c["state"] != "healthy" or c["total_faults"]:
        failures.append(f"{what}: compute state {c['state']!r}, "
                        f"{c['total_faults']} faults "
                        f"{c['faults_by_kind']}")
    if not health["native_ingest"]:
        failures.append(f"{what}: native ingest library not loaded "
                        "(config asks for it)")
    for name in ("compute_fallback_served", "compute_oom_backoff",
                 "search_compile_retries", "worker_batch_failures",
                 "compute_poison_outputs"):
        if count(metrics, name):
            failures.append(f"{what}: {name}={count(metrics, name)}")
    if chips > 1:
        if c["device_count"] != chips \
                or len(c["index_devices"]) != chips:
            failures.append(
                f"{what}: index on devices {c['index_devices']} of "
                f"{c['device_count']}, wanted {chips}")
        used = [d["bytes_in_use"] for d in c["device_memory"]]
        if not rehearse and (len(used) != chips
                             or max(used) > 2 * min(used)):
            failures.append(f"{what}: per-device bytes_in_use {used} "
                            "not within 2x of each other")


# --------------------------------------------------------------------------
# served stage — BASELINE.json config 2 on one worker
# --------------------------------------------------------------------------

def served_stage(sz: dict, *, rehearse: bool, chips: int, seed: int,
                 workdir: str, failures: list[str]) -> dict:
    rng = np.random.default_rng(seed)
    t0 = time.monotonic()
    corpus = Corpus(rng, sz["served_docs"], sz["served_vocab"],
                    sz["served_len"])
    texts = corpus.texts()
    n_q = sz["served_queries"]
    B = sz["query_batch"]
    # parity sample + one full-bucket warm-up set + the window
    queries = make_queries(rng, sz["served_vocab"],
                           sz["parity_queries"] + 2 * B + n_q)
    parity_q = queries[:sz["parity_queries"]]
    warm_q = queries[sz["parity_queries"]:sz["parity_queries"] + 2 * B]
    window_q = queries[-n_q:]
    oracle = Oracle(corpus, parity_q)
    log(f"served: corpus {corpus.n_docs} docs / {corpus.ids.shape[0]} "
        f"postings + oracle in {time.monotonic() - t0:.1f}s")
    out: dict = {"docs": corpus.n_docs, "vocab": corpus.vocab,
                 "postings": int(corpus.ids.shape[0]), "query_batch": B}

    fleet = Fleet(workdir, failures)
    host = "127.0.0.1"
    ports = {t: free_port() for t in ("coord", "leader", "router",
                                      "worker")}
    hp = {t: (host, p) for t, p in ports.items()}
    coord = f"{host}:{ports['coord']}"
    tfidf = [sys.executable, "-m", "tfidf_tpu"]
    # the coalescers may ship a whole device batch per RPC; the session
    # timeout is generous because five processes and this load
    # generator share the host's cores
    common = dict(TFIDF_SCATTER_BATCH=str(B), TFIDF_SESSION_TIMEOUT_S="15")
    cpu_env = child_env("cpu", **common)
    worker_env = child_env(device_platform(rehearse),
                           TFIDF_QUERY_BATCH=str(B), **common)
    worker_argv = tfidf + [
        "serve", "--host", host, "--port", str(ports["worker"]),
        "--coordinator-address", coord,
        "--documents-path", f"{workdir}/worker/docs",
        "--index-path", f"{workdir}/worker/index"]
    if chips > 1:
        worker_argv += ["--engine-mode", "mesh"]
        if rehearse:   # N virtual CPU devices stand in for N chips
            worker_env.update(TFIDF_JAX_PLATFORM="cpu",
                              TFIDF_CPU_DEVICES=str(chips))

    def worker_up() -> bool:
        return get_json(hp["leader"], "/api/services") \
            == [f"http://{host}:{ports['worker']}"]

    def worker_state():
        return (get_json(hp["worker"], "/api/health"),
                get_json(hp["worker"], "/api/metrics"))

    def ask_worker(q: str, timeout: float):
        """The reference-API per-query endpoint, straight at the worker
        (a cold commit + compile can outlast the leader's scatter
        timeout). It answers [] on ANY failure, so an empty answer to a
        query with corpus terms is a failure here."""
        status, _h, body = call(
            hp["worker"], "POST", "/worker/process",
            json.dumps({"query": q}).encode(), timeout=timeout)
        if status != 200:
            raise SmokeFailure(f"/worker/process: {status} {body[:200]!r}")
        return [(h["document"]["name"], float(h["score"]))
                for h in json.loads(body)]

    def warm_buckets() -> float:
        """Every power-of-two batch bucket the worker can be handed, so
        the query window compiles nothing: the largest first and alone
        (it ratchets the unique-term capacity the others then share),
        twice with different queries (the capacity must hold), then the
        rest at once — XLA compiles off the GIL, one core each."""
        def bucket(qs: list[str]) -> None:
            status, _h, body = call(
                hp["worker"], "POST", "/worker/process-batch",
                json.dumps({"queries": qs, "k": TOP_K}).encode(),
                timeout=min(600.0, remaining()))
            if status != 200:
                raise SmokeFailure(
                    f"warm-up of bucket {len(qs)}: {status} "
                    f"{body[:300]!r}\n" + fleet.tail("worker"))

        t = time.monotonic()
        bucket(warm_q[:B])
        bucket(warm_q[B:2 * B])
        smaller = [warm_q[:n] for n in
                   (B >> s for s in range(1, B.bit_length()))]
        with concurrent.futures.ThreadPoolExecutor(len(smaller)) as ex:
            list(ex.map(bucket, smaller))
        return time.monotonic() - t

    try:
        fleet.spawn("coord", tfidf + ["coordinator", "--listen", coord],
                    cpu_env)
        wait_until("coordinator", lambda: socket.create_connection(
            hp["coord"], timeout=1).close() or True,
            watch=[(fleet, "coord")])
        fleet.spawn("leader", tfidf + [
            "serve", "--host", host, "--port", str(ports["leader"]),
            "--coordinator-address", coord,
            "--documents-path", f"{workdir}/leader/docs",
            "--index-path", f"{workdir}/leader/index"], cpu_env)
        wait_until("leader election", lambda: call(
            hp["leader"], "GET", "/api/status")[2] == b"I am the leader",
            watch=[(fleet, "leader")])
        fleet.spawn("router", tfidf + [
            "router", "--coordinator", coord, "--host", host,
            "--port", str(ports["router"])], cpu_env)
        t_start = time.monotonic()
        fleet.spawn("worker", worker_argv, worker_env)
        wait_until("worker registration", worker_up, timeout=300.0,
                   watch=[(fleet, "worker")])
        out["worker_start_s"] = round(time.monotonic() - t_start, 1)
        wait_until("router", lambda: get_json(
            hp["router"], "/api/health")["ok"], watch=[(fleet, "router")])

        # ---- ingest through the leader ----
        docs = [{"name": f"d{i}", "text": t} for i, t in enumerate(texts)]
        groups = [json.dumps(docs[g:g + 500]).encode()
                  for g in range(0, len(docs), 500)]
        del docs, texts

        def upload(body: bytes) -> int:
            status, _h, resp = call(hp["leader"], "POST",
                                    "/leader/upload-batch", body,
                                    timeout=min(600.0, remaining()))
            if status != 200:
                raise SmokeFailure(f"upload-batch: {status} "
                                   f"{resp[:300]!r}")
            r = json.loads(resp)
            if r.get("errors") or r.get("skipped"):
                raise SmokeFailure(f"upload-batch: {str(r)[:300]}")
            return sum(r["placed"].values())

        t = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            placed = sum(ex.map(upload, groups))
        out["upload_s"] = round(time.monotonic() - t, 1)
        if placed != corpus.n_docs:
            failures.append(f"served: {placed} of {corpus.n_docs} "
                            "documents placed")
        log(f"served: {placed} docs uploaded in {out['upload_s']}s")

        # ---- first answer: commit + compile, reported as set-up ----
        t = time.monotonic()
        first = ask_worker(parity_q[0], timeout=min(900.0, remaining()))
        out["cold_first_answer_s"] = round(time.monotonic() - t, 1)
        if not first:
            raise SmokeFailure(
                "the first /worker/process answer is empty (that "
                "endpoint swallows search failures):\n"
                + fleet.tail("worker"))
        health, metrics = worker_state()
        check_worker_health(health, metrics, rehearse=rehearse,
                            chips=chips, what="served/first answer",
                            failures=failures)
        if failures:   # no point driving load at a worker off the chip
            raise SmokeFailure("worker failed its first health check")
        out["cold_buckets_s"] = round(warm_buckets(), 1)
        log(f"served: first answer {out['cold_first_answer_s']}s, "
            f"bucket warm-up {out['cold_buckets_s']}s")

        # the router follows the placement znode; wait until it
        # answers as the leader does
        def router_caught_up() -> bool:
            a = call(hp["leader"], "POST", "/leader/start",
                     warm_q[0].encode())
            b = call(hp["router"], "POST", "/leader/start",
                     warm_q[0].encode())
            return a[0] == b[0] == 200 and json.loads(a[2]) \
                and json.loads(a[2]) == json.loads(b[2])
        wait_until("router view", router_caught_up, timeout=60.0)

        # ---- the query window ----
        # Closed-loop clients in several PROCESSES, so the generator's
        # own GIL is not what paces the doors. How large the coalesced
        # batches get is reported, not judged: on a local v5e the
        # Python front doors, not the chip, set it (PERF.md: 10.7 from
        # 512 clients, 26.6 from 1024 at a third of the throughput).
        doors = [hp["leader"], hp["router"]]
        procs = sz["client_procs"]
        for p in range(procs):
            with open(os.path.join(workdir, f"client{p}.json"), "w") as f:
                json.dump({"doors": doors, "queries": window_q[p::procs],
                           "threads": sz["clients_per_proc"],
                           "deadline_s": min(300.0, remaining())}, f)
        _h0, m0 = worker_state()
        front0 = [get_json(d, "/api/metrics") for d in doors]
        t = time.monotonic()
        for p in range(procs):
            fleet.spawn(f"client{p}", [
                sys.executable, os.path.abspath(__file__),
                "--client-child", os.path.join(workdir, f"client{p}.json")],
                child_env("cpu"))
        replies: list = []
        for p in range(procs):
            fleet.wait(f"client{p}")
            with open(os.path.join(workdir, f"client{p}.json.out")) as f:
                replies += json.load(f)
        out["window_s"] = round(time.monotonic() - t, 1)
        health, m1 = worker_state()
        front1 = [get_json(d, "/api/metrics") for d in doors]
        bad_status = sorted({s for s, _f, _e in replies if s != 200})
        flagged = sorted({f for _s, fl, _e in replies for f in fl})
        empty = sum(1 for s, _f, e in replies if s == 200 and e)
        if len(replies) != n_q:
            failures.append(f"served: {len(replies)} replies to {n_q} "
                            "window queries")
        if bad_status:
            failures.append(f"served: reply statuses {bad_status} in the "
                            "query window")
        if flagged:
            failures.append(f"served: replies carried {flagged}")
        if empty > n_q // 2:
            failures.append(f"served: {empty}/{n_q} replies empty")
        compiles = count(m1, "xla_compiles") - count(m0, "xla_compiles")
        if compiles:
            failures.append(f"served: {compiles} XLA compile(s) inside "
                            "the query window after warm-up")

        def delta(name: str) -> int:   # the leader's + the router's
            return sum(count(f1, k) - count(f0, k)
                       for f0, f1 in zip(front0, front1)
                       for k in (f"scatter_{name}",
                                 f"router_scatter_{name}"))
        scatter_failures = sum(count(f1, "scatter_failures")
                               for f1 in front1)
        if scatter_failures:
            failures.append(f"served: scatter_failures={scatter_failures}")
        served = count(m1, "dispatch_queries") - count(m0,
                                                       "dispatch_queries")
        if served < n_q:
            failures.append(f"served: the worker scored {served} of "
                            f"{n_q} window queries")
        mean_batch = delta("items") / max(delta("batches"), 1)
        if mean_batch < 2:
            failures.append(f"served: mean scatter batch {mean_batch:.1f} "
                            "— concurrent queries were not coalesced")
        out.update(window_queries=n_q,
                   clients=procs * sz["clients_per_proc"],
                   mean_scatter_batch=round(mean_batch, 1),
                   mean_worker_batch=round(served / max(
                       count(m1, "worker_batch_search_count")
                       - count(m0, "worker_batch_search_count"), 1), 1),
                   compiles_in_window=compiles,
                   scatter_failures=scatter_failures)
        check_worker_health(health, m1, rehearse=rehearse, chips=chips,
                            what="served/after window", failures=failures)

        # ---- parity, through both doors ----
        results = [hits_of(call(doors[i % 2], "POST", "/leader/start",
                                q.encode()))
                   for i, q in enumerate(parity_q)]
        out["parity_ok"] = check_parity(oracle, results, "served",
                                        failures)
        out.update(
            {k: health["compute"][k] for k in COMPUTE_FACTS},
            native_ingest=health["native_ingest"],
            ingest_native_fast_path=count(m1, "ingest_native_fast_path"),
            ingest_python_fallback=count(m1, "ingest_python_fallback"),
            cold_cache_hits=count(m1, "compile_cache_hits"),
            cold_cache_misses=count(m1, "compile_cache_misses"))
        log(f"served: window {n_q} queries in {out['window_s']}s, mean "
            f"scatter batch {out['mean_scatter_batch']}, parity "
            f"{out['parity_ok']}/{len(parity_q)}")

        # ---- SIGTERM the worker, start it again, ask again ----
        out["worker_stop_s"] = round(fleet.stop("worker"), 1)
        wait_until("worker deregistration", lambda: get_json(
            hp["leader"], "/api/services") == [], timeout=60.0)
        t_start = time.monotonic()
        fleet.spawn("worker", worker_argv, worker_env)
        wait_until("worker registration after restart", worker_up,
                   timeout=600.0, watch=[(fleet, "worker")])
        out["worker_restart_s"] = round(time.monotonic() - t_start, 1)
        t = time.monotonic()
        again = ask_worker(parity_q[0], timeout=min(900.0, remaining()))
        out["warm_first_answer_s"] = round(time.monotonic() - t, 1)
        if sorted(again) != sorted(first):
            failures.append("served: the restarted worker's first answer "
                            "differs from the first start's")
        out["warm_buckets_s"] = round(warm_buckets(), 1)
        health, m2 = worker_state()
        check_worker_health(health, m2, rehearse=rehearse, chips=chips,
                            what="served/after restart", failures=failures)
        out["warm_cache_hits"] = count(m2, "compile_cache_hits")
        out["warm_cache_misses"] = count(m2, "compile_cache_misses")
        # (a CPU-pinned process keeps no default cache: not rehearsed)
        if out["warm_cache_hits"] < 1 and not rehearse:
            failures.append("served: the restarted worker reports no "
                            "persistent compile-cache hit")
        # "shorter" is owed only where the first start really compiled
        if out["cold_cache_misses"] and (
                out["warm_buckets_s"] >= out["cold_buckets_s"]):
            failures.append(
                f"served: bucket warm-up took {out['warm_buckets_s']}s "
                f"after the restart, {out['cold_buckets_s']}s before")
        if oracle.mismatch(1, hits_of(call(
                hp["leader"], "POST", "/leader/start",
                parity_q[1].encode(), timeout=120.0))) is not None:
            failures.append("served: wrong answer through the leader "
                            "after the worker's restart")
        log(f"served: restart {out['worker_restart_s']}s, first answer "
            f"{out['warm_first_answer_s']}s (cold "
            f"{out['cold_first_answer_s']}s), buckets "
            f"{out['warm_buckets_s']}s (cold {out['cold_buckets_s']}s), "
            f"cache hits {out['warm_cache_hits']}")
    except SmokeFailure:
        for tag in fleet.procs:
            log(f"--- {tag} log tail ---\n{fleet.tail(tag, 15)}")
        raise
    finally:
        fleet.stop_all()
    return out


# --------------------------------------------------------------------------
# engine stage — BASELINE.json config 3 through the library surface
# --------------------------------------------------------------------------

def engine_data(sz: dict, seed: int) -> tuple[Corpus, list[str]]:
    """The engine stage's corpus and queries. About a minute of numpy
    at full size, so ``main`` starts it on a thread beside the served
    stage (sampling and sorting run off the GIL)."""
    rng = np.random.default_rng(seed + 1)
    corpus = Corpus(rng, sz["engine_docs"], sz["engine_vocab"],
                    sz["engine_len"])
    del corpus.tokens, corpus.tok_off      # ids/tfs are what it ingests
    queries = make_queries(
        rng, sz["engine_vocab"], sz["parity_queries"]
        + (2 + sz["engine_batches"]) * sz["query_batch"])
    return corpus, queries


def engine_stage(sz: dict, *, rehearse: bool, chips: int, data,
                 workdir: str, failures: list[str]) -> dict:
    t0 = time.monotonic()
    corpus, queries = data.result(timeout=remaining())
    B = sz["query_batch"]
    parity_q = queries[:sz["parity_queries"]]
    for name in ("offsets", "ids", "tfs", "lengths"):
        np.save(os.path.join(workdir, f"{name}.npy"),
                getattr(corpus, name))
    job = {"vocab": corpus.vocab, "query_batch": B, "chips": chips,
           "rehearse": rehearse, "parity_queries": parity_q,
           "queries": queries[sz["parity_queries"]:],
           "timed_batches": sz["engine_batches"]}
    with open(os.path.join(workdir, "job.json"), "w") as f:
        json.dump(job, f)
    log(f"engine: corpus {corpus.n_docs} docs / {corpus.ids.shape[0]} "
        f"postings ready and saved after {time.monotonic() - t0:.1f}s")

    fleet = Fleet(workdir, failures)
    try:
        fleet.spawn("engine", [sys.executable, os.path.abspath(__file__),
                               "--engine-child", workdir],
                    child_env(device_platform(rehearse)))
        # the reference is computed while the child builds its index
        oracle = Oracle(corpus, parity_q)
        fleet.wait("engine")
    finally:
        fleet.stop_all()
    with open(os.path.join(workdir, "report.json")) as f:
        rep = json.load(f)
    out = {"docs": corpus.n_docs, "vocab": corpus.vocab,
           "postings": int(corpus.ids.shape[0]), "query_batch": B,
           **rep["facts"],
           **{k: rep["compute"][k] for k in COMPUTE_FACTS}}
    results = [[(n, float(s)) for n, s in hits] for hits in rep["hits"]]
    out["parity_ok"] = check_parity(oracle, results, "engine", failures)
    check_worker_health(
        {"compute": rep["compute"],
         "native_ingest": rep["facts"]["native_ingest"]},
        rep["metrics"], rehearse=rehearse, chips=chips, what="engine",
        failures=failures)
    if rep["facts"]["compiles_in_window"]:
        failures.append(f"engine: {rep['facts']['compiles_in_window']} "
                        "XLA compile(s) inside the search window")
    kp = rep["kernel_parity"]
    if not kp["all_ok"] or kp["mosaic_compiled"] == rehearse:
        failures.append(
            "engine: kernel parity matrix "
            f"all_ok={kp['all_ok']} mosaic={kp['mosaic_compiled']}: "
            + str([c["name"] for c in kp["cases"] if not c["ok"]]))
    out["kernel_parity_cases"] = len(kp["cases"])
    out["kernel_parity_ok"] = kp["all_ok"]
    out["kernel_parity"] = kp      # kept whole in the artifact only
    log(f"engine: {json.dumps(rep['facts'])}")
    return out


def engine_child(workdir: str) -> int:
    """The one process of the engine stage that touches jax."""
    with open(os.path.join(workdir, "job.json")) as f:
        job = json.load(f)
    import jax
    if job["rehearse"] and job["chips"] > 1:
        jax.config.update("jax_num_cpu_devices", job["chips"])
    sys.path.insert(0, HERE)
    import kernel_parity
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.compile_cache import configure_compile_cache
    from tfidf_tpu.utils.config import Config
    from tfidf_tpu.utils.metrics import global_metrics

    configure_compile_cache()
    facts: dict = {}

    def timed(name: str, t0: float) -> None:
        facts[name] = round(time.monotonic() - t0, 1)
        log(f"engine child: {name} {facts[name]}s")

    offsets, ids, tfs, lengths = (
        np.load(os.path.join(workdir, f"{n}.npy"))
        for n in ("offsets", "ids", "tfs", "lengths"))
    B = job["query_batch"]
    cfg = Config(query_batch=B)
    if job["chips"] > 1:
        cfg = cfg.replace(engine_mode="mesh")
    engine = Engine(cfg)
    t = time.monotonic()
    for i in range(job["vocab"]):
        engine.vocab.add(f"t{i}")
    add = engine.index.add_document_arrays
    for i in range(offsets.shape[0] - 1):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
    timed("ingest_s", t)
    t = time.monotonic()
    engine.commit()
    timed("commit_s", t)
    # warm up on the batches with the most distinct terms: the compiled
    # program is sized by a high-water mark of that count, and a window
    # batch that raised it would recompile
    batches = [job["queries"][lo:lo + B]
               for lo in range(0, len(job["queries"]), B)]
    batches.sort(key=lambda b: -len({t for qs in b for t in qs.split()}))
    q = [qs for b in batches for qs in b]
    t = time.monotonic()
    engine.search_batch(q[:B], k=TOP_K)
    timed("first_search_s", t)
    engine.search_batch(q[B:2 * B], k=TOP_K)
    c0 = global_metrics.get("xla_compiles", 0)
    t = time.monotonic()
    got = engine.search_batch(q[2 * B:(2 + job["timed_batches"]) * B],
                              k=TOP_K)
    timed("window_s", t)
    facts["window_queries"] = len(got)
    facts["compiles_in_window"] = int(
        global_metrics.get("xla_compiles", 0) - c0)
    hits = [[(h.name, h.score) for h in hs]
            for hs in engine.search_batch(job["parity_queries"],
                                          k=TOP_K)]
    compute = engine.compute_stats()
    facts["peak_hbm_bytes"] = max(
        (d["peak_bytes_in_use"] for d in compute["device_memory"]),
        default=None)
    facts["native_ingest"] = engine.native is not None
    del engine, got
    t = time.monotonic()
    kp = kernel_parity.run_matrix()
    timed("kernel_parity_s", t)
    m = global_metrics.snapshot()
    facts["cache_hits"] = count(m, "compile_cache_hits")
    facts["cache_misses"] = count(m, "compile_cache_misses")
    with open(os.path.join(workdir, "report.json"), "w") as f:
        json.dump({"facts": facts, "hits": hits, "compute": compute,
                   "metrics": m, "kernel_parity": kp}, f)
    return 0


def client_child(job_path: str) -> int:
    """One load-generator process: ``threads`` closed-loop clients over
    keep-alive connections, alternating the two front doors. Writes
    ``[status, degraded/fault headers, empty?]`` per query."""
    with open(job_path) as f:
        job = json.load(f)
    doors = [tuple(d) for d in job["doors"]]
    queries = job["queries"]

    def start(i: int):
        status, headers, body = call(
            doors[i % len(doors)], "POST", "/leader/start",
            queries[i].encode(), timeout=job["deadline_s"])
        flags = [h for h in ("X-Compute-Degraded", "X-Scatter-Degraded",
                             "X-Compute-Fault") if h in headers]
        return status, flags, status == 200 and not json.loads(body)

    with concurrent.futures.ThreadPoolExecutor(job["threads"]) as ex:
        replies = list(ex.map(start, range(len(queries))))
    with open(job_path + ".out", "w") as f:
        json.dump(replies, f)
    return 0


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the worker and the engine run "
                         "engine_mode=mesh over four devices")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on JAX_PLATFORMS=cpu with the "
                         "interpret-mode kernel; stamped, never a pass")
    ap.add_argument("--stages", default="served,engine",
                    help="comma list of served,engine (a partial run "
                         "is never ok)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine-child", metavar="DIR",
                    help=argparse.SUPPRESS)
    ap.add_argument("--client-child", metavar="JOB",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.engine_child:
        return engine_child(args.engine_child)
    if args.client_child:
        return client_child(args.client_child)

    if not os.path.isdir(os.path.join(HERE, "tfidf_tpu")):
        print("chip_smoke: no tfidf_tpu package beside this script — "
              "nothing to smoke", file=sys.stderr)
        return 1
    sz = REHEARSAL if args.rehearse else FULL
    stages = [s for s in ("served", "engine")
              if s in args.stages.split(",")]
    failures: list[str] = []
    try:
        device = probe_device(device_platform(args.rehearse))
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    log(f"device: {device}")
    result: dict = {
        "ok": False, "device": device, "rehearsal": args.rehearse,
        "chips": args.chips, "seed": args.seed,
        # cuts from the BASELINE.json configs, each with its reason
        "reduced": (
            ["served: 1 worker, not config 2's 2 — one process per chip"
             if args.chips == 1 else
             "served: 1 mesh worker over 4 chips, not config 2's 2 "
             "workers — four one-chip workers need device pinning the "
             "engine lacks (ROADMAP R5b)"]
            + (["rehearsal: every size cut to debug on the CPU"]
               if args.rehearse else [])
            + ([f"stages: only {stages}"]
               if stages != ["served", "engine"] else [])),
    }
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    side = concurrent.futures.ThreadPoolExecutor(1)
    try:
        data = side.submit(engine_data, sz, args.seed) \
            if "engine" in stages else None
        for stage in stages:
            sub = os.path.join(workdir, stage)
            os.makedirs(sub)
            common = dict(rehearse=args.rehearse, chips=args.chips,
                          workdir=sub, failures=failures)
            result[stage] = (
                served_stage(sz, seed=args.seed, **common)
                if stage == "served" else
                engine_stage(sz, data=data, **common))
    except SmokeFailure as e:
        failures.append(str(e))
    except Exception as e:   # the verdict line must still be printed
        traceback.print_exc()
        failures.append(f"{type(e).__name__}: {e}")
    finally:
        side.shutdown(wait=True, cancel_futures=True)
        shutil.rmtree(workdir, ignore_errors=True)
    assert "jax" not in sys.modules, "the parent must stay off jax"
    complete = stages == ["served", "engine"]
    passed = not failures and complete
    if args.rehearse:
        result["rehearsal_passed"] = passed
    else:
        result["ok"] = passed
    result["failures"] = failures
    result["seconds"] = round(time.monotonic() - T0, 1)
    result["claim"] = None
    # the whole record (kernel matrix included) where the chip tool
    # brings it back; the record line on stdout carries everything but that
    art_dir = os.path.join(HERE, "chiprun_out")
    try:
        os.makedirs(art_dir, exist_ok=True)
        name = "chip_smoke_rehearsal.json" if args.rehearse else \
            f"chip_smoke_{args.chips}chip.json"
        with open(os.path.join(art_dir, name), "w") as f:
            json.dump(result, f, indent=1)
    except OSError as e:
        log(f"could not write the artifact: {e}")
    result.get("engine", {}).pop("kernel_parity", None)
    for msg in failures:
        print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    # two stdout lines: the record, then the verdict whose keys are
    # exactly these two — what reads the last line gets nothing else
    print(json.dumps(result))
    print(json.dumps({"ok": result["ok"], "device": device}), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
