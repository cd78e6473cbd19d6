#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json`` (repo root): a
configuration under a traffic mix. Everything that belongs to one
configuration, one mix or one metric is a data file found by name —
``benchmarks/configs/<config>.json``, ``benchmarks/traffic/<mix>.json``,
``benchmarks/metrics/<metric>.json`` — so a later PR adds cells as files
and edits none (``benchmarks/README.md``).

This process never imports jax (asserted before it exits): a process that
touches jax holds the chip. It makes corpus and queries from ``--seed``,
computes the plain float64 reference (``lib/oracle.py``), starts the
processes (``lib/worker_main.py`` holds the chip; coordinator and leader
are the program's own ``python -m tfidf_tpu`` entry points on the CPU),
warms up only the cell's own shapes, measures for ``--seconds``, and
prints progress lines and then ONE JSON object as the last line of
stdout: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, traced, ``breakdown``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

It exits non-zero and prints no result line when: there is no TPU; a
worker reports anything but ``platform: tpu``, an interpreted kernel or
no kernel-eligible block; ``compute_fallback_served``,
``compute_oom_backoff`` (or a compile retry, a failed worker batch, a
scatter failure) is non-zero; XLA compiled anything inside the window; a
child had to be killed; or the program is not beside it.

``--rehearse`` (benchmark-only) runs the same code end to end at a tiny
size on ``JAX_PLATFORMS=cpu`` to debug the harness. Its line says
``"rehearsal": true`` and platform ``cpu``: it is never a number, and no
measuring run accepts it.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import queue
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LIB = os.path.join(HERE, "lib")
sys.path.insert(0, LIB)

import data  # noqa: E402
import genstats  # noqa: E402
import oracle as oracle_mod  # noqa: E402
import readers  # noqa: E402
import xtrace  # noqa: E402
from fleet import (BenchFailure, Fleet, PauseWatch, T0, call,  # noqa: E402
                   child_env, free_port, get_json, log, wait_until)

SAMPLE_ANSWERS = 64          # answers of the window compared per run
KEEP_ONE_IN = 32             # share of served replies the generators keep
LEADER_WARM_QUERIES = 16     # the front door's own first requests
MIN_COMPARED = 16
TRACE_SECONDS = 3.0          # of the window, from a quarter in
CHILD_READY_TIMEOUT_S = 1000.0
# /api/metrics counters that must stay 0 on the chip-owning worker
WORKER_ZERO = ("compute_fallback_served", "compute_oom_backoff",
               "search_compile_retries", "worker_batch_failures",
               "compute_poison_outputs")


# --------------------------------------------------------------------------
# the data files
# --------------------------------------------------------------------------

def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchFailure(f"no such file: {os.path.relpath(path, ROOT)}") \
            from None


def rehearsed(spec: dict, rehearse: bool) -> dict:
    """A data file's numbers, with its ``rehearse`` block laid over them
    in a rehearsal."""
    out = {k: v for k, v in spec.items() if k != "rehearse"}
    if rehearse:
        out.update(spec.get("rehearse", {}))
    return out


def cell_metrics(bench: dict, cell: str, traced: bool) -> dict[str, dict]:
    """name -> the metric's data file, for the metrics this cell reports
    in this kind of run (an entry without ``workloads`` is every
    cell's)."""
    entries = bench["per_layer" if traced else "end_to_end"]
    return {m["name"]: load_json(HERE, "metrics", m["name"] + ".json")
            for m in entries
            if cell in m.get("workloads", [cell])}


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------

class ChipChild:
    """The chip-owning child and its one-line JSON control channel."""

    def __init__(self, fleet: Fleet, job: dict, platform: str) -> None:
        self.fleet = fleet
        job_path = os.path.join(fleet.workdir, "worker.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        self.proc = fleet.spawn(
            "worker", [sys.executable, os.path.join(LIB, "worker_main.py"),
                       job_path], child_env(platform, **job["env"]),
            pipes=True)
        self.lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line)
        self.lines.put(None)

    def read(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = self.lines.get(timeout=max(
                    0.05, min(1.0, deadline - time.monotonic())))
            except queue.Empty:
                if time.monotonic() >= deadline:
                    raise BenchFailure(
                        "the chip-owning child did not answer in "
                        f"{timeout:.0f}s:\n" + self.fleet.tail("worker")
                    ) from None
                continue
            if line is None:
                raise BenchFailure("the chip-owning child exited:\n"
                                   + self.fleet.tail("worker", 40))
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)

    def tell(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def ask(self, timeout: float = 120.0, **cmd) -> dict:
        self.tell(**cmd)
        return self.read(timeout)


def check_health(compute: dict, native_ingest: bool, metrics: dict, *,
                 rehearse: bool, chips: int, what: str) -> None:
    """Every quiet way off the device is an error (copied from
    ``chip_smoke.py check_worker_health``)."""
    want = "cpu" if rehearse else "tpu"
    bad = []
    if compute["platform"] != want:
        bad.append(f"platform {compute['platform']!r}, not {want!r}")
    if not rehearse and compute["device_count"] < chips:
        bad.append(f"{compute['device_count']} devices, the cell asks "
                   f"for {chips}")
    if compute["kernel_blocks"] < 1:
        bad.append("no committed block rides the Pallas kernel "
                   f"({compute['posting_blocks']} blocks)")
    if compute["kernel_interpret"] != rehearse:
        bad.append(f"kernel_interpret={compute['kernel_interpret']}")
    if compute["state"] != "healthy" or compute["total_faults"]:
        bad.append(f"compute state {compute['state']!r}, "
                   f"{compute['total_faults']} faults")
    if not native_ingest:
        bad.append("native ingest library not loaded")
    for name in WORKER_ZERO:
        if metrics.get(name, 0):
            bad.append(f"{name}={metrics[name]}")
    if bad:
        raise BenchFailure(f"{what}: " + "; ".join(bad))


def device_block(compute: dict, trace: dict | None) -> dict:
    dev = {"platform": compute["platform"],
           "kind": compute["device_kind"],
           "count": compute["device_count"],
           "memory_peak_bytes": max(
               (d["peak_bytes_in_use"] for d in compute["device_memory"]),
               default=0)}
    if trace is not None:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
    return dev


# --------------------------------------------------------------------------
# what every driver shares
# --------------------------------------------------------------------------

class Run:
    def __init__(self, args, bench: dict) -> None:
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise BenchFailure(f"no workload {args.workload!r} in "
                               "BENCHMARK.json")
        self.args = args
        self.cell = cell
        self.rehearse = args.rehearse
        self.platform = "cpu" if args.rehearse else "tpu"
        self.config = rehearsed(
            load_json(HERE, "configs", cell["config"] + ".json"),
            args.rehearse)
        self.traffic = rehearsed(
            load_json(HERE, "traffic", cell["traffic"] + ".json"),
            args.rehearse)
        self.metrics = cell_metrics(bench, cell["name"], bool(args.trace))
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.top_k = self.config["scoring"]["top_k"]
        self.workdir = tempfile.mkdtemp(prefix="bench_")
        self.fleet = Fleet(self.workdir, ROOT)
        self.ctx: dict = {"notes": []}
        self.pause_watch = PauseWatch()
        self.pause_watch.start()

    def worker_job(self, mode: str, **extra) -> dict:
        env = {}
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            # a fixed path inside the checkout: the path is part of the
            # cache's key
            env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(HERE,
                                                            ".jax_cache")
        return {"mode": mode, "seed": self.seed, "rehearse": self.rehearse,
                "corpus": data.corpus_args(self.config),
                "config": dict(self.config["engine_config"]),
                "workdir": self.workdir, "top_k": self.top_k,
                "break": self.args.break_path, "env": env, **extra}

    def make_corpus(self) -> None:
        """The reference's own copy of the corpus, made here from the
        seed while the child makes its copy the same way: nothing the
        program made comes near the reference."""
        t = time.monotonic()
        self.corpus = data.make_corpus(self.seed,
                                       **data.corpus_args(self.config))
        self.ctx["step"] = {"nnz": self.corpus.nnz,
                            "docs": self.corpus.n_docs}
        log(f"reference: corpus {self.corpus.n_docs} docs / "
            f"{self.corpus.nnz} postings in {time.monotonic() - t:.1f}s")

    def reference(self, sample_queries: list[str]) -> oracle_mod.Oracle:
        """The float64 reference for the sampled answers."""
        t = time.monotonic()
        sc = self.config["scoring"]
        ref = oracle_mod.Oracle(self.corpus, sample_queries, k1=sc["k1"],
                                b=sc["b"], top_k=self.top_k)
        del self.corpus
        log(f"reference: {len(sample_queries)} queries in "
            f"{time.monotonic() - t:.1f}s")
        return ref

    def judge(self, ref: oracle_mod.Oracle, answers: dict[int, list]) -> dict:
        if len(answers) < MIN_COMPARED:
            raise BenchFailure(
                f"only {len(answers)} of the {SAMPLE_ANSWERS} sampled "
                "answers fell inside the window; --seconds is too short "
                "for this cell")
        verdict = oracle_mod.compare(ref, answers)
        for name, n in verdict["numbers"].items():
            log(f"compared: {name} = {n['value']!r} (limit {n['limit']})")
        if verdict["worst"]:
            log(f"compared: widest at {verdict['worst']}")
        return verdict

    def result(self, verdict: dict, attempted: int, failed: int,
               compute: dict) -> dict:
        ctx = self.ctx
        trace = ctx.get("trace")
        device = device_block(compute, trace)
        ctx["memory_peak_bytes"] = device["memory_peak_bytes"]
        ctx["device_kind"] = device["kind"]
        metrics = {}
        for name, spec in self.metrics.items():
            value = readers.read(spec, ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": spec["unit"]}
        for n in ctx["notes"]:
            log(n)
        if trace is not None:
            # the names the trace gives the device's operations and the
            # host's spans: what the metric files' patterns are read from
            log("trace: device ops (name, count, seconds): " + json.dumps(
                sorted(([k, *v] for k, v in trace["device_ops"].items()),
                       key=lambda r: -r[2])[:40]))
            log("trace: host spans (name, count, seconds): " + json.dumps(
                sorted(([k, *v] for k, v in trace["host_spans"].items()),
                       key=lambda r: -r[2])[:30]))
        out = {"correct": verdict["correct"], "attempted": attempted,
               "failed": failed, "metrics": metrics, "device": device}
        if trace is not None:
            out["breakdown"] = {
                "device_ops": xtrace.top(trace["device_ops"]),
                "idle_gaps": xtrace.top(trace["idle_gaps"])}
        if self.rehearse:
            out["rehearsal"] = True
        return out

    def close(self) -> None:
        self.fleet.stop_all()
        shutil.rmtree(self.workdir, ignore_errors=True)
        if self.fleet.killed:
            raise BenchFailure(f"{self.fleet.killed} did not exit on "
                               "SIGTERM and had to be killed")


# --------------------------------------------------------------------------
# served cells: client -> leader /leader/start -> ... -> worker -> kernel
# --------------------------------------------------------------------------

def served(run: Run, loop: str) -> dict:
    cfg, tr, fleet = run.config, run.traffic, run.fleet
    host = "127.0.0.1"
    ports = {t: free_port() for t in ("coord", "leader", "worker")}
    hp = {t: (host, p) for t, p in ports.items()}
    coord = f"{host}:{ports['coord']}"
    B = cfg["engine_config"]["query_batch"]
    common = dict(TFIDF_SCATTER_BATCH=str(cfg["engine_config"]
                                          ["scatter_batch"]),
                  TFIDF_SESSION_TIMEOUT_S="15")
    tfidf = [sys.executable, "-m", "tfidf_tpu"]
    job = run.worker_job("serve")
    job["config"].update(
        host=host, port=ports["worker"], coordinator_address=coord,
        session_timeout_s=15.0,
        documents_path=f"{run.workdir}/worker/docs",
        index_path=f"{run.workdir}/worker/index")
    worker = ChipChild(fleet, job, run.platform)     # the long pole first
    fleet.spawn("coord", tfidf + ["coordinator", "--listen", coord],
                child_env("cpu", **common, **cfg.get("leader_env", {})))
    fleet.spawn("leader", tfidf + [
        "serve", "--host", host, "--port", str(ports["leader"]),
        "--coordinator-address", coord,
        "--documents-path", f"{run.workdir}/leader/docs",
        "--index-path", f"{run.workdir}/leader/index"],
        child_env("cpu", **common, **cfg.get("leader_env", {})))

    # ---- the run's inputs, all from the seed ----
    ramp = float(tr["ramp_s"])
    procs, threads = tr["processes"], tr["threads_per_process"]
    horizon = ramp + run.seconds
    if loop == "open":
        rate = float(tr["rate_qps"])
        due = data.poisson_arrivals(run.seed, rate, horizon)
        n_pool = len(due)
    else:
        due = None
        n_pool = int(tr["max_qps"] * (horizon + 2.0))
    n_warm = 2 * B + LEADER_WARM_QUERIES
    pool = data.make_queries(run.seed, n_warm + n_pool, vocab=cfg["vocab"],
                             query_terms=cfg["query_terms"],
                             zipf_a=cfg.get("zipf_a", 1.25))
    warm_q, pool = pool[:n_warm], pool[n_warm:]
    # the generators keep the answers at a seeded share of the pool's
    # positions; which of those fall in the window is known only after it
    keep = data.sample_positions(run.seed, n_pool, max(
        4 * SAMPLE_ANSWERS, n_pool // KEEP_ONE_IN))
    run.make_corpus()

    wait_until("leader election", lambda: call(
        hp["leader"], "GET", "/api/status")[2] == b"I am the leader",
        watch=[(fleet, "leader"), (fleet, "coord"), (fleet, "worker")])
    built = worker.read(CHILD_READY_TIMEOUT_S)
    log(f"worker built its engine: {built['timings']}")
    worker.ask(cmd="serve")
    wait_until("worker registration", lambda: get_json(
        hp["leader"], "/api/services")
        == [f"http://{host}:{ports['worker']}"], watch=[(fleet, "worker")])

    # ---- warm up this cell's shapes, and no others ----
    def bucket(qs: list[str]) -> None:
        status, _h, body = call(
            hp["worker"], "POST", "/worker/process-batch",
            json.dumps({"queries": qs, "k": run.top_k}).encode(),
            timeout=900.0)
        if status != 200:
            raise BenchFailure(f"warm-up of bucket {len(qs)}: {status} "
                               f"{body[:300]!r}\n" + fleet.tail("worker"))

    t = time.monotonic()
    cap = cfg.get("unique_term_capacity")
    if cap:
        # pins the compiled step's unique-term capacity for every seed
        bucket(data.capacity_batch(pool, B, cap))
    bucket(warm_q[:B])
    t_first = time.monotonic() - t
    smaller = [warm_q[:n] for n in (B >> s for s in range(1, B.bit_length()))]
    with concurrent.futures.ThreadPoolExecutor(len(smaller)) as ex:
        list(ex.map(bucket, smaller))
    t_buckets = time.monotonic() - t

    def through_leader(q: str) -> None:
        status, _h, body = call(hp["leader"], "POST", "/leader/start",
                                q.encode(), timeout=120.0)
        if status != 200:
            raise BenchFailure(f"warm-up through the leader: {status} "
                               f"{body[:300]!r}")

    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        list(ex.map(through_leader, warm_q[2 * B:]))
    health = get_json(hp["worker"], "/api/health")
    pre = {"worker": get_json(hp["worker"], "/api/metrics"),
           "leader": get_json(hp["leader"], "/api/metrics")}
    log(f"warm-up: largest shape {t_first:.1f}s, all {len(smaller) + 1} "
        f"shapes {t_buckets:.1f}s, with the front door "
        f"{time.monotonic() - t:.1f}s; worker compiles "
        f"{pre['worker'].get('xla_compiles', 0)}, cache hits "
        f"{pre['worker'].get('compile_cache_hits', 0)}, misses "
        f"{pre['worker'].get('compile_cache_misses', 0)}")
    check_health(health["compute"], health["native_ingest"], pre["worker"],
                 rehearse=run.rehearse, chips=run.cell["chips"],
                 what="worker after warm-up")

    # ---- the load ----
    def offered(due_times, t_start: float, t_end: float,
                tag: str) -> list[list]:
        """Start the generators, wait for them, return their records
        (and keep the sampled answers in ``kept``)."""
        for p in range(procs):
            mine = list(range(p, len(pool) if due_times is None
                              else len(due_times), procs))
            with open(os.path.join(run.workdir, f"{tag}{p}.json"), "w") as f:
                json.dump({
                    "door": hp["leader"], "threads": threads,
                    "queries": [pool[i % len(pool)] for i in mine],
                    "positions": mine,
                    "due": None if due_times is None
                    else [float(due_times[i]) for i in mine],
                    "keep": keep, "t_start": t_start, "t_end": t_end,
                    "timeout_s": tr["request_timeout_s"]}, f)
            fleet.spawn(f"{tag}{p}", [
                sys.executable, os.path.join(LIB, "loadgen.py"),
                os.path.join(run.workdir, f"{tag}{p}.json")],
                child_env("cpu"))
        records: list[list] = []
        for p in range(procs):
            fleet.wait(f"{tag}{p}", timeout=(t_end - time.monotonic())
                       + tr["request_timeout_s"] + 60.0)
            with open(os.path.join(run.workdir, f"{tag}{p}.json.out")) as f:
                out = json.load(f)
            records += out["records"]
            kept.update({int(k): v for k, v in out["kept"].items()})
            if out["wrapped"]:
                log(f"generator {p} ran out of distinct queries and "
                    "started over")
        return records

    kept: dict[int, list] = {}
    if run.args.sweep:
        return sweep(run, offered)

    t_start = time.monotonic() + tr["start_delay_s"]
    t0, t1 = t_start + ramp, t_start + ramp + run.seconds
    run.ctx["setup_s"] = t0 - T0
    snaps: dict = {}

    def watch_window() -> None:
        """Snapshots at the window's edges and, traced, the profiler
        around a stretch of it."""
        time.sleep(max(0.0, t0 - time.monotonic()))
        snaps["t0"] = {p: get_json(hp[p], "/api/metrics")
                       for p in ("leader", "worker")}
        if run.args.trace:
            time.sleep(max(0.0, t0 + run.seconds * 0.25 - time.monotonic()))
            worker.ask(cmd="trace_start")
            time.sleep(min(TRACE_SECONDS, run.seconds * 0.5))
            worker.ask(cmd="trace_stop", timeout=300.0)
        time.sleep(max(0.0, t1 - time.monotonic()))
        snaps["t1"] = {p: get_json(hp[p], "/api/metrics")
                       for p in ("leader", "worker")}

    watcher = threading.Thread(target=watch_window)
    watcher.start()
    records = offered(due, t_start, t1, "gen")
    watcher.join()
    if "t1" not in snaps:
        raise BenchFailure("the window's snapshots were not taken")
    log(f"window closed: {len(records)} requests sent by {procs} x "
        f"{threads} clients")

    # ---- after the window ----
    compiles = snaps["t1"]["worker"].get("xla_compiles", 0) \
        - pre["worker"].get("xla_compiles", 0)
    if compiles:
        raise BenchFailure(f"{compiles} XLA compile(s) between the "
                           "warm-up and the end of the window")
    if snaps["t1"]["leader"].get("scatter_failures", 0):
        raise BenchFailure("scatter_failures="
                           f"{snaps['t1']['leader']['scatter_failures']}")
    rep = worker.ask(cmd="report")
    check_health(rep["compute"], rep["native_ingest"], rep["metrics"],
                 rehearse=run.rehearse, chips=run.cell["chips"],
                 what="worker after the window")
    if run.args.trace:
        run.ctx["trace"] = worker.ask(cmd="trace_reduce",
                                      timeout=300.0)["trace"]
    stats = genstats.window_stats(
        records, loop=loop, t0=t0, t1=t1,
        fail_ms=tr["request_timeout_s"] * 1e3)
    log(f"generator: {json.dumps(stats)}")
    log("generator, by second [s, n, p50 ms, p95 ms]: " + json.dumps(
        genstats.per_second(records, loop=loop, t0=t0, t1=t1)))
    run.ctx["gen"] = stats
    run.ctx["host_pauses"] = run.pause_watch.within(t0, t1)
    log(f"machine-wide pauses in the window: {run.ctx['host_pauses']}")
    run.ctx["snaps"] = {p: (snaps["t0"][p], snaps["t1"][p])
                        for p in ("leader", "worker")}
    log("leader: mean scatter batch " + str(readers.counter_delta(
        {"process": "leader", "counter": "scatter_items",
         "per": "scatter_batches"}, run.ctx)))
    in_window = {r[0] for r in records
                 if (t0 <= r[3] < t1 if loop == "closed"
                     else t0 <= r[1] < t1)}
    mine = sorted(p for p in kept if p in in_window)
    mine = [mine[i] for i in data.sample_positions(
        run.seed, len(mine), SAMPLE_ANSWERS)]
    ref = run.reference([pool[p % len(pool)] for p in mine])
    answers = {i: [(n, float(s)) for n, s in kept[p]]
               for i, p in enumerate(mine)}
    verdict = run.judge(ref, answers)
    return run.result(verdict, stats["attempted"], stats["failed"],
                      rep["compute"])


def sweep(run: Run, offered) -> None:
    """Benchmark-only tool (``--sweep r1,r2,...``): inside one set-up,
    offer each rate for ``--seconds`` and print what the generator saw —
    the knee is the highest rate at which neither the generator's lateness
    nor the reply time grows from the first half to the second. It prints
    no result line."""
    tr = run.traffic
    for i, rate in enumerate(float(r) for r in run.args.sweep.split(",")):
        due = data.poisson_arrivals(run.seed + i, rate, run.seconds)
        t_start = time.monotonic() + tr["start_delay_s"]
        t_end = t_start + run.seconds
        recs = offered(due, t_start, t_end, f"sweep{i}_")
        half = t_start + run.seconds / 2
        fail_ms = tr["request_timeout_s"] * 1e3
        a = genstats.window_stats(recs, loop="open", t0=t_start, t1=half,
                                  fail_ms=fail_ms)
        b = genstats.window_stats(recs, loop="open", t0=half, t1=t_end,
                                  fail_ms=fail_ms)
        log("sweep " + json.dumps({"rate_qps": rate, "first_half": a,
                                   "second_half": b}))
        time.sleep(2.0)      # let the queue drain before the next rate


# --------------------------------------------------------------------------
# the batch cell: one caller, Engine.search_batch, back to back
# --------------------------------------------------------------------------

def batch(run: Run, _loop: str) -> dict:
    cfg, tr = run.config, run.traffic
    width = tr["queries_per_call"]
    n_batches = tr["pool_batches"]
    pool = data.make_queries(run.seed, (n_batches + 1) * width,
                             vocab=cfg["vocab"],
                             query_terms=cfg["query_terms"],
                             zipf_a=cfg.get("zipf_a", 1.25))
    warm_q, pool = pool[:width], pool[width:]
    batches = [pool[i * width:(i + 1) * width] for i in range(n_batches)]
    cap = cfg.get("unique_term_capacity")
    widest = max(data.distinct_terms(b) for b in batches + [warm_q])
    if cap and widest > cap:
        raise BenchFailure(
            f"a batch holds {widest} distinct terms, over the "
            f"configuration's unique_term_capacity {cap}")
    warm = ([data.capacity_batch(pool, width, cap)] if cap else []) \
        + [warm_q]
    sample = data.sample_positions(
        run.seed, min(len(pool), 4 * width), SAMPLE_ANSWERS)
    job = run.worker_job(
        "batch", batches=batches, warm=warm, seconds=run.seconds,
        trace=bool(run.args.trace), trace_seconds=TRACE_SECONDS,
        keep={str(p): i for i, p in enumerate(sample)})
    job["config"]["query_batch"] = width
    worker = ChipChild(run.fleet, job, run.platform)
    run.make_corpus()
    ref = run.reference([pool[p] for p in sample])
    ready = worker.read(CHILD_READY_TIMEOUT_S)
    log(f"worker ready: {ready['timings']}")
    worker.tell(cmd="go")
    done = worker.read(run.seconds + 300.0)
    run.ctx["setup_s"] = done["t0"] - T0
    if done["compiles_in_window"]:
        raise BenchFailure(f"{done['compiles_in_window']} XLA compile(s) "
                           "inside the window")
    check_health(done["compute"], done["native_ingest"], done["metrics"],
                 rehearse=run.rehearse, chips=run.cell["chips"],
                 what="engine after the window")
    run.ctx["trace"] = done["trace"]
    run.ctx["host_pauses"] = run.pause_watch.within(
        done["t0"], done["t0"] + done["elapsed_s"])
    log(f"machine-wide pauses in the window: {run.ctx['host_pauses']}")
    run.ctx["gen"] = {"attempted": done["queries"], "failed": 0,
                      "completed_qps": done["queries"] / done["elapsed_s"],
                      "batches": done["batches"]}
    run.ctx["step"].update(batch=width, unique_terms=float(sum(
        data.distinct_terms(b) for b in batches)) / len(batches))
    log(f"engine: {done['batches']} batches of {width} in "
        f"{done['elapsed_s']:.3f}s")
    answers = {int(s): [(n, float(v)) for n, v in hits]
               for s, hits in done["kept"].items()}
    verdict = run.judge(ref, answers)
    return run.result(verdict, done["queries"], 0, done["compute"])


DRIVERS = {"served-closed": (served, "closed"),
           "served-open": (served, "open"),
           "batch-closed": (batch, "closed")}


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on JAX_PLATFORMS=cpu; debugs the "
                         "harness; stamped, never a number")
    ap.add_argument("--sweep", metavar="R1,R2,...",
                    help="open-loop cells: offer each rate in turn and "
                         "print what the generator saw; no result line")
    ap.add_argument("--break-path", action="store_true",
                    help=argparse.SUPPRESS)      # benchmarks/tests only
    args = ap.parse_args(argv)
    run = None
    try:
        if args.break_path and not args.rehearse:
            raise BenchFailure("--break-path exists for the rehearsal "
                               "test only")
        if not os.path.isdir(os.path.join(ROOT, "tfidf_tpu")):
            raise BenchFailure("no tfidf_tpu package beside benchmarks/ — "
                               "nothing to measure")
        bench = load_json(ROOT, "BENCHMARK.json")
        run = Run(args, bench)
        driver, loop = DRIVERS.get(run.traffic["driver"], (None, None))
        if driver is None:
            raise BenchFailure(f"unknown driver "
                               f"{run.traffic['driver']!r}; have "
                               f"{sorted(DRIVERS)}")
        try:
            result = driver(run, loop)
        except BenchFailure:
            for tag in ("worker", "leader"):
                tail = run.fleet.tail(tag, 12)
                if tail:
                    print(f"--- {tag} log tail ---\n{tail}", file=sys.stderr)
            raise
        finally:
            run.close()
    except BenchFailure as e:
        print(f"benchmark: FAILED, no result: {e}", file=sys.stderr)
        return 1
    assert "jax" not in sys.modules, "the parent must stay off jax"
    if result is None:       # a sweep
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
