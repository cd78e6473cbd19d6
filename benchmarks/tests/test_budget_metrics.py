"""The budget metrics of PR 35 (the reply first byte to last, the scatter
RPC's legs, what each process costs itself, the starved dispatch thread):
each is a data file for a reader that was there, reads a key the program
emits, reads NOTHING (and does not raise) from a program without the key,
and comes out of a traced rehearsal of the served cells that list it.
``test_stage_metrics.py``'s pattern; by hand and in rehearsal, as this
directory is.
"""

import json
import math
import os
import subprocess
import sys

import pytest

import readers
from conftest import BENCH, ROOT

SAT = ["msmarco2m.served-sat", "msmarco4m-mesh.served-sat",
       "msmarco-full.served-sat"]
STEADY = ["msmarco2m.served-steady"]

# metric -> (reader, process, the /api/metrics keys it reads)
TIMINGS = {
    "leader_pre_submit_ms": ("leader", "leader_pre_submit"),
    "leader_in_batch_ms": ("leader", "leader_in_batch"),
    "leader_post_wake_ms": ("leader", "leader_post_wake"),
    "leader_reply_write_ms": ("leader", "leader_reply_write"),
    "leader_client_gap_ms": ("leader", "leader_client_gap"),
    "scatter_rpc_out_ms": ("leader", "scatter_rpc_out"),
    "scatter_rpc_handle_ms": ("leader", "scatter_rpc_handle"),
    "scatter_rpc_back_ms": ("leader", "scatter_rpc_back"),
    "leader_gil_wait_ms": ("leader", "gil_wait"),
    "worker_gil_wait_ms": ("worker", "gil_wait"),
}
COUNTERS = {
    "dispatcher_busy": ("leader", "scatter_batch_total_sum_ms",
                        "process_wall_ms", "threads"),
    "leader_cpu_cores": ("leader", "process_cpu_ms", "process_wall_ms",
                         "cores"),
    "worker_cpu_cores": ("worker", "process_cpu_ms", "process_wall_ms",
                         "cores"),
    "leader_sys_cores": ("leader", "process_sys_ms", "process_wall_ms",
                         "cores"),
    "worker_sys_cores": ("worker", "process_sys_ms", "process_wall_ms",
                         "cores"),
    "leader_gc_pause_share": ("leader", "gc_pause_sum_ms",
                              "process_wall_ms", "share"),
    "worker_gc_pause_share": ("worker", "gc_pause_sum_ms",
                              "process_wall_ms", "share"),
    "dispatch_starved_share": ("worker", "phase_dispatch_idle_sum_ms",
                               "process_wall_ms", "share"),
    "device_wait_share": ("worker", "phase_device_wait_sum_ms",
                          "process_wall_ms", "share"),
}
SAT_ONLY = {"leader_client_gap_ms", "dispatcher_busy",
            "leader_gc_pause_share", "worker_gc_pause_share"}


def names(family: str) -> list[str]:
    return [f"{base}.{family}" for base in list(TIMINGS) + list(COUNTERS)
            if family == "sat" or base not in SAT_ONLY]


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def per_layer() -> dict[str, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


@pytest.mark.parametrize("name", names("sat") + names("steady"))
def test_file_names_a_reader_that_was_there_and_the_program_s_key(
        name, per_layer):
    base, family = name.rsplit(".", 1)
    spec, entry = spec_of(name), per_layer[name]
    assert spec["name"] == name and spec["unit"] == entry["unit"]
    assert spec["layer"] == entry["layer"]
    if base in TIMINGS:
        process, key = TIMINGS[base]
        assert spec["reader"] == "timing-delta" and spec["unit"] == "ms"
        assert (spec["process"], spec["timing"]) == (process, key)
    else:
        process, counter, per, unit = COUNTERS[base]
        assert spec["reader"] == "counter-delta" and spec["unit"] == unit
        assert (spec["process"], spec["counter"], spec["per"]) == (
            process, counter, per)
    assert spec["reader"] in readers.READERS
    assert entry["source"] == "program_span"
    if family == "sat":
        assert (entry["workloads"], entry["moves"]) == (SAT, "served_qps")
    else:
        assert (entry["workloads"], entry["moves"]) == (STEADY,
                                                        "served_p50_ms")


@pytest.mark.parametrize("name", names("sat") + names("steady"))
def test_a_program_without_the_key_reads_nothing(name):
    """The parent commit's ``/api/metrics`` has none of these keys: the
    line leaves the metric out, and nothing is raised."""
    old = {"leader_search_count": 10, "leader_search_sum_ms": 100.0,
           "scatter_batch_total_sum_ms": 50.0, "scatter_batches": 3}
    newer = {k: v * 2 for k, v in old.items()}
    ctx = {"snaps": {"leader": (old, newer), "worker": (old, newer)}}
    assert readers.read(spec_of(name), ctx) is None
    assert readers.read(spec_of(name), {}) is None


@pytest.mark.parametrize("cell,family", [
    ("msmarco2m.served-sat", "sat"), ("msmarco2m.served-steady", "steady")])
def test_traced_rehearsal_prints_the_budget_metrics(cell, family):
    p = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload", cell, "--seed",
         "2147483693", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "TFIDF_SEARCH_PIPELINE_MODE": "executor"})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in names(family):
        assert name in got, (name, sorted(got))
        assert math.isfinite(got[name])
    s = "." + family
    for base in COUNTERS:
        if base.endswith("_share") and base + s in got:
            assert 0 <= got[base + s] <= 1
    assert got["leader_cpu_cores" + s] > 0
    assert got["worker_cpu_cores" + s] > 0
    # the legs are the RPC (the two snapshots can split one RPC's four
    # observations, so not to the last digit)
    legs = sum(got[f"scatter_rpc_{leg}_ms{s}"]
               for leg in ("out", "handle", "back"))
    assert legs == pytest.approx(got["scatter_rpc_ms" + s], rel=0.02)
    assert got["scatter_rpc_out_ms" + s] > -0.5
    assert got["scatter_rpc_back_ms" + s] > -0.5
    # and the five chained stages are leader_search
    stages = sum(got[k + s] for k in (
        "leader_pre_submit_ms", "coalesce_wait_ms", "leader_in_batch_ms",
        "reply_wake_ms", "leader_post_wake_ms"))
    assert stages == pytest.approx(got["leader_search_ms" + s], rel=0.03)
