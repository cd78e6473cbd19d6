"""One load-generator process: ``threads`` clients on keep-alive
connections to the front door, closed loop or open loop.

Run as a child (``python loadgen.py <job.json>``); writes ``<job>.out``.
All times are ``time.monotonic()`` — CLOCK_MONOTONIC is one clock for
every process of the host, so the parent's window and four generators'
records line up.

* ``closed``: each client sends its next query when the last reply has
  come back, from ``t_start`` until ``t_end``. A reply is timed from its
  send.
* ``open``: request ``i`` is DUE at ``t_start + due[i]`` whether or not
  earlier ones have finished; a free client sleeps until then and sends.
  A reply is timed from when it was due, so a stall charges the requests
  queued behind it, and ``late`` (send - due) says how far the generator
  itself ran behind. Requests due before ``t_end`` are all sent and
  waited for.

Every client opens its connection BEFORE ``t_start``, a few milliseconds
after its neighbour: 512 clients connecting in one instant overflow the
front door's listen queue, the dropped SYNs come back after 1 s and 3 s,
and the first seconds of the window would run with fewer clients than
the mix states (seen on the chip, PR 23: replies 4-6 s late in every
closed-loop run, and a start-up transient of a different depth in each).

A record is ``[position, t_ref, t_send, t_done, status, flags]`` with
``t_ref`` the due time (open) or the send time (closed), ``position`` the
query's place in the run's pool, and ``flags`` the degraded / fault
headers the reply carried. Replies whose position is in ``keep`` are kept
whole, for the check of outputs.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from http import client as httplib

FLAG_HEADERS = ("X-Compute-Degraded", "X-Scatter-Degraded",
                "X-Compute-Fault")
CONNECT_SPACING_S = 0.004


def run(job: dict) -> dict:
    door = tuple(job["door"])
    queries: list[str] = job["queries"]
    positions: list[int] = job["positions"]
    due = job.get("due")             # open loop only, seconds from t_start
    keep = set(job["keep"])
    t_start, t_end = job["t_start"], job["t_end"]
    timeout = job["timeout_s"]
    lock = threading.Lock()
    state = {"next": 0}
    records: list[list] = []
    kept: dict[int, list] = {}

    def take() -> int | None:
        with lock:
            i = state["next"]
            if due is not None and i >= len(due):
                return None
            state["next"] = i + 1
            return i

    def client(k: int) -> None:
        conn = httplib.HTTPConnection(*door, timeout=timeout)
        time.sleep(k * CONNECT_SPACING_S)
        try:
            conn.connect()
        except OSError:
            pass                      # the first request connects again
        time.sleep(max(0.0, t_start - time.monotonic()))
        while True:
            i = take()
            if i is None:
                break
            if due is not None:
                t_ref = t_start + due[i]
                delay = t_ref - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            elif time.monotonic() >= t_end:
                break
            q = i % len(queries)
            t_send = time.monotonic()
            status, flags, body = 0, [], b""
            try:
                conn.request("POST", "/leader/start",
                             body=queries[q].encode())
                resp = conn.getresponse()
                body = resp.read()
                status = resp.status
                flags = [h for h in FLAG_HEADERS
                         if resp.getheader(h) is not None]
            except (httplib.HTTPException, OSError):
                conn.close()
                conn = httplib.HTTPConnection(*door, timeout=timeout)
            t_done = time.monotonic()
            rec = [positions[q], t_send if due is None else t_ref,
                   t_send, t_done, status, flags]
            hits = None
            if status == 200 and positions[q] in keep and i < len(queries):
                hits = sorted(json.loads(body).items(),
                              key=lambda kv: -kv[1])
            with lock:
                records.append(rec)
                if hits is not None:
                    kept[positions[q]] = hits
        conn.close()

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(job["threads"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"records": records, "kept": {str(k): v for k, v in kept.items()},
            "wrapped": state["next"] > len(queries)}


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        job = json.load(f)
    out = run(job)
    with open(argv[1] + ".out", "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
