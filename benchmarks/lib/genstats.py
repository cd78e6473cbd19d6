"""The generator's arithmetic: from request records to window numbers.

A record is ``[position, t_ref, t_send, t_done, status, flags]``
(``loadgen.py``). The window is ``[t0, t1)``.

* closed loop: the requests of the window are those whose reply came
  back inside it; a reply is timed from its send.
* open loop: the requests of the window are those DUE inside it, whenever
  they finished; a reply is timed from its due time and a failed one
  counts as having taken ``fail_ms`` (it missed any limit).

A request failed if its status is not 200 or its reply carried a degraded
or fault header (shed, degraded and non-200 replies all count).
"""

from __future__ import annotations

from data import percentile


def failed(rec: list) -> bool:
    return rec[4] != 200 or bool(rec[5])


def window_stats(records: list[list], *, loop: str, t0: float, t1: float,
                 fail_ms: float) -> dict:
    if loop == "closed":
        mine = [r for r in records if t0 <= r[3] < t1]
    elif loop == "open":
        mine = [r for r in records if t0 <= r[1] < t1]
    else:
        raise ValueError(f"unknown loop {loop!r}")
    bad = sum(1 for r in mine if failed(r))
    seconds = t1 - t0
    out = {"attempted": len(mine), "failed": bad,
           "completed_qps": (len(mine) - bad) / seconds}
    if not mine:
        return out
    lat = [fail_ms if failed(r) else (r[3] - r[1]) * 1e3 for r in mine]
    late = [(r[2] - r[1]) * 1e3 for r in mine]
    out.update(latency_p50_ms=percentile(lat, 50),
               latency_p90_ms=percentile(lat, 90),
               latency_p95_ms=percentile(lat, 95),
               latency_p99_ms=percentile(lat, 99),
               latency_max_ms=max(lat),
               late_p95_ms=percentile(late, 95),
               offered_qps=len(mine) / seconds)
    return out


def per_second(records: list[list], *, loop: str, t0: float, t1: float
               ) -> list[list]:
    """For the progress log: ``[second, requests, p50 ms, p95 ms]`` of the
    window's requests by the second they were due (open) or came back
    (closed) — where in a window a tail came from."""
    col = 1 if loop == "open" else 3
    rows = []
    for sec in range(int(t1 - t0 + 0.999)):
        lat = [(r[3] - r[1]) * 1e3 for r in records
               if t0 + sec <= r[col] < min(t0 + sec + 1, t1)]
        if lat:
            rows.append([sec, len(lat), round(percentile(lat, 50), 1),
                         round(percentile(lat, 95), 1)])
    return rows
