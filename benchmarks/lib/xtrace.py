"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but jax; only
this module touches it, and it runs in the chip-owning child after the
window (reading a file needs no device). The parent gets the summary
below and the metric readers take their numbers from it.

* busy: per device plane, the UNION of the intervals in which an
  operation ran (the ``XLA Ops`` line), averaged over the device planes
  that ran anything. Idle share = 1 - busy / window.
* window: from the first to the last event of the device-op lines and of
  the host spans named in HOST_SPANS — the stretch the trace covers.
* per-name operation time: count and summed duration of the device events
  of each name. Only LEAF events are summed: an event that encloses
  others on its line (a ``while``, a fusion's outer frame) would count its
  children twice. An operation's name is its HLO text (``%name = shape
  op(...)``); a Pallas kernel is a ``custom-call`` whose text names the
  target ``tpu_custom_call``.
* per-program time: the same for the ``XLA Modules`` line, whose events
  are whole executed programs named ``jit_<function>(<fingerprint>)``.
* host spans: count and summed duration of every host-plane event by
  name (``trace_phase``'s ``jax.profiler.TraceAnnotation``s land here).
* idle gaps: each stretch of a device's line in which nothing ran, charged
  to the HOST_SPANS span that covers most of it, else "no span"; summed
  by name.

On a CPU rehearsal there is no device plane; the XLA:CPU client threads'
events stand in so that the same code runs end to end. Such a summary is
marked ``"rehearsal": true`` and is never a number.
"""

from __future__ import annotations

import glob
import os

HOST_SPANS = ("vectorize", "score", "topk")
DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
DEVICE_MODULE_LINE = "XLA Modules"    # one event per executed program
_CPU_OP_LINE_PREFIX = "tf_XLAPjRtCpuClient"
_CPU_SKIP_PREFIX = "ThreadpoolListener"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_length(intervals: list[tuple[float, float]]
                 ) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of ``(start, end)`` intervals, and the
    gaps between its pieces."""
    busy = 0.0
    gaps: list[tuple[float, float]] = []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def leaf_events(events: list[tuple[float, float, str]]
                ) -> list[tuple[float, float, str]]:
    """Of ``(start, end, name)`` events on one line, those that enclose
    no other event."""
    ev = sorted(events, key=lambda e: (e[0], -e[1]))
    leaves = []
    for i, (s, e, name) in enumerate(ev):
        encloses = i + 1 < len(ev) and ev[i + 1][0] < e \
            and ev[i + 1][1] <= e and (ev[i + 1][0], ev[i + 1][1]) != (s, e)
        if not encloses:
            leaves.append((s, e, name))
    return leaves


def attribute_gaps(gaps: list[tuple[float, float]],
                   spans: list[tuple[float, float, str]]
                   ) -> dict[str, float]:
    """Seconds of ``gaps`` (ns intervals) by the span covering most of
    each gap, ``"no span"`` where none overlaps."""
    out: dict[str, float] = {}
    spans = sorted(spans)
    lo = 0
    for gs, ge in sorted(gaps):
        while lo < len(spans) and spans[lo][1] <= gs:
            lo += 1
        cover: dict[str, float] = {}
        for s, e, name in spans[lo:]:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        name = max(cover, key=cover.get) if cover else "no span"
        out[name] = out.get(name, 0.0) + (ge - gs) / 1e9
    return out


def _line_events(line) -> list[tuple[float, float, str]]:
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def reduce_xplane(path: str) -> dict:
    from jax.profiler import ProfileData    # the child's import, not ours
    data = ProfileData.from_file(path)
    device_lines, host_events = [], []
    cpu_lines, module_events = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    ev = _line_events(line)
                    if ev:
                        device_lines.append(ev)
                elif line.name == DEVICE_MODULE_LINE:
                    module_events.extend(_line_events(line))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                ev = _line_events(line)
                if line.name.startswith(_CPU_OP_LINE_PREFIX):
                    cpu_lines.append([e for e in ev if not
                                      e[2].startswith(_CPU_SKIP_PREFIX)])
                else:
                    host_events.extend(ev)
    rehearsal = not device_lines
    if rehearsal:
        device_lines = [ev for ev in cpu_lines if ev]
    return summarize(device_lines, host_events, module_events,
                     rehearsal=rehearsal)


def _by_name(events) -> dict[str, list[float]]:
    out: dict[str, list[float]] = {}
    for s, e, name in events:
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) / 1e9
    return out


def summarize(device_lines: list[list[tuple[float, float, str]]],
              host_events: list[tuple[float, float, str]],
              module_events: list[tuple[float, float, str]] = (),
              *, rehearsal: bool = False) -> dict:
    """``device_lines``: one list of (start_ns, end_ns, name) per device."""
    spans = [e for e in host_events if e[2] in HOST_SPANS]
    marks = [t for ev in device_lines for s, e, _n in ev for t in (s, e)] \
        + [t for s, e, _n in spans for t in (s, e)]
    window_ns = (max(marks) - min(marks)) if marks else 0.0
    busy_ns = 0.0
    ops: dict[str, list[float]] = {}
    gaps_by: dict[str, float] = {}
    for ev in device_lines:
        busy, gaps = union_length([(s, e) for s, e, _n in ev])
        busy_ns += busy
        for name, (cnt, sec) in _by_name(leaf_events(ev)).items():
            c = ops.setdefault(name, [0, 0.0])
            c[0] += cnt
            c[1] += sec
        for name, sec in attribute_gaps(gaps, spans).items():
            gaps_by[name] = gaps_by.get(name, 0.0) + sec
    n = max(len(device_lines), 1)
    return {
        "rehearsal": rehearsal,
        "devices": len(device_lines),
        "busy_s": busy_ns / 1e9 / n,
        "window_s": window_ns / 1e9,
        "device_ops": ops,
        "device_modules": _by_name(module_events),
        "host_spans": _by_name(host_events),
        "idle_gaps": {k: v / n for k, v in gaps_by.items()},
    }


def top(d: dict, n: int = 10) -> list[list]:
    """``{name: seconds}`` or ``{name: [count, seconds]}`` as the ``n``
    largest ``[name, seconds]`` pairs."""
    pairs = [[k, v[1] if isinstance(v, list) else v] for k, v in d.items()]
    return sorted(pairs, key=lambda kv: -kv[1])[:n]
