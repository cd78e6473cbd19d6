"""The metric readers: each takes one number from what a run gathered.

A metric is a file ``benchmarks/metrics/<name>.json`` that names a reader
and its arguments; ``read(spec, ctx)`` returns the value, or None where
the reader finds nothing to read (the harness then leaves the metric out
of the line). ``ctx`` holds what the run gathered:

* ``setup_s``; ``gen`` — the generator's own arithmetic (``genstats``);
* ``snaps`` — ``{process: (before, after)}``, two ``/api/metrics``
  snapshots around the window (``leader``, ``worker``);
* ``trace`` — ``xtrace``'s summary of the traced stretch, or None;
* ``host_pauses`` — ``{"count", "total_ms"}`` of machine-wide pauses in
  the window, as the parent's idle ``fleet.PauseWatch`` thread saw them;
* ``memory_peak_bytes``, ``device_kind``, ``step`` (what one scoring
  step has to move: ``nnz``, ``docs``, ``batch``, ``unique_terms``).
"""

from __future__ import annotations

import re

import cost


def _delta(ctx: dict, process: str, key: str) -> float | None:
    snaps = ctx.get("snaps", {}).get(process)
    if snaps is None:
        return None
    before, after = snaps
    return float(after.get(key, 0)) - float(before.get(key, 0))


def setup(spec: dict, ctx: dict):
    return ctx.get("setup_s")


def generator(spec: dict, ctx: dict):
    return ctx.get("gen", {}).get(spec["field"])


def counter_delta(spec: dict, ctx: dict):
    """Window delta of a counter, over the delta of ``per`` if given."""
    num = _delta(ctx, spec["process"], spec["counter"])
    if num is None:
        return None
    if "per" not in spec:
        return num
    den = _delta(ctx, spec["process"], spec["per"])
    return num / den if den else None


def timing_delta(spec: dict, ctx: dict):
    """Mean milliseconds of an ``observe()`` timing over the window:
    delta ``<name>_sum_ms`` / delta ``<name>_count``."""
    total = _delta(ctx, spec["process"], spec["timing"] + "_sum_ms")
    n = _delta(ctx, spec["process"], spec["timing"] + "_count")
    return total / n if total is not None and n else None


def _span(ctx: dict, name: str):
    tr = ctx.get("trace")
    return tr["host_spans"].get(name) if tr else None


def host_annotation(spec: dict, ctx: dict):
    """Mean milliseconds of the host ``TraceAnnotation`` ``span`` in the
    traced stretch."""
    c = _span(ctx, spec["span"])
    return c[1] / c[0] * 1e3 if c and c[0] else None


def _device_seconds(spec: dict, ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr:
        return None
    pats = [re.compile(p) for p in spec["patterns"]]
    table = tr["device_modules" if spec.get("line") == "modules"
               else "device_ops"]
    hit = [v[1] for name, v in table.items()
           if any(p.search(name) for p in pats)]
    return sum(hit) if hit else None


def device_ops(spec: dict, ctx: dict):
    """Device milliseconds of the operations (``"line": "modules"``: the
    whole programs) whose names match ``patterns``, per occurrence of the host span ``per_span`` (one per
    dispatched batch). With ``"roofline": true``, instead the share in
    percent of the chip's roofline those milliseconds reach for one
    scoring step (``cost.py``)."""
    sec = _device_seconds(spec, ctx)
    per = _span(ctx, spec["per_span"])
    if sec is None or not per or not per[0]:
        return None
    per_batch = sec / per[0]
    if not spec.get("roofline"):
        return per_batch * 1e3
    share = cost.roofline_share(cost.score_step_cost(**ctx["step"]),
                                per_batch, ctx["device_kind"])
    ctx.setdefault("notes", []).append(
        f"roofline of {spec['patterns']}: {share['bound']}-bound, least "
        f"{share['least_seconds'] * 1e3:.3f} ms a step against "
        f"{per_batch * 1e3:.3f} ms measured")
    return share["share"] * 100.0


def device_idle(spec: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0


def host_pause(spec: dict, ctx: dict):
    """Milliseconds of the window in which the whole machine stood still
    (``fleet.PauseWatch``)."""
    seen = ctx.get("host_pauses")
    return None if seen is None else float(seen["total_ms"])


def memory(spec: dict, ctx: dict):
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e9 if peak else None


READERS = {
    "setup": setup, "generator": generator, "counter-delta": counter_delta,
    "timing-delta": timing_delta, "host-annotation": host_annotation,
    "device-ops": device_ops, "device-idle": device_idle, "memory": memory,
    "host-pause": host_pause,
}


def read(spec: dict, ctx: dict):
    if spec["reader"] not in READERS:
        raise KeyError(f"unknown reader {spec['reader']!r}; have "
                       f"{sorted(READERS)}")
    return READERS[spec["reader"]](spec, ctx)
