"""The per-layer readers: a few generic kinds, implemented once.

A file under ``benchmark/layer_metrics`` names a kind and its parameters.
A reader that finds nothing to read returns None and the metric is left
out of the line; it never returns 0 for a share of a roofline.

Kinds, and what they read:
  span_sum            flight-recorder spans whose name matches ``match``
                      (a list of patterns), summed, per cycle, in ms
  span_self           the spans matching ``match`` minus the spans
                      matching ``minus`` that lie under them, per cycle, ms
  counter_delta       movement of the program counter ``counter`` per cycle
  trace_program_time  device time of the programs matching ``match`` in
                      the profiler's trace, per traced cycle, in ms
  trace_idle          1 - device busy time / traced window, in %
  roofline            least time the chip needs for the bytes that the
                      byte count ``model`` computes from the shapes the
                      cell's generator gives for it
                      (``run["kernel_shapes"][model]``), over the traced
                      time of the programs matching ``match``, in %.  The
                      count is looked for in the cell's generator module
                      first, then in ``benchmark/roofline.py``: a cell on
                      another kernel brings its count as a new file
"""

from __future__ import annotations

from fnmatch import fnmatchcase

from . import trace as tr


def _matches(name: str, patterns) -> bool:
    if isinstance(patterns, str):
        patterns = [patterns]
    return any(fnmatchcase(name, p) for p in patterns)


def span_sum(reader, run):
    per_cycle = []
    for rec in run["records"]:
        hits = [dur for name, _k, _i, _p, _s, dur in rec.spans
                if _matches(name, reader["match"])]
        if hits:
            per_cycle.append(sum(hits))
    if not per_cycle:
        return None
    return 1e3 * sum(per_cycle) / len(per_cycle)


def span_self(reader, run):
    per_cycle = []
    for rec in run["records"]:
        by_id = {sid: (name, parent) for name, _k, sid, parent, _s, _d
                 in rec.spans}
        own = {sid: dur for name, _k, sid, _p, _s, dur in rec.spans
               if _matches(name, reader["match"])}
        if not own:
            continue
        total = sum(own.values())
        for name, _k, _sid, parent, _s, dur in rec.spans:
            if not _matches(name, reader["minus"]):
                continue
            # Walk up: the span counts once, under the nearest matching
            # ancestor it has.
            while parent is not None and parent not in own:
                parent = by_id.get(parent, (None, None))[1]
            if parent is not None:
                total -= dur
        per_cycle.append(total)
    if not per_cycle:
        return None
    return 1e3 * sum(per_cycle) / len(per_cycle)


def counter_delta(reader, run):
    vals = [rec.counters[reader["counter"]] for rec in run["records"]
            if reader["counter"] in rec.counters]
    if not vals:
        return None
    return sum(vals) / len(vals)


def trace_program_time(reader, run):
    red = run.get("reduced")
    if not red or not run.get("traced_cycles"):
        return None
    secs = tr.program_seconds(red, reader["match"])
    if secs is None:
        return None
    return 1e3 * secs / run["traced_cycles"]


def trace_idle(reader, run):
    red = run.get("reduced")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def roofline(reader, run):
    from .. import roofline as rf
    red = run.get("reduced")
    if not red or not run.get("traced_cycles"):
        return None
    secs = tr.program_seconds(red, reader["match"])
    if not secs:
        return None
    name = reader["model"]
    shape = run.get("kernel_shapes", {}).get(name)
    if shape is None:
        return None
    model = getattr(run.get("generator"), name, None) or getattr(rf, name)
    need = model(**shape) * run["traced_cycles"]
    peak = rf.peaks(run["device_kind"])[reader["peak"]]
    return 100.0 * (need / peak) / secs


KINDS = {f.__name__: f for f in (span_sum, span_self, counter_delta,
                                 trace_program_time, trace_idle, roofline)}


def read_all(metrics: list, run: dict) -> dict:
    """{name: {"value", "unit"}} for the metrics that found something."""
    out = {}
    for m in metrics:
        reader = m["reader"]
        value = KINDS[reader["kind"]](reader, run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def counters_wanted(metrics: list) -> tuple:
    return tuple(sorted({m["reader"]["counter"] for m in metrics
                         if m["reader"]["kind"] == "counter_delta"}))
