"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

A device plane (``/device:TPU:0``) has a line of whole programs (``XLA
Modules``) and a line of their operations (``XLA Ops``).  Busy time is the
union of the intervals in which an operation ran; a program's time is the
sum of its module events.  Host annotations (``jax.profiler.
TraceAnnotation``) are on the same clock, which is how an idle gap gets
the name of what the host was doing in it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from fnmatch import fnmatchcase

MODULES = "XLA Modules"
OPS = "XLA Ops"


def profile_options():
    """No Python tracer (a cycle makes millions of Python calls) and no
    HLO dump: the device lines and the host annotations are what is read."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union(intervals: list) -> list:
    """Sorted, merged [(start, end)]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def read(path: str, device_prefix: str = "/device:TPU") -> dict:
    """{devices: [{name, modules: [(name, start, dur)], ops: ...}],
    annotations: [(name, start, dur)]}, times in ns."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, annotations = [], []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = {ln.name: ln for ln in plane.lines}
            dev = {"name": plane.name, "modules": [], "ops": []}
            for key, line_name in (("modules", MODULES), ("ops", OPS)):
                line = lines.get(line_name)
                if line is not None:
                    dev[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
            if dev["modules"] or dev["ops"]:
                devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        annotations.append((e.name, e.start_ns,
                                            e.duration_ns))
    return {"devices": devices, "annotations": sorted(
        annotations, key=lambda a: a[1])}


def reduce(trace: dict, window_s: float) -> dict | None:
    """Device numbers of the traced window, averaged over the devices
    used.  None where no operation ran on a device."""
    devs = trace["devices"]
    if not devs:
        return None
    busy, programs, ops = [], defaultdict(float), defaultdict(float)
    gaps_named = []
    for dev in devs:
        work = dev["ops"] or dev["modules"]
        merged = union([(s, s + d) for _n, s, d in work])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, _s, d in dev["modules"]:
            programs[name] += d / 1e9 / len(devs)
        for name, _s, d in dev["ops"]:
            # An op's name is its whole HLO line; what is left of " = "
            # is the instruction's own name.
            ops[name.split(" = ")[0]] += d / 1e9 / len(devs)
    busy_s = sum(busy) / len(devs)
    if busy_s <= 0:
        return None
    first = devs[0]
    merged = union([(s, s + d)
                    for _n, s, d in (first["ops"] or first["modules"])])
    ann = trace["annotations"]
    if ann:
        lo = min(a[1] for a in ann)
        hi = max(a[1] + a[2] for a in ann)
    else:
        lo, hi = merged[0][0], merged[-1][1]
    edges = [(lo, lo)] + [m for m in merged if m[1] > lo and m[0] < hi] \
        + [(hi, hi)]
    for (_a0, a1), (b0, _b1) in zip(edges, edges[1:]):
        if b0 > a1:
            gaps_named.append((a1, b0))
    return {"busy_s": busy_s, "window_s": window_s,
            "programs": dict(programs), "ops": dict(ops),
            "gaps": sorted(gaps_named, key=lambda g: g[0] - g[1])}


def program_seconds(reduced: dict, match: str) -> float | None:
    hits = [s for name, s in reduced["programs"].items()
            if fnmatchcase(name, match)]
    return sum(hits) if hits else None


def top(table: dict, n: int = 10) -> list:
    return [[name, secs] for name, secs in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def name_gaps(reduced: dict, trace: dict, spans_of, n: int = 10) -> list:
    """The idle time of the longest gaps, by what the host was doing: the
    harness's annotation and, inside a cycle, the deepest of the program's
    flight-recorder spans (``spans_of(annotation index)`` gives them as
    (name, start_ns, end_ns) on the trace's clock).  A gap that runs
    across several spans is shared out among them, the deepest first."""
    table = defaultdict(float)
    for a, b in reduced["gaps"][:200]:
        for i, (name, s, d) in enumerate(trace["annotations"]):
            lo, hi = max(a, s), min(b, s + d)
            if hi <= lo:
                continue
            label = name[len("bench:"):]
            left = [(lo, hi)]
            for sname, s0, s1 in sorted(spans_of(i),
                                        key=lambda sp: sp[2] - sp[1]):
                rest = []
                for x, y in left:
                    u, v = max(x, s0), min(y, s1)
                    if v <= u:
                        rest.append((x, y))
                        continue
                    table[f"{label}/{sname}"] += (v - u) / 1e9
                    rest += [(x, u)] if u > x else []
                    rest += [(v, y)] if y > v else []
                left = rest
            table[label] += sum(y - x for x, y in left) / 1e9
    return top({k: v for k, v in table.items() if v > 0}, n)
