"""What every cell's loop shares: the compile watch, the guard's counters,
the scheduler built from the configuration's settings, one cycle of
``Scheduler.run_once`` with its spans and counters read, and the measured
window.

Arrivals, completions, what is read back after a cycle and how long a job
may stay pending belong to the cell's generator
(``<path>/generators/<name>.py``, named by the traffic file).  Its client
gives ``cycle(annotate)``, ``records`` (one a cycle, each with ``spans``,
``counters``, ``t_sched`` and ``trace_t0``), ``ledger`` and ``close()``.
"""

from __future__ import annotations

import contextlib
import time


class CompileWatch:
    """Counts what JAX compiles, by its own monitoring events: a backend
    compile is a program that was in no in-process cache, whether the
    persistent cache then held it (``hits``) or not (``misses``).
    ``names`` has the function of each, in order."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0
        self.names: list[str] = []
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def _duration(self, event, seconds, fun_name="", **_kw):
        if event.endswith("/backend_compile_duration"):
            self.compiles += 1
            self.compile_s += seconds
            self.names.append(str(fun_name))

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "hits": self.hits,
                "misses": self.misses, "compile_s": round(self.compile_s, 3)}

    def since(self, before: dict) -> dict:
        """What compiled since ``before`` (a ``snapshot()``)."""
        return {"compiles": self.compiles - before["compiles"],
                "misses": self.misses - before["misses"],
                "compiled": self.names[before["compiles"]:]}


def guard_counters() -> dict:
    from kai_scheduler_tpu.utils.deviceguard import device_guard
    g = device_guard()
    return {"fallback_calls": g.fallback_calls, "timeouts": g.timeouts,
            "bad_results": g.bad_results}


def moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before if after[k] != before[k]}


class _AskedKeys(dict):
    """A settings document that notes every key it is asked for."""

    def __init__(self, doc):
        super().__init__(doc)
        self.asked = set()

    def __contains__(self, key):
        self.asked.add(key)
        return super().__contains__(key)

    def __getitem__(self, key):
        self.asked.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.asked.add(key)
        return super().get(key, default)


def scheduler_config(config: dict, where: str = "the configuration"):
    """The operator's ``SchedulerConfig``: the defaults with the
    configuration's ``scheduler`` settings applied by ``apply_dict``, which
    is what reads a deployment's config file.  A key that ``apply_dict``
    never asks for would be dropped in silence: it stops the run."""
    from kai_scheduler_tpu.framework.conf import SchedulerConfig
    settings = config.get("scheduler")
    if not isinstance(settings, dict):
        raise SystemExit(
            f"{where}: \"scheduler\" has to be an object of settings that "
            f"SchedulerConfig.apply_dict reads ({{}} for the defaults)")
    doc = _AskedKeys(settings)
    out = SchedulerConfig().apply_dict(doc)
    unknown = sorted(set(settings) - doc.asked)
    if unknown:
        raise SystemExit(
            f"{where}: SchedulerConfig.apply_dict knows no \"scheduler\" "
            f"setting {unknown}")
    return out


def phases(annotate):
    """``phase(name)``: the profiler's annotation (``annotate``, in a traced
    cycle) or nothing, around a phase of the client's cycle."""
    return annotate or (lambda _name: contextlib.nullcontext())


def device_operand(shape, dtype):
    """An operand of a ``.lower()`` call, in the type ``jnp.asarray`` gives
    a host array of ``dtype`` in this process (f64 is f32 without x64)."""
    import jax
    import numpy as np
    return jax.ShapeDtypeStruct(
        shape, jax.dtypes.canonicalize_dtype(np.dtype(dtype)))


def run_once(sched, rec, counters, phase) -> None:
    """One ``Scheduler.run_once`` with the movement of the program's
    ``counters`` and its flight-recorder spans read into ``rec``.  A
    counter the program does not have is left out, so that its metric is
    left out of the line and not printed as 0."""
    from kai_scheduler_tpu.utils.metrics import METRICS
    from kai_scheduler_tpu.utils.tracing import TRACER
    before = {c: METRICS.counters.get(c, 0.0) for c in counters}
    rec.t_sched = time.perf_counter()
    with phase("bench:run_once"):
        sched.run_once()
    trace = TRACER.get_trace()
    rec.counters = {c: METRICS.counters[c] - before[c]
                    for c in counters if c in METRICS.counters}
    if trace is not None:
        rec.spans = [(s.name, s.kind, s.span_id, s.parent_id,
                      s.start_s, s.duration_s)
                     for s in trace.spans]
        rec.trace_t0 = trace.t0


def measure(client, seconds: float, trace_cycles: int = 0,
            trace_dir: str | None = None) -> dict:
    """The window: whole cycles until ``seconds`` have passed, closed at
    the end of the cycle in flight.  With ``trace_cycles`` the profiler
    records the window's first cycles."""
    import jax

    from .trace import profile_options
    tracing = bool(trace_cycles and trace_dir)
    traced_s = 0.0
    first = len(client.records)
    if tracing:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=profile_options())
    t0 = time.perf_counter()
    ends = []
    try:
        while True:
            client.cycle(jax.profiler.TraceAnnotation if tracing else None)
            ends.append(time.perf_counter())
            if tracing and len(client.records) - first >= trace_cycles:
                traced_s = time.perf_counter() - t0
                jax.profiler.stop_trace()
                tracing = False
            if time.perf_counter() - t0 >= seconds:
                break
    finally:
        if tracing:
            traced_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
    return {"first": first, "elapsed_s": time.perf_counter() - t0,
            "cycle_s": [round(b - a, 3) for a, b in zip([t0] + ends, ends)],
            "traced_s": traced_s,
            "traced_cycles": min(trace_cycles, len(client.records) - first)
            if traced_s else 0}
