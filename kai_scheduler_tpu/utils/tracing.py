"""Cycle flight recorder: structured span tracing for the decision path.

The per-cycle hot loop (snapshot -> plugin opens -> actions -> kernel
dispatches -> commit) is the paper's latency-critical contribution, yet
latency averages cannot answer the two questions that matter
after an incident: *which span burned the budget of cycle N* and *why is
this PodGroup still pending*.  This module gives every cycle a structured
trace — nested spans with monotonic durations, attributes, and error
status — and keeps the last N complete traces in a bounded in-memory
**flight recorder**, exportable as Chrome trace-event / Perfetto JSON.

Design constraints (the kailint contracts):

- all timing is ``time.perf_counter`` (KAI003: no wall clock in utils/);
- span bookkeeping is thread-local and lock-free on the cycle path; the
  ring lock guards only finished-trace appends and reads (KAI006: no
  blocking work under a lock — trace-file dumps happen outside it);
- memory is bounded at every layer: the ring holds ``capacity`` traces,
  a trace holds at most ``max_spans_per_trace`` spans, and the
  explainability ledger caps groups/reasons per trace — every overflow
  is counted (``dropped_spans`` / ``dropped_rejections``), never silent.

Correlation: the scheduler threads the cycle's ``trace_id`` into
BindRequest specs (``spec.traceId``) and status-updater events
(``spec.traceId``), so a bind object in the store points back at the
exact cycle trace that produced it.  Rejection reasons land in a
per-cycle **explainability ledger** (``CycleTrace.explain``) surfaced at
``GET /explain?podgroup=<name>``.  See docs/OBSERVABILITY.md.

Post-mortem hook: when ``KAI_TRACE_DIR`` is set, every aborted or
degraded cycle's Chrome trace JSON is written there as it completes —
``tools/chaos_matrix.py --trace-dir`` uses this to capture the traces of
failing chaos iterations.

Cross-process propagation (PR 19, the wire observatory): a trace no
longer dies at the process boundary.  ``HTTPKubeAPI`` opens a
``client_span`` around every request and injects the active context as
``X-Kai-Trace`` / ``X-Kai-Span`` headers (W3C ``traceparent`` shape,
flattened to two headers because the only peer is our own apiserver);
the apiserver times each request's dispatch-queue wait / handler /
serialize / sendall phases and records them — tagged with the injected
context — into a bounded ``SpanRing`` (utils/wireobs.py) served at
``GET /debug/spans?since=``.  Once per cycle the operator pulls that
ring and ``graft_remote_spans`` joins the server's spans back into the
owning ring trace, CENTERED inside their client parent span: the two
processes' ``perf_counter`` domains are unrelated, so the only honest
alignment is containment — the residual gap on each side of the server
span IS the wire time, visible in Perfetto instead of lost.  Threads
that carry no live cycle (the commit executor) arm an **ambient wire
context** (``set_wire_context``) so their requests still stamp the
owning cycle's trace and their client spans attach post-hoc.

Across the device guard's worker thread (PR 25): the guard runs a
dispatch's thunk on a ``deviceguard-worker`` thread while the cycle
thread is parked in the guard's wait.  ``Tracer.hand_off`` captures the
live trace and its open-span stack on the cycle thread; the thunk it
wraps adopts them on whichever thread runs it, so spans opened there are
children of the open ``dispatch:<label>`` span.  One writer at a time: a
later adopter (a retry, the CPU fallback) supersedes an earlier one, and
a worker the guard abandoned finds its hand-off revoked once the dispatch
span closed.  An adopter records under the ring lock, which superseding
and revoking take too, so a worker abandoned in the middle of a close
writes either before the cycle thread moves on or never; what it opens
or closes afterwards is counted in ``trace_spans_revoked_total`` and on
no trace (the trace it meant may have ended by then).

One clock: while a cycle is live every span is also a
``jax.profiler.TraceAnnotation`` named ``kai:<span name>``, so a profiler
session started by anyone holds the scheduler's phases on the device
trace's own clock.  A process that never imported jax (the apiserver
child) records spans without it.

The cyclic collector (PR 40): from the first ``begin_cycle`` on, one
``gc.callbacks`` entry of the process-wide ``TRACER`` counts every
collection and its pause by generation, and a collection of the oldest
generation that lands on a thread with a live cycle is a ``gc:full`` span
under that thread's innermost open span.  ``end_cycle`` folds the counts
into ``METRICS``; the callback itself takes no lock (``Tracer._on_gc``).

What the recorder kept: beside the collector's, ``end_cycle`` folds into
``METRICS`` how many spans closed in the cycle (``trace_spans_total``) and
how many of them the trace had no room for (``trace_spans_dropped_total``):
a reader of ``CycleTrace.spans`` that sees the second move knows its sums
are short, where ``name_totals`` still holds every span.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque

from .logging import LOG
from .metrics import METRICS

# Cross-process trace-context carriers (W3C traceparent analog, split
# into two headers: trace id and the client span awaiting its server
# half).  Shared by httpclient (inject) and apiserver (extract).
TRACE_HEADER = "X-Kai-Trace"
SPAN_HEADER = "X-Kai-Span"


class Span:
    """One timed operation inside a cycle trace.

    ``start_s`` is relative to the trace's origin (monotonic), so spans
    serialize directly into Chrome trace-event ``ts``/``dur`` pairs."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "kind",
                 "start_s", "duration_s", "attrs", "status", "error",
                 "annotation")

    def __init__(self, trace_id: str, span_id: str, parent_id: str | None,
                 name: str, kind: str, start_s: float):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.start_s = start_s
        self.duration_s = 0.0
        self.attrs: dict = {}
        self.status = "ok"
        self.error = ""
        self.annotation = None  # the open kai:<name> TraceAnnotation

    def set(self, **attrs) -> None:
        """Attach attributes (kernel label, breaker state, ...)."""
        self.attrs.update(attrs)

    def mark_error(self, message: str) -> None:
        self.status = "error"
        self.error = message[:300]

    def to_event(self) -> dict:
        """Chrome trace-event (Perfetto/about:tracing) complete event."""
        args = dict(self.attrs)
        args["status"] = self.status
        if self.error:
            args["error"] = self.error
        if self.parent_id:
            args["parent"] = self.parent_id
        return {"name": self.name, "cat": self.kind, "ph": "X",
                "ts": round(self.start_s * 1e6, 1),
                "dur": round(self.duration_s * 1e6, 1),
                "pid": 1, "tid": 1, "id": self.span_id, "args": args}


class _NullSpan:
    """Span opened outside an active cycle (offline sessions, bench
    setup): every call is a no-op, so instrumented code never branches."""

    __slots__ = ()
    status = "ok"

    def set(self, **attrs) -> None:
        pass

    def mark_error(self, message: str) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    """Context manager around a span: closes it on exit and converts an
    escaping exception into error status (the exception still
    propagates — tracing observes failures, never swallows them)."""

    __slots__ = ("_tracer", "span")

    def __init__(self, tracer: "Tracer", span):
        self._tracer = tracer
        self.span = span

    def __enter__(self):
        return self.span

    def __exit__(self, exc_type, exc, tb):
        if self.span is not _NULL_SPAN:
            if exc is not None and self.span.status == "ok":
                self.span.mark_error(f"{exc_type.__name__}: {exc}")
            self._tracer._close_span(self.span)
        return False


class _ClientSpanCtx:
    """Client half of a cross-process wire span (one HTTP request).

    Three regimes, decided at open time by ``Tracer.client_span``:

    - **live**: a cycle trace is active on this thread — a real nested
      span rides the thread-local stack like any ``Tracer.span``;
    - **deferred**: no live trace, but an ambient wire context is armed
      (commit-executor threads) — the span's id is pre-allocated so the
      ``X-Kai-Span`` header can carry it, the duration is measured here,
      and the finished span attaches to the finalized ring trace on
      exit (same post-hoc path as ``attach_async_span``);
    - **null**: no context at all (watch thread, bench setup) — every
      call no-ops and ``trace_id`` is None, so the caller skips the
      headers.
    """

    __slots__ = ("_tracer", "trace_id", "span_id", "_span", "_name",
                 "_kind", "_parent_id", "_attrs", "_t0")

    def __init__(self, tracer, trace_id=None, span_id=None, span=None,
                 name="", kind="wire", parent_id=None, attrs=None):
        self._tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self._span = span  # live regime only
        self._name = name
        self._kind = kind
        self._parent_id = parent_id
        self._attrs = dict(attrs) if attrs else {}
        self._t0 = 0.0

    def set(self, **attrs) -> None:
        if self._span is not None:
            self._span.set(**attrs)
        elif self.trace_id is not None:
            self._attrs.update(attrs)

    def mark_error(self, message: str) -> None:
        if self._span is not None:
            self._span.mark_error(message)
        elif self.trace_id is not None:
            self._attrs["status"] = "error"
            self._attrs["error"] = message[:300]

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._span is not None:
            if exc is not None and self._span.status == "ok":
                self._span.mark_error(f"{exc_type.__name__}: {exc}")
            self._tracer._close_span(self._span)
        elif self.trace_id is not None:
            if exc is not None and "error" not in self._attrs:
                self._attrs["status"] = "error"
                self._attrs["error"] = f"{exc_type.__name__}: {exc}"[:300]
            self._tracer._attach_completed_span(
                self.trace_id, self.span_id, self._parent_id, self._name,
                self._kind, time.perf_counter() - self._t0, self._attrs)
        return False


# Shared null client span: requests made with tracing off (observability
# traffic like the /debug/spans pull itself) reuse this.
NULL_CLIENT_SPAN = _ClientSpanCtx(None)


class _HandOff:
    """The live trace and its open-span stack, captured on the cycle
    thread for a thunk that another thread will run (``Tracer.hand_off``);
    outside a cycle ``trace`` is None and there is nothing to carry.

    ``writer`` is the ident of the one thread that may record at the
    moment: each adoption takes it over, and leaving the ``with`` block
    (the dispatch span is about to close) clears it for good.  Both
    happen under the tracer's ring lock, as an adopter's record does.

    ``pending`` holds the ``gc:full`` spans that closed on the writer's
    thread: the collector's callback may not take that lock, so it queues
    them here and the next holder of the lock records them
    (``record_pending``)."""

    __slots__ = ("_tracer", "trace", "stack", "writer", "pending")

    def __init__(self, tracer: "Tracer", trace: "CycleTrace | None",
                 stack: list):
        self._tracer = tracer
        self.trace = trace
        self.stack = stack
        self.writer: int | None = None
        self.pending: list = []

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        with self._tracer._lock:
            self.writer = None
            self.record_pending()
        return False

    def record_pending(self) -> None:
        """Under the ring lock: record what the collector queued.  A span
        queued after the hand-off was revoked stays here, on no trace."""
        while self.pending:
            self.trace.record(self.pending.pop(0))

    def adopting(self, thunk):
        """``thunk``, recording into this trace on the thread that calls
        it."""
        if self.trace is None:
            return thunk

        def adopted():
            with self._tracer._adopt(self):
                return thunk()
        return adopted


# Span kinds that get no ``cycle_span_<kind>_latency_ms`` histogram: each
# mixes unlike intervals under one kind (``seam`` is a 5 s staging beside
# a 1 ms launch), so a quantile over it says nothing.  ``span_names`` in
# /debug/cycles carries their totals by name.  ``snapshot_part``: the parts
# of the ``snapshot`` span (``snapshot:<part>``), whose own histogram keeps
# holding the whole span alone.
_NO_HISTOGRAM_KINDS = frozenset({"allocate", "topology", "propose", "seam",
                                 "reclaim", "solver", "consolidation",
                                 "snapshot_part"})


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` where this process has loaded
    jax, else None: without jax no profiler session exists to write to,
    and the tracer must not be what imports it (the apiserver child)."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


class CycleTrace:
    """One complete scheduling cycle: the root span, its children, the
    abort/degraded verdict, and the explainability ledger."""

    # Ledger bounds: a sustained over-capacity cluster keeps thousands
    # of PodGroups pending every cycle; without caps the ring would hold
    # ring-size x pending-groups x reasons strings live.  Overflow is
    # counted (dropped_rejections), never silent.
    MAX_EXPLAIN_GROUPS = 256
    MAX_REASONS_PER_GROUP = 8
    # Per-name totals bound: names are a small fixed vocabulary except
    # the per-object kubeapi spans (``bind:<pod>``), which past this
    # many distinct names fold into one ``OTHER_NAMES`` row.
    MAX_SPAN_NAMES = 128
    OTHER_NAMES = "(other)"

    def __init__(self, trace_id: str, cycle: int, max_spans: int):
        self.trace_id = trace_id
        self.cycle = cycle
        self.t0 = time.perf_counter()
        self.root: Span | None = None
        self.spans: list[Span] = []   # completed spans, completion order
        self.max_spans = max_spans
        self.dropped_spans = 0
        # name -> [count, seconds] over every span that closed, kept or
        # dropped by the cap: sums by name stay right in a cycle with a
        # thousand jobs.
        self.name_totals: dict[str, list] = {}
        self.aborted: str | None = None
        self.degraded = False
        self.duration_ms = 0.0
        self.explain: dict[str, list[str]] = {}  # podgroup -> reasons
        self.dropped_rejections = 0
        # Wire observatory (PR 19): per-cycle wire-counter delta
        # (attach_wire_summary) and the ids of server-side records
        # already grafted — the graft dedup set, so a re-pulled or
        # replayed /debug/spans record can never join twice.
        self.wire: dict | None = None
        self.grafted: set = set()

    def add_rejection(self, podgroup: str, reason: str) -> None:
        reasons = self.explain.get(podgroup)
        if reasons is None:
            if len(self.explain) >= self.MAX_EXPLAIN_GROUPS:
                self.dropped_rejections += 1
                return
            reasons = self.explain[podgroup] = []
        if reason in reasons:
            return
        if len(reasons) >= self.MAX_REASONS_PER_GROUP:
            self.dropped_rejections += 1
            return
        reasons.append(reason)

    def record(self, span: Span, keep: bool = False) -> None:
        """A span closed: add it to its name's total, and to the span
        list while there is room (``keep``: the root's reserved seat)."""
        total = self.name_totals.get(span.name)
        if total is None:
            name = (span.name if len(self.name_totals) < self.MAX_SPAN_NAMES
                    else self.OTHER_NAMES)
            total = self.name_totals.setdefault(name, [0, 0.0])
        total[0] += 1
        total[1] += span.duration_s
        if keep or len(self.spans) < self.max_spans - 1:
            self.spans.append(span)
        else:
            self.dropped_spans += 1

    def span_summary(self) -> dict:
        """kind -> {count, total_ms, errors}: where the cycle went."""
        out: dict = {}
        for sp in self.spans:
            entry = out.setdefault(sp.kind, {"count": 0, "total_ms": 0.0,
                                             "errors": 0})
            entry["count"] += 1
            entry["total_ms"] += sp.duration_s * 1e3
            if sp.status == "error":
                entry["errors"] += 1
        for entry in out.values():
            entry["total_ms"] = round(entry["total_ms"], 3)
        return out

    def to_summary(self) -> dict:
        return {"cycle": self.cycle, "trace_id": self.trace_id,
                "duration_ms": round(self.duration_ms, 3),
                "aborted": self.aborted, "degraded": self.degraded,
                "spans": self.span_summary(),
                "span_names": {
                    name: {"count": n, "total_ms": round(secs * 1e3, 3)}
                    for name, (n, secs) in self.name_totals.items()},
                "dropped_spans": self.dropped_spans,
                "dropped_rejections": self.dropped_rejections,
                "rejected_podgroups": sorted(self.explain),
                "wire": self.wire}

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON: load in Perfetto (ui.perfetto.dev)
        or chrome://tracing."""
        return {"displayTimeUnit": "ms",
                "traceEvents": [sp.to_event() for sp in self.spans],
                "otherData": {"trace_id": self.trace_id,
                              "cycle": self.cycle,
                              "aborted": self.aborted,
                              "degraded": self.degraded,
                              "dropped_spans": self.dropped_spans,
                              "explain": self.explain}}


class Tracer:
    """Thread-safe tracer + bounded flight recorder.

    The active trace is thread-local: one scheduler thread drives one
    cycle, and spans opened on other threads (status-updater workers)
    deliberately no-op instead of racing the cycle's span stack; the
    one way in for another thread is a ``hand_off`` from the cycle
    thread while that thread waits (the device guard's worker).  Reads
    (`cycles`, `get_trace`, `explain_for`) come from HTTP handler threads
    and take the ring lock; finished traces are immutable."""

    def __init__(self, capacity: int | None = None,
                 max_spans_per_trace: int | None = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get("KAI_TRACE_CYCLES", 32))
            except ValueError:
                capacity = 32
        if max_spans_per_trace is None:
            # Fleet-scale cycles (hundreds of nodes over the http wire)
            # legitimately record thousands of wire + grafted server
            # spans per cycle; KAI_TRACE_MAX_SPANS deepens the recorder
            # for those runs while the default keeps tier-1 memory flat.
            try:
                max_spans_per_trace = int(
                    os.environ.get("KAI_TRACE_MAX_SPANS", 512))
            except ValueError:
                max_spans_per_trace = 512
        self.capacity = max(1, capacity)
        self.max_spans_per_trace = max(8, max_spans_per_trace)
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        # TraceAnnotation class, looked up at each begin_cycle.
        self._annotation = None
        # podgroup -> latest rejection record ({"cycle", "trace_id",
        # "reasons"}); bounded like ClusterCache._warned_selectors.
        self._explain_latest: dict = {}
        # The cyclic collector, as _on_gc keeps it (the process-wide
        # TRACER's alone ever move): [collections, pause seconds] so far
        # by generation, what end_cycle has folded into METRICS of each,
        # and the clock and the span of the collection under way.
        self._gc_totals = [[0, 0.0], [0, 0.0], [0, 0.0]]
        self._gc_folded = [[0, 0.0], [0, 0.0], [0, 0.0]]
        self._gc_t0 = 0.0
        self._gc_span: Span | None = None

    # -- cycle lifecycle ---------------------------------------------------
    def _state(self) -> dict:
        st = getattr(self._local, "state", None)
        if st is None:
            st = self._local.state = {"trace": None, "stack": [],
                                      "hand_off": None}
        return st

    def begin_cycle(self, cycle: int) -> str:
        """Open a cycle trace (and its root span) on this thread; returns
        the trace id the scheduler threads into binds and events."""
        st = self._state()
        if st["trace"] is not None:
            # An exception escaped the previous cycle driver before
            # end_cycle ran: finalize the dangling trace as aborted so
            # the recorder never loses it (and the stack never leaks).
            self.end_cycle(aborted="trace abandoned by next cycle")
        if TRACER._on_gc not in gc.callbacks:
            # Once a process: a process that opens no cycle never pays.
            gc.callbacks.append(TRACER._on_gc)
        # What end_cycle counts of the recorder, at 0 before the first.
        METRICS.inc("trace_spans_total", 0)
        METRICS.inc("trace_spans_dropped_total", 0)
        trace_id = f"t{next(self._ids):06d}"
        trace = CycleTrace(trace_id, cycle, self.max_spans_per_trace)
        self._annotation = _trace_annotation()
        st["trace"] = trace
        st["stack"] = []
        root = trace.root = self._open(st, trace, "cycle", "cycle",
                                       {"cycle": cycle})
        root.start_s = 0.0  # the trace's origin, not when _open ran
        return trace_id

    def end_cycle(self, aborted: str | None = None, degraded: bool = False,
                  explain: dict | None = None,
                  dropped_rejections: int = 0,
                  resolved=()) -> CycleTrace | None:
        """Finalize the active trace: close leftover spans, record the
        verdict, merge the explainability ledger, push to the ring, emit
        per-span-kind latency histograms, and (when KAI_TRACE_DIR is
        armed) dump aborted/degraded traces for post-mortem.

        ``dropped_rejections``: rejections the caller discarded at the
        source (ledger caps) — folded in BEFORE publication so readers
        and the post-mortem dump never see a half-counted trace.
        ``resolved``: PodGroup names this cycle saw WITHOUT any rejection
        (scheduled, or no longer pending) — their stale ``/explain``
        records drop, so an operator is never pointed at a group that is
        actually running."""
        st = self._state()
        trace: CycleTrace | None = st["trace"]
        if trace is None:
            return None
        now = time.perf_counter()
        # Leftover spans above the root belong to an aborted phase whose
        # exception bypassed their context managers; close deepest-first.
        while len(st["stack"]) > 1:
            sp = st["stack"].pop()
            self._end_annotation(sp)
            sp.duration_s = (now - trace.t0) - sp.start_s
            if aborted and sp.status == "ok":
                sp.mark_error(aborted)
            trace.record(sp)
        root = st["stack"].pop()
        self._end_annotation(root)
        root.duration_s = now - trace.t0
        if aborted:
            root.mark_error(aborted)
        trace.record(root, keep=True)  # the root survives the span cap
        trace.aborted = aborted
        trace.degraded = bool(degraded)
        trace.duration_ms = root.duration_s * 1e3
        for podgroup, reasons in (explain or {}).items():
            for reason in reasons:
                trace.add_rejection(podgroup, reason)
        trace.dropped_rejections += int(dropped_rejections)
        st["trace"] = None
        st["stack"] = []
        for sp in trace.spans:
            if sp.kind not in _NO_HISTOGRAM_KINDS:
                METRICS.observe(f"cycle_span_{sp.kind}_latency_ms",
                                sp.duration_s * 1e3)
        # What the recorder kept of the cycle: every span that closed in
        # it, and those of them that found no room.  Read before the ring
        # has the trace: a span attached afterwards is in neither.
        closed = sum(n for n, _secs in trace.name_totals.values())
        dropped = trace.dropped_spans
        with self._lock:
            gc_moved = self._gc_unfolded()
            self._ring.append(trace)
            for name in resolved:
                self._explain_latest.pop(name, None)
            if len(self._explain_latest) >= 4096:
                # Bounded memory in a long-lived daemon whose PodGroup
                # names churn: reset over growing forever.
                self._explain_latest.clear()
            for podgroup, reasons in trace.explain.items():
                self._explain_latest[podgroup] = {
                    "podgroup": podgroup, "cycle": trace.cycle,
                    "trace_id": trace.trace_id, "reasons": list(reasons)}
        for gen, (collections, pause_s) in enumerate(gc_moved):
            METRICS.inc("gc_collections_total", collections, generation=gen)
            METRICS.inc("gc_pause_seconds_total", pause_s, generation=gen)
        METRICS.inc("trace_spans_total", closed)
        METRICS.inc("trace_spans_dropped_total", dropped)
        self._maybe_dump(trace)
        return trace

    # -- spans -------------------------------------------------------------
    def span(self, name: str, kind: str, **attrs) -> _SpanCtx:
        """Open a child span under the current one.  Outside an active
        cycle this returns a null span — instrumentation is always safe
        to leave in place."""
        st = self._state()
        trace: CycleTrace | None = st["trace"]
        if trace is None:
            return _SpanCtx(self, _NULL_SPAN)
        if self._superseded(st):
            METRICS.inc("trace_spans_revoked_total")
            return _SpanCtx(self, _NULL_SPAN)
        return _SpanCtx(self, self._open(st, trace, name, kind, attrs))

    def _open(self, st: dict, trace: CycleTrace, name: str, kind: str,
              attrs: dict) -> Span:
        """A new span under this thread's innermost open one, and its
        ``kai:<name>`` annotation on the profiler's clock."""
        parent = st["stack"][-1] if st["stack"] else None
        sp = Span(trace.trace_id, f"s{next(self._ids)}",
                  parent.span_id if parent is not None else None,
                  name, kind, time.perf_counter() - trace.t0)
        if attrs:
            sp.attrs.update(attrs)
        st["stack"].append(sp)
        if self._annotation is not None:
            sp.annotation = self._annotation(f"kai:{name}")
            sp.annotation.__enter__()
        return sp

    @staticmethod
    def _end_annotation(span: Span) -> None:
        if span.annotation is not None:
            span.annotation.__exit__(None, None, None)
            span.annotation = None

    @staticmethod
    def _superseded(st: dict) -> bool:
        """True on an adopting thread that may no longer record: the
        dispatch span it was handed closed (the guard abandoned this
        worker at its deadline), or a later attempt adopted after it."""
        hand_off = st["hand_off"]
        return hand_off is not None and hand_off.writer != st["ident"]

    def _close_span(self, span: Span) -> None:
        st = self._state()
        trace: CycleTrace | None = st["trace"]
        if st["stack"] and st["stack"][-1] is span:
            st["stack"].pop()
        else:  # out-of-order close (defensive): remove wherever it sits
            try:
                st["stack"].remove(span)
            except ValueError:
                pass
        self._end_annotation(span)
        if trace is None:
            return
        span.duration_s = (time.perf_counter() - trace.t0) - span.start_s
        if st["hand_off"] is None:  # the cycle thread, the trace's owner
            trace.record(span)
            return
        with self._lock:
            live = not self._superseded(st)
            if live:
                st["hand_off"].record_pending()
                trace.record(span)
        if not live:
            METRICS.inc("trace_spans_revoked_total")

    # -- the cyclic collector ----------------------------------------------
    def _on_gc(self, phase: str, info: dict) -> None:
        """The process's one ``gc.callbacks`` entry: every collection and
        its pause into the totals by generation, and a collection of the
        oldest generation on a thread with a live cycle as a ``gc:full``
        span under that thread's innermost open span.  The two younger
        generations open nothing: thousands a cycle would fill the trace.

        This takes NO lock and calls nothing that does.  A due collection
        starts between any two bytecodes of the thread whose allocation
        crossed the threshold, also inside a ``with lock:`` block that
        thread holds: ``METRICS.inc`` under its data lock, an adopter's
        ``_close_span`` under the ring lock.  Neither lock is reentrant, so
        a callback that took either would deadlock there.  Hence the totals
        are plain lists that ``end_cycle`` folds into ``METRICS`` (CPython
        runs one collection at a time under the GIL and both phases inside
        it: one writer), the cycle thread records its span as it records
        every span, without the lock, and an adopter queues it on the
        hand-off for the next holder of the ring lock."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            if info["generation"] == 2:
                st = getattr(self._local, "state", None)
                if st is not None and st["trace"] is not None \
                        and not self._superseded(st):
                    span = self._gc_span = self._open(
                        st, st["trace"], "gc:full", "gc", {"generation": 2})
                    span.start_s = self._gc_t0 - st["trace"].t0
            return
        pause_s = time.perf_counter() - self._gc_t0
        total = self._gc_totals[info["generation"]]
        total[0] += 1
        total[1] += pause_s
        span = self._gc_span
        if span is None:
            return
        self._gc_span = None
        st = self._local.state
        if st["stack"] and st["stack"][-1] is span:
            st["stack"].pop()
        self._end_annotation(span)
        span.attrs["collected"] = info["collected"]
        span.duration_s = pause_s  # to the digit what the counter gained
        hand_off = st["hand_off"]
        if hand_off is None:  # the cycle thread, the trace's owner
            st["trace"].record(span)
        elif hand_off.writer == st["ident"]:
            hand_off.pending.append(span)

    def _gc_unfolded(self) -> list:
        """Under the ring lock (cycles of two schedulers may end at once):
        (collections, pause seconds) of each generation since the last
        call.  Each total is read once, so a collection that falls between
        the reads is counted whole by the next call."""
        moved = []
        for total, folded in zip(self._gc_totals, self._gc_folded):
            collections, pause_s = total
            moved.append((collections - folded[0], pause_s - folded[1]))
            folded[:] = collections, pause_s
        return moved

    # -- across a thread seam (the device guard's worker) ------------------
    def hand_off(self):
        """On the cycle thread, around a call that runs a thunk on another
        thread while this one waits: ``with TRACER.hand_off() as seam:``
        and give the other side ``seam.adopting(thunk)``.  Spans the thunk
        opens become children of this thread's innermost open span.
        Outside a cycle the thunk comes back as it is."""
        st = self._state()
        return _HandOff(self, st["trace"], list(st["stack"]))

    @contextlib.contextmanager
    def _adopt(self, hand_off: _HandOff):
        st = self._state()
        ident = threading.get_ident()
        with self._lock:
            hand_off.writer = ident  # whoever adopted before is superseded
        if st["trace"] is hand_off.trace:
            # The guard ran the thunk inline (no deadline): this thread
            # owns the trace already.
            yield
            return
        st.update(trace=hand_off.trace, stack=list(hand_off.stack),
                  hand_off=hand_off, ident=ident)
        try:
            yield
        finally:
            st.update(trace=None, stack=[], hand_off=None)

    def stamp(self, name: str, **attrs) -> None:
        """Set attributes on the nearest open span called ``name`` above
        the caller: how a wrapper that resolves something deep inside a
        dispatch (the grouped kernel's rung) marks the call site's span."""
        st = self._state()
        if st["trace"] is None:
            return
        with self._lock:  # as an adopter's record: not after a revoke
            if self._superseded(st):
                return
            for sp in reversed(st["stack"]):
                if sp.name == name:
                    sp.attrs.update(attrs)
                    return

    def current_trace_id(self) -> str | None:
        st = getattr(self._local, "state", None)
        trace = st["trace"] if st else None
        return trace.trace_id if trace is not None else None

    # -- cross-process context (the wire observatory) ----------------------
    def current_context(self) -> tuple[str | None, str | None]:
        """(trace_id, span_id) to inject into outbound headers: the live
        thread-local trace's innermost open span when a cycle is active
        on this thread, else the ambient wire context armed by the
        commit executor, else (None, None)."""
        st = getattr(self._local, "state", None)
        trace = st["trace"] if st else None
        if trace is not None:
            stack = st["stack"]
            top = stack[-1] if stack else trace.root
            return trace.trace_id, (top.span_id if top is not None
                                    else None)
        ambient = getattr(self._local, "ambient", None)
        if ambient is not None:
            return ambient
        return None, None

    def set_wire_context(self, trace_id: str | None,
                         span_id: str | None = None) -> None:
        """Arm an ambient wire context on THIS thread: requests made
        here (commit executor, control epilogue) stamp ``trace_id``
        even though the cycle trace was finalized on another thread.
        Pair with ``clear_wire_context`` in a finally."""
        self._local.ambient = (trace_id, span_id) if trace_id else None

    def clear_wire_context(self) -> None:
        self._local.ambient = None

    def client_span(self, name: str, kind: str = "wire",
                    **attrs) -> _ClientSpanCtx:
        """Open the client half of a cross-process span (one outbound
        request).  See ``_ClientSpanCtx`` for the three regimes; the
        returned ctx's ``trace_id``/``span_id`` are what the transport
        injects as ``X-Kai-Trace``/``X-Kai-Span``."""
        st = self._state()
        trace: CycleTrace | None = st["trace"]
        if trace is not None:  # live: a real span on this thread's stack
            sp = self._open(st, trace, name, kind, attrs)
            return _ClientSpanCtx(self, trace.trace_id, sp.span_id,
                                  span=sp)
        ambient = getattr(self._local, "ambient", None)
        if ambient is not None and ambient[0] is not None:  # deferred
            return _ClientSpanCtx(self, ambient[0],
                                  f"s{next(self._ids)}", name=name,
                                  kind=kind, parent_id=ambient[1],
                                  attrs=attrs)
        return NULL_CLIENT_SPAN

    def note_pipelined(self) -> None:
        """Mark the active cycle trace as running in overlapped-pipeline
        mode (the root span carries ``pipelined=True``)."""
        st = getattr(self._local, "state", None)
        trace = st["trace"] if st else None
        if trace is not None and trace.root is not None:
            trace.root.set(pipelined=True)

    def attach_async_span(self, trace_id: str | None, name: str,
                          kind: str, duration_s: float, **attrs) -> bool:
        """Attach a completed span to an ALREADY-FINALIZED trace still in
        the ring — the overlapped pipeline's commit stages finish after
        their cycle's ``end_cycle`` ran on the scheduler thread, and the
        flight recorder must still show where cycle N's commit budget
        went.  Thread-safe (ring lock); a trace that already aged out of
        the ring drops the span (returns False)."""
        if not self._attach_completed_span(trace_id,
                                           f"s{next(self._ids)}", None,
                                           name, kind, duration_s,
                                           attrs):
            return False
        METRICS.observe(f"cycle_span_{kind}_latency_ms",
                        duration_s * 1e3)
        return True

    def _attach_completed_span(self, trace_id, span_id, parent_id, name,
                               kind, duration_s, attrs) -> bool:
        """Append an already-measured span to a finalized ring trace
        (attach_async_span and the deferred client-span regime).  With
        no explicit parent the span hangs off the root; the start is
        back-dated from now so async work lands where it actually ran
        relative to the cycle origin."""
        if trace_id is None:
            return False
        with self._lock:
            for trace in reversed(self._ring):
                if trace.trace_id != trace_id:
                    continue
                root = trace.root
                pid = parent_id or (root.span_id if root is not None
                                    else None)
                sp = Span(trace_id, span_id, pid, name, kind,
                          max(0.0, time.perf_counter() - trace.t0
                              - duration_s))
                sp.duration_s = duration_s
                if attrs:
                    sp.attrs.update(attrs)
                trace.record(sp)
                return True
        return False

    # Phase order inside one server-side request record: insertion
    # order matters — grafted phase children are laid out sequentially.
    _SERVER_PHASES = ("queue_wait", "handler", "serialize", "sendall")

    def graft_remote_spans(self, remote_spans) -> dict:
        """Join server-side span records (``GET /debug/spans``) into
        their owning ring traces; returns counts
        ``{"grafted", "orphaned", "duplicate"}``.

        Each record carries the (trace, parent) context the client
        injected.  The server's ``perf_counter`` domain is unrelated to
        ours, so a grafted request span is CENTERED inside its client
        parent span — the residual left/right gap is the wire time,
        attributed instead of invisible.  Its phases become child spans
        (kinds ``server_queue_wait`` / ``server_handler`` /
        ``server_serialize`` / ``server_sendall``) laid out
        sequentially.  Records that carried no context at all (watch
        fanout bursts, pre-cycle traffic) are expected and count as
        unattributed; records whose trace already aged out of the ring
        count as orphaned; a record id seen before on its trace counts
        as duplicate and never double-grafts (``CycleTrace.grafted``)."""
        out = {"grafted": 0, "orphaned": 0, "duplicate": 0,
               "unattributed": 0}
        if not remote_spans:
            return out
        with self._lock:
            traces = {t.trace_id: t for t in self._ring}
            for rec in remote_spans:
                tid = rec.get("trace")
                if not tid:
                    out["unattributed"] += 1
                    continue
                trace = traces.get(tid)
                if trace is None:
                    out["orphaned"] += 1
                    continue
                rid = rec.get("id")
                if rid in trace.grafted:
                    out["duplicate"] += 1
                    continue
                trace.grafted.add(rid)
                parent = None
                parent_id = rec.get("parent")
                if parent_id:
                    for sp in trace.spans:
                        if sp.span_id == parent_id:
                            parent = sp
                            break
                dur = max(0.0, float(rec.get("dur_s") or 0.0))
                if parent is not None:
                    start = parent.start_s + max(
                        0.0, (parent.duration_s - dur) / 2.0)
                    pid = parent.span_id
                else:
                    # Client span lost (span cap) or never existed:
                    # hang off the root at the trace's tail.
                    start = max(0.0, trace.duration_ms / 1e3 - dur)
                    pid = (trace.root.span_id
                           if trace.root is not None else None)
                srv = Span(trace.trace_id, f"s{next(self._ids)}", pid,
                           str(rec.get("name") or "server"),
                           str(rec.get("kind") or "server_request"),
                           start)
                srv.duration_s = dur
                srv.attrs.update(
                    {k: rec[k] for k in ("path", "status", "bytes_in",
                                         "bytes_out", "frames",
                                         "lag_frames", "stream")
                     if k in rec})
                srv.attrs["remote_id"] = rid
                trace.record(srv)
                cursor = start
                phases = rec.get("phases") or {}
                for phase in self._SERVER_PHASES:
                    phase_s = max(0.0, float(phases.get(phase) or 0.0))
                    if phase_s <= 0.0:
                        continue
                    child = Span(trace.trace_id, f"s{next(self._ids)}",
                                 srv.span_id,
                                 f"{srv.name}:{phase}",
                                 f"server_{phase}", cursor)
                    child.duration_s = phase_s
                    cursor += phase_s
                    trace.record(child)
                out["grafted"] += 1
        if out["grafted"]:
            METRICS.inc("wire_spans_grafted_total", out["grafted"])
        if out["orphaned"]:
            METRICS.inc("wire_spans_orphaned_total", out["orphaned"])
        if out["duplicate"]:
            METRICS.inc("wire_spans_duplicate_total", out["duplicate"])
        if out["unattributed"]:
            METRICS.inc("wire_spans_unattributed_total",
                        out["unattributed"])
        return out

    def attach_wire_summary(self, trace_id: str | None,
                            wire: dict) -> bool:
        """Attach this cycle's wire-counter delta (wireobs.wire_delta)
        to its finalized ring trace — the `wire` section each row of
        ``GET /debug/cycles`` carries."""
        if trace_id is None or not wire:
            return False
        with self._lock:
            for trace in reversed(self._ring):
                if trace.trace_id == trace_id:
                    trace.wire = dict(wire)
                    return True
        return False

    def export_chrome(self, key: str | None = None) -> dict | None:
        """Chrome-trace JSON for one ring entry, serialized UNDER the
        ring lock (async commit spans may still be attaching to a
        finalized trace — an unlocked ``to_chrome`` could read a
        half-appended span list)."""
        with self._lock:
            if not self._ring:
                return None
            if key is None or key == "":
                return self._ring[-1].to_chrome()
            for trace in reversed(self._ring):
                if trace.trace_id == key or str(trace.cycle) == key:
                    return trace.to_chrome()
        return None

    def note_rejection(self, podgroup: str, reason: str) -> None:
        """Record a filter/score rejection into the active cycle's
        explainability ledger (actions call this as failures happen; the
        cycle driver merges fit errors again at end_cycle)."""
        st = getattr(self._local, "state", None)
        trace = st["trace"] if st else None
        if trace is not None:
            trace.add_rejection(podgroup, reason)

    # -- flight-recorder reads (HTTP endpoints, tests) ---------------------
    def cycles(self) -> list[dict]:
        """Last-N cycle summaries, newest first (GET /debug/cycles)."""
        with self._lock:
            return [t.to_summary() for t in reversed(self._ring)]

    def get_trace(self, key: str | None = None) -> CycleTrace | None:
        """Look a trace up by trace id or cycle number; None = latest."""
        with self._lock:
            if not self._ring:
                return None
            if key is None or key == "":
                return self._ring[-1]
            for trace in reversed(self._ring):
                if trace.trace_id == key or str(trace.cycle) == key:
                    return trace
        return None

    def explain_for(self, podgroup: str) -> dict | None:
        """Latest unschedulability record for a PodGroup, or None."""
        with self._lock:
            record = self._explain_latest.get(podgroup)
            return dict(record) if record is not None else None

    def explained_podgroups(self) -> list[str]:
        with self._lock:
            return sorted(self._explain_latest)

    def reset(self) -> None:
        """Drop all recorded state (tests).  The collector's callback and
        its totals are the process's and stay."""
        with self._lock:
            self._ring.clear()
            self._explain_latest.clear()
        self._local = threading.local()

    # -- post-mortem dump --------------------------------------------------
    def _maybe_dump(self, trace: CycleTrace) -> None:
        out_dir = os.environ.get("KAI_TRACE_DIR")
        if not out_dir or not (trace.aborted or trace.degraded):
            return
        try:
            os.makedirs(out_dir, exist_ok=True)
            path = os.path.join(
                out_dir, f"cycle_{trace.cycle}_{trace.trace_id}.json")
            with open(path, "w") as fh:
                json.dump(trace.to_chrome(), fh)
        except OSError as exc:
            METRICS.inc("trace_dump_errors")
            LOG.warning("cycle trace dump to %s failed: %s", out_dir, exc)


# Process-wide tracer, like METRICS: every layer of the decision path
# records into it without plumbing, and the server reads it back out.
TRACER = Tracer()
