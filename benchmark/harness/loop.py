"""One run's loop: build, prime, warm cycles, the measured window.

The harness is the scheduler's client.  It holds one ``ClusterInfo`` and
drives ``Scheduler.run_once`` over it in a closed loop: before a cycle the
gang that was bound ``lifetime_cycles`` cycles ago completes and is
removed, and the mix's next gang arrives as a pending PodGroup; after the
cycle the pods it bound are running.  Default ``SchedulerConfig``, no
environment option, no override.
"""

from __future__ import annotations

import contextlib
import gc
import time
from dataclasses import dataclass, field

import numpy as np

from . import cluster as gen


class CompileWatch:
    """Counts what JAX compiles, by its own monitoring events: a backend
    compile is a program that was in no in-process cache, whether the
    persistent cache then held it (``hits``) or not (``misses``)."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def _duration(self, event, seconds, **_kw):
        if event.endswith("/backend_compile_duration"):
            self.compiles += 1
            self.compile_s += seconds

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "hits": self.hits,
                "misses": self.misses, "compile_s": round(self.compile_s, 3)}


def guard_counters() -> dict:
    from kai_scheduler_tpu.utils.deviceguard import device_guard
    g = device_guard()
    return {"fallback_calls": g.fallback_calls, "timeouts": g.timeouts,
            "bad_results": g.bad_results}


def moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before if after[k] != before[k]}


@dataclass
class CycleRecord:
    index: int
    gang: gen.Gang
    used_before: np.ndarray          # [N,3] the ledger before the cycle
    pods_before: np.ndarray          # [N]
    queue_used_before: dict
    t_sched: float = 0.0             # perf_counter at run_once
    foreign_binds: int = 0           # binds of pods that are not the gang's
    counters: dict = field(default_factory=dict)   # program counter deltas
    spans: list = field(default_factory=list)   # flight-recorder spans
    trace_t0: float = 0.0            # perf_counter origin of the spans


class Client:
    """The closed loop over one fleet."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 counters: tuple = ()):
        """``counters``: names in the program's metrics registry whose
        per-cycle movement the per-layer readers ask for."""
        from kai_scheduler_tpu.framework.conf import SchedulerConfig
        from kai_scheduler_tpu.scheduler import Scheduler
        self.config = config
        self.traffic = traffic
        self.cluster, self.ledger = gen.build_fleet(config, seed)
        self.sched = Scheduler(lambda: self.cluster, SchedulerConfig())
        self.rng = np.random.default_rng([int(seed), 2])
        self.leaves = gen.leaf_queues(self.ledger)
        self.node_index = {gen.node_name(i): i
                           for i in range(self.ledger.n)}
        self.live: list[tuple[gen.Gang, object]] = []   # (gang, podgroup)
        self.lifetime = int(traffic["lifetime_cycles"])
        self.next_index = 0
        self.records: list[CycleRecord] = []
        self.pending_gang: gen.Gang | None = None   # the cycle's arrival
        self.counters = tuple(counters)

    # -- what the client does between cycles -------------------------------
    def _complete(self, gang: gen.Gang, pg) -> None:
        cluster = self.cluster
        for task in pg.pods.values():
            node = cluster.nodes.get(task.node_name)
            if node is not None:
                node.remove_task(task)
        del cluster.podgroups[pg.uid]
        cluster.invalidate_aggregates()
        self._charge(gang, -1.0)

    def _charge(self, gang: gen.Gang, sign: float) -> None:
        """Enter (or with -1 take out) the gang's bound pods in the ledger."""
        if not gang.bound:
            return
        row = {n: i for i, n in enumerate(gang.names)}
        names = list(gang.bound)
        self.ledger.charge(gang.queue,
                           np.array([gang.bound[n] for n in names]),
                           gang.req[[row[n] for n in names]], sign)

    def _arrive(self) -> tuple:
        queue = self.leaves[int(self.rng.integers(len(self.leaves)))]
        pg, gang = gen.make_gang(self.traffic, self.next_index, queue)
        self.next_index += 1
        self.cluster.podgroups[pg.uid] = pg
        self.cluster.invalidate_aggregates()
        return gang, pg

    def _settle(self, rec: CycleRecord, pg) -> None:
        """Read what the cycle bound, as the binder would see it."""
        from kai_scheduler_tpu.api import PodStatus
        cache = self.sched.cache
        gang = rec.gang
        members = set(gang.names)
        for uid, node in cache.bound:
            if uid in members:
                gang.bound[uid] = self.node_index[node]
            else:
                rec.foreign_binds += 1
        cache.bound.clear()
        self.cluster.bind_requests.clear()
        self._charge(gang, 1.0)
        for task in pg.pods.values():
            if task.uid in gang.bound:
                pg.update_task_status(task, PodStatus.RUNNING)

    # -- one cycle ---------------------------------------------------------
    def cycle(self, annotate=None) -> CycleRecord:
        """Completions, one arrival, ``run_once``, and the binds read
        back.  ``annotate`` (the profiler's TraceAnnotation) names the
        phases on the trace's clock."""
        from kai_scheduler_tpu.utils.metrics import METRICS
        from kai_scheduler_tpu.utils.tracing import TRACER
        phase = annotate or (lambda _name: contextlib.nullcontext())
        with phase("bench:client_before"):
            while len(self.live) >= self.lifetime:
                self._complete(*self.live.pop(0))
            gang, pg = self._arrive()
        self.pending_gang = gang
        ledger = self.ledger
        rec = CycleRecord(
            index=len(self.records), gang=gang,
            used_before=ledger.used.copy(), pods_before=ledger.pods.copy(),
            queue_used_before={q: v.copy()
                               for q, v in ledger.queue_used.items()})
        self.cluster.now += 1.0
        before = {c: METRICS.counters.get(c, 0.0) for c in self.counters}
        rec.t_sched = time.perf_counter()
        with phase("bench:run_once"):
            self.sched.run_once()
        trace = TRACER.get_trace()
        rec.counters = {c: METRICS.counters.get(c, 0.0) - before[c]
                        for c in self.counters}
        with phase("bench:client_after"):
            self._settle(rec, pg)
            self.live.append((gang, pg))
        if trace is not None:
            rec.spans = [(s.name, s.kind, s.span_id, s.parent_id,
                          s.start_s, s.duration_s)
                         for s in trace.spans]
            rec.trace_t0 = trace.t0
        self.records.append(rec)
        return rec

    def close(self) -> None:
        """Free the program's state before the comparison runs."""
        self.sched = None
        self.cluster = None
        self.live = []
        gc.collect()


def prime(client: Client, watch: CompileWatch) -> dict:
    """Compile the exact kernel at the cell's own shape before the first
    guarded dispatch: the device guard allows a dispatch 30 s, compile
    included, and past that re-runs it on the CPU.

    The shapes come from a pack of the fleet with the mix's first gang
    pending, as the first cycle will pack it; the gang is taken out again.
    """
    import jax

    from kai_scheduler_tpu.api.snapshot import pack
    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
    from kai_scheduler_tpu.ops.scoring import BINPACK

    pg, gang = gen.make_gang(client.traffic, 0, client.leaves[0])
    client.cluster.podgroups[pg.uid] = pg
    try:
        snap = pack(client.cluster)
    finally:
        del client.cluster.podgroups[pg.uid]
        client.cluster.invalidate_aggregates()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, jax.dtypes.canonicalize_dtype(np.dtype(dtype)))

    def like(a):
        return sds(a.shape, a.dtype)

    t_pad = gen.padded(len(gang.names))
    n = snap.node_idle.shape[0]
    mask = sds((t_pad, n), bool) if gang.topology else None
    before = watch.snapshot()
    t0 = time.perf_counter()
    allocate_jobs_kernel.lower(
        like(snap.node_allocatable), like(snap.node_idle),
        like(snap.node_releasing), like(snap.node_labels),
        like(snap.node_taints), like(snap.node_pod_room),
        sds((t_pad, snap.task_req.shape[1]), np.float64),
        sds((t_pad,), np.int32),
        sds((t_pad, snap.task_selector.shape[1]), np.int32),
        sds((t_pad, snap.task_tolerations.shape[1]), np.int32),
        sds((2,), bool), sds((t_pad, n), np.float64),
        task_node_mask=mask, task_anti_domain=None, task_aff_domain=None,
        gpu_strategy=BINPACK, cpu_strategy=BINPACK,
        allow_pipeline=True, pipeline_only=False).compile()
    after = watch.snapshot()
    return {"seconds": round(time.perf_counter() - t0, 3),
            "t_pad": t_pad, "nodes": n,
            "resources": int(snap.node_idle.shape[1]),
            "label_cols": int(snap.node_labels.shape[1]),
            "taint_cols": int(snap.node_taints.shape[1]),
            "cache_misses": after["misses"] - before["misses"]}


def measure(client: Client, seconds: float, trace_cycles: int = 0,
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
