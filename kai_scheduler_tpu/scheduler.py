"""Scheduler daemon: the cycle driver.

Mirrors pkg/scheduler/scheduler.go:54-147 (NewScheduler/Run/runOnce): once
per period, snapshot the world, open a session (plugins register), run the
configured actions in order, close the session.  The durable outputs are
BindRequests and evictions applied through the cache.
"""

from __future__ import annotations

import time

from .actions import build_actions
from .api.snapshot import fragmentation_stats
from .framework.arena import HostArena
from .framework.conf import SchedulerConfig
from .framework.session import InMemoryCache, Session
from .utils.deviceguard import (CycleDeadlineExceeded, DeviceGuardError,
                                device_guard)
from .utils.lifecycle import LIFECYCLE
from .utils.logging import LOG
from .utils.metrics import METRICS
from .utils.tracing import TRACER


class Scheduler:
    def __init__(self, cluster_provider, config: SchedulerConfig | None = None,
                 cache=None, usage_provider=None):
        """cluster_provider: callable returning the current ClusterInfo
        snapshot (the informer-cache analog); usage_provider: callable
        returning per-queue normalized historical usage (usagedb analog)."""
        self.cluster_provider = cluster_provider
        self.config = config or SchedulerConfig()
        self.cache = cache or InMemoryCache()
        self.usage_provider = usage_provider
        # What one session hands the next on the host where the cache
        # brings no arena (framework/arena.py): nothing to configure.
        self.host_arena = HostArena()
        # kairace: single-writer=main
        self.session_id = 0
        # kairace: single-writer=main
        self.last_session = None  # kept for introspection endpoints
        # Overlapped pipeline (DESIGN §10): when the operator arms a
        # commit executor here, Statement.commit registers decisions
        # speculatively and ships the durable writes to it — cycle N's
        # commit I/O overlaps cycle N+1's host prep.  None = the serial
        # path, byte-for-byte the pre-pipeline behavior.
        self.commit_executor = None

    def run_once(self) -> Session:
        """One scheduling cycle (scheduler.go:113-138).

        The cycle runs under an optional whole-cycle deadline
        (config.cycle_deadline_s): checked between actions here, and
        inside actions at every kernel dispatch (Session.dispatch_kernel).
        A device death or deadline expiry mid-action rolls back that
        action's uncommitted statements — committed work stands, phantom
        allocations never reach the cache — and the cycle ends degraded
        instead of wedging the daemon (docs/DEGRADATION.md)."""
        self.session_id += 1
        guard = device_guard()
        trace_id = TRACER.begin_cycle(self.session_id)
        fallbacks0 = guard.fallback_calls
        t0 = time.perf_counter()
        deadline = self.config.cycle_deadline_s
        # The dispatch-level deadline shares t0's origin: taking it after
        # the snapshot build would let kernel dispatches overrun the
        # whole-cycle budget by the full snapshot cost at fleet scale.
        clock0 = guard.clock()
        ssn = None
        escaped: BaseException | None = None
        try:
            with TRACER.span("snapshot", kind="snapshot") as snap_sp:
                visits0 = METRICS.counters.get(
                    "queue_aggregate_pod_visits_total", 0)
                with TRACER.span("snapshot:provider", kind="snapshot_part"):
                    cluster = self.cluster_provider()
                    usage = (self.usage_provider()
                             if self.usage_provider else None)
                ssn = Session(cluster, self.config, self.cache,
                              queue_usage=usage,
                              host_arena=self.host_arena)
                # ``aggregate_pod_visits``: the pods this span counted
                # anew for the queue sums; the PodGroups that stand as
                # they stood keep theirs (``PodGroupInfo.queue_counts``).
                snap_sp.set(nodes=len(cluster.nodes),
                            podgroups=len(cluster.podgroups),
                            aggregate_pod_visits=int(
                                METRICS.counters[
                                    "queue_aggregate_pod_visits_total"]
                                - visits0))
                cache_stats = getattr(cluster, "cache_stats", None)
                if cache_stats:
                    # Incremental ClusterInfo verdict: how many objects
                    # the watch delta actually dirtied this cycle.
                    snap_sp.set(
                        dirty_objects=sum(cache_stats["dirty"].values()),
                        watch_mode=cache_stats["watch_mode"])
                if ssn.pack_stats:
                    # Arena pack verdict (delta vs full rebuild) on the
                    # cycle trace: /debug/trace shows per-cycle pack
                    # behavior next to the span that paid for it.
                    snap_sp.set(**ssn.pack_stats)
                with TRACER.span("snapshot:fragmentation",
                                 kind="snapshot_part"):
                    frag = fragmentation_stats(ssn.snapshot)
                if frag is not None:
                    # Fragmentation gauges ride the snapshot span AND the
                    # metrics registry so bench fleet rows and /metrics both
                    # see per-cycle stranded capacity (ROADMAP item 4a).
                    for res, amount in frag["stranded"].items():
                        METRICS.set_gauge("stranded_resource_total",
                                          amount, resource=res)
                    METRICS.set_gauge("largest_placeable_gang",
                                      float(frag["largest_placeable_gang"]))
                    snap_sp.set(
                        largest_placeable_gang=frag["largest_placeable_gang"],
                        stranded_nodes=frag["stranded_nodes"])
            ssn.trace_id = trace_id
            ssn.commit_executor = self.commit_executor
            if self.commit_executor is not None:
                TRACER.note_pipelined()
            if deadline:
                ssn.cycle_deadline_at = clock0 + deadline
            ssn.aborted = None
            return self._run_session(ssn, deadline, t0)
        except BaseException as exc:
            # Captured explicitly, NOT via sys.exc_info() in the finally:
            # that would also see an outer, already-handled exception when
            # run_once is called from inside an except block, falsely
            # finalizing a healthy cycle as aborted.
            escaped = exc
            raise
        finally:
            # Finalize the flight-recorder trace whatever happened —
            # including exceptions that escaped the action loop's
            # DeviceGuardError handling (e.g. a provider failure).
            # getattr: an exception landing between Session construction
            # and the `ssn.aborted = None` assignment must not turn the
            # finalize into an AttributeError masking the real error.
            aborted = getattr(ssn, "aborted", None)
            if aborted is None and escaped is not None:
                aborted = f"{type(escaped).__name__}: {escaped}"
            # Build the explainability ledger capped at the source: on a
            # sustained over-capacity cluster thousands of groups stay
            # pending — materializing every reason list only for the
            # trace's caps to discard it would be per-cycle garbage.
            from .utils.tracing import CycleTrace
            cap_groups = CycleTrace.MAX_EXPLAIN_GROUPS
            cap_reasons = CycleTrace.MAX_REASONS_PER_GROUP
            explain: dict = {}
            skipped_groups = 0
            resolved: list = []
            if ssn is not None:
                for pg in ssn.cluster.podgroups.values():
                    if not pg.fit_errors and not pg.task_fit_errors:
                        # No rejection this cycle: its stale /explain
                        # record (if any) drops — the group scheduled or
                        # stopped pending.  Only this shard's groups are
                        # in the snapshot, so other shards' records are
                        # untouched.
                        resolved.append(pg.name)
                        continue
                    if len(explain) >= cap_groups:
                        skipped_groups += 1
                        continue
                    reasons = list(pg.fit_errors[:cap_reasons])
                    if len(reasons) < cap_reasons:
                        reasons += [
                            f"task {uid}: {msg}" for uid, msg in
                            sorted(pg.task_fit_errors.items())
                            [:cap_reasons - len(reasons)]]
                    explain[pg.name] = reasons
            TRACER.end_cycle(
                aborted=aborted,
                degraded=(guard.degraded
                          or guard.fallback_calls > fallbacks0),
                explain=explain,
                # Over-cap groups are counted, never silently dropped;
                # folded in pre-publication so readers and the
                # post-mortem dump see the complete trace.
                dropped_rejections=skipped_groups,
                # An aborted cycle proved nothing about the groups it
                # never attempted: keep their records.
                resolved=(resolved if aborted is None else ()))

    def _run_session(self, ssn: Session, deadline, t0: float) -> Session:
        """The action loop of one cycle (split from run_once so the
        flight-recorder finalize wraps the whole body exactly once)."""
        # Deferred: controllers/__init__ imports this module (operator
        # builds Schedulers), so a top-level import would be circular.
        from .controllers.kubeapi import Fenced

        def _abort(where: str, exc: Exception) -> None:
            # Device path dead AND no fallback (or the cycle deadline
            # fired mid-dispatch): abandon the phase, leave the cache
            # consistent, keep the daemon alive.
            rolled = ssn.abort_uncommitted()
            ssn.aborted = f"{where}: {exc}"
            METRICS.inc("scheduler_cycle_aborts")
            if isinstance(exc, CycleDeadlineExceeded):
                # Deadline-driven aborts count in both families: they are
                # aborts AND deadline expiries, wherever the budget ran
                # out (a dispatch inside open/an action, not only the
                # action-boundary check below).
                METRICS.inc("scheduler_cycle_deadline_exceeded")
            if isinstance(exc, Fenced):
                # Deposed mid-commit: the store rejected our writes (a
                # newer leader's epoch is in the Lease).  Everything
                # uncommitted rolls back; this daemon must stop leading
                # (server.py's loop exits on the elector flag).
                METRICS.inc("scheduler_fenced_aborts")
            LOG.warning(
                "cycle %d aborted in %s (%d statements rolled back): %s",
                self.session_id, where, rolled, exc)
            record = getattr(ssn.cache, "record_event", None)
            if record is not None:
                record("CycleAborted", ssn.aborted)

        try:
            try:
                # Plugin open runs device kernels too (proportion's
                # fair-share division) — it degrades, not wedges, like
                # any action.
                ssn.open()
            except DeviceGuardError as exc:
                _abort("session open", exc)
            if ssn.aborted is None:
                for action in build_actions(self.config.actions):
                    if deadline and time.perf_counter() - t0 > deadline:
                        ssn.aborted = (f"cycle deadline {deadline:g}s "
                                       f"reached before action "
                                       f"{action.name}")
                        METRICS.inc("scheduler_cycle_deadline_exceeded")
                        break
                    ta = time.perf_counter()
                    try:
                        with TRACER.span(f"action:{action.name}",
                                         kind="action",
                                         action=action.name):
                            action.execute(ssn)
                    except (DeviceGuardError, Fenced) as exc:
                        _abort(f"action {action.name}", exc)
                        break
                    METRICS.observe(
                        f"action_scheduling_latency_{action.name}",
                        (time.perf_counter() - ta) * 1000.0)
        finally:
            ssn.close()
        cycle_ms = (time.perf_counter() - t0) * 1000.0
        METRICS.observe("e2e_scheduling_latency_milliseconds", cycle_ms)
        # SLO accounting: burn the cycle budget counter when over, and
        # refresh the lifecycle time-in-state gauges once per cycle.
        LIFECYCLE.note_cycle(cycle_ms)
        self.last_session = ssn
        return ssn

    def run(self, cycles: int, period_seconds: float = 0.0) -> None:
        for _ in range(cycles):
            self.run_once()
            if period_seconds:
                time.sleep(period_seconds)
