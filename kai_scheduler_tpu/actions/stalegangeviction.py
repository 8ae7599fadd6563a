"""Stale gang eviction: broken gangs don't hold resources forever.

Mirrors pkg/scheduler/actions/stalegangeviction/stalegangeviction.go:29-90:
a gang running below its minAvailable (stale, job_info.go:417) past the
grace period has ALL its remaining pods evicted so the resources return to
the pool and the gang can be rescheduled whole later.
"""

from __future__ import annotations

from ..utils.metrics import METRICS
from ..utils.tracing import TRACER


class StaleGangEvictionAction:
    name = "stalegangeviction"

    def execute(self, ssn) -> None:
        now = ssn.cluster.now
        jobs = list(ssn.cluster.podgroups.values())
        # ``is_stale()`` reads its answer off what the PodGroup keeps of
        # its pods' statuses, and off its pods where a status changed
        # since: what this pass walked, on the counter and on the
        # action's span (scheduler.py opens it).
        pod_visits = 0
        for job in jobs:
            pod_visits += job.uncounted_pods()
            if not job.is_stale():
                continue
            grace = job.staleness_grace_seconds
            if grace is None:
                grace = ssn.config.default_staleness_grace_seconds
            stale_since = job.last_start_ts
            if stale_since is not None and (now - stale_since) < grace:
                continue
            stmt = ssn.statement()
            for task in list(job.pods.values()):
                if task.is_active_used():
                    stmt.evict(task)
            stmt.commit()
            ssn.cache.record_event(
                "StaleGangEvicted",
                f"gang {job.namespace}/{job.name} below minAvailable for "
                f">{grace}s; evicting {len(stmt.ops)} pods")
        METRICS.inc("fleet_walk_pod_visits_total", pod_visits,
                    walk="stale_gangs")
        TRACER.stamp(f"action:{self.name}", podgroups=len(jobs),
                     pod_visits=pod_visits)
