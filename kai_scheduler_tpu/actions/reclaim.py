"""Reclaim action: cross-queue fair-share enforcement.

Mirrors pkg/scheduler/actions/reclaim/reclaim.go:47-143: for each pending
job whose queue is under its fair share (CanReclaimResources gate), build
the victim set from OTHER queues' preemptible running jobs, order victims
weakest-claim-first, and run the scenario solver; validation is the
proportion plugin's reclaimable rules + minruntime.  Scheduling-signature
dedup skips lookalike jobs that already failed (:74-82).
"""

from __future__ import annotations

from ..api.podgroup_info import PodGroupInfo
from ..utils.tracing import TRACER
from .solvers import solve_job
from .utils import INFINITE, JobsOrderByQueues


class ReclaimAction:
    name = "reclaim"

    def execute(self, ssn) -> None:
        pending = [pg for pg in ssn.cluster.podgroups.values()
                   if pg.has_tasks_to_allocate()
                   and pg.is_ready_for_scheduling()
                   and pg.queue_id in ssn.cluster.queues]
        if not pending:
            return
        with TRACER.span("reclaim:order", kind="reclaim",
                         jobs=len(pending)):
            order = JobsOrderByQueues(
                ssn, pending,
                ssn.config.queue_depth_per_action.get(self.name, INFINITE))
        failed_signatures: set[str] = set()
        # Victim survey is expensive (scans every podgroup, ranks by queue
        # dominant share): compute once and invalidate only when a
        # successful reclaim changes the cluster.
        survey = None

        while not order.empty():
            job = order.pop_next_job()
            if job is None:
                break
            sig = job.scheduling_signature()
            if ssn.config.use_scheduling_signatures \
                    and sig in failed_signatures:
                order.requeue_queue(job.queue_id)
                continue
            if not ssn.can_reclaim_resources(job):
                order.requeue_queue(job.queue_id)
                continue
            # The span of one reclaimer past its gates: the victim survey
            # (the first reclaimer of the cycle pays it), the filter and
            # the solver.  A job turned away above opens none.
            with TRACER.span("reclaim:job", kind="reclaim", job=job.name,
                             queue=job.queue_id) as sp:
                if survey is None:
                    with TRACER.span("reclaim:survey", kind="reclaim") as sv:
                        survey = survey_reclaim_victims(ssn)
                        sv.set(victims=len(survey))
                victims = [pg for pg in survey
                           if pg.queue_id != job.queue_id]
                surveyed = len(victims)
                victims = ssn.filter_reclaim_victims(job, victims)
                sp.set(victims=len(victims),
                       filtered=surveyed - len(victims), success=False)
                result = None
                if victims:
                    result = solve_job(ssn, job, victims,
                                       ssn.validate_reclaim_scenario,
                                       self.name)
                    sp.set(success=result.success)
            if result is None:
                order.requeue_queue(job.queue_id)
                continue
            if result.success:
                # Incremental survey maintenance: evicted victims leave the
                # candidate pool; queue-share drift is tolerated until the
                # next full cycle (the reference re-sorts per job, but the
                # order is advisory — validators stay exact).
                # Elastic victims may have only shed surplus tasks; keep
                # them as candidates while their core gang still runs.
                gone = {uid for uid in result.evicted_jobs
                        if ssn.cluster.podgroups[uid]
                        .num_active_allocated() == 0}
                survey = [pg for pg in survey if pg.uid not in gone]
            elif ssn.config.use_scheduling_signatures:
                failed_signatures.add(sig)
            order.requeue_queue(job.queue_id)


def survey_reclaim_victims(ssn) -> list[PodGroupInfo]:
    """All queues' running preemptible jobs (reclaim.go:123-143), ordered
    by the REVERSED hierarchical queue order with reversed job order
    inside each queue — the least deserving queue's weakest claim first
    (getOrderedVictimsQueue -> JobsOrderByQueues VictimQueue mode).
    Per-reclaimer filtering (own queue) happens at use site."""
    victims = []
    for pg in ssn.cluster.podgroups.values():
        if pg.queue_id not in ssn.cluster.queues:
            continue
        if not pg.is_preemptible():
            continue
        if pg.num_active_allocated() == 0:
            continue
        victims.append(pg)
    order = JobsOrderByQueues(ssn, victims, victim_mode=True)
    out = []
    while not order.empty():
        job = order.pop_next_job()
        if job is None:
            break
        out.append(job)
        order.requeue_queue(job.queue_id)
    return out
