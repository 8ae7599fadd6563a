"""Preempt action: in-queue priority preemption.

Mirrors pkg/scheduler/actions/preempt/preempt.go:46-161: a pending job may
preempt strictly-lower-priority preemptible jobs in its OWN queue (:126-155
victim filter); the scenario solver simulates eviction + re-placement and
preempt validators (minruntime) approve.
"""

from __future__ import annotations

from ..api.podgroup_info import PodGroupInfo
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from .solvers import solve_job
from .utils import INFINITE, JobsOrderByQueues


class PreemptAction:
    name = "preempt"

    def execute(self, ssn) -> None:
        pending = [pg for pg in ssn.cluster.podgroups.values()
                   if pg.has_tasks_to_allocate()
                   and pg.is_ready_for_scheduling()
                   and pg.queue_id in ssn.cluster.queues]
        if not pending:
            return
        order = JobsOrderByQueues(
            ssn, pending,
            ssn.config.queue_depth_per_action.get(self.name, INFINITE))
        failed_signatures: set[str] = set()
        # Per-queue victim survey, maintained incrementally (the per-job
        # rescan of every podgroup dominates cycle time at scale).
        survey: dict | None = None

        while not order.empty():
            job = order.pop_next_job()
            if job is None:
                break
            sig = job.scheduling_signature()
            if ssn.config.use_scheduling_signatures \
                    and sig in failed_signatures:
                order.requeue_queue(job.queue_id)
                continue
            if survey is None:
                # The first preemptor of the cycle pays the walk over
                # every PodGroup; the others reuse it.
                with TRACER.span("preempt:survey", kind="preempt") as sv:
                    survey = survey_preempt_victims(ssn)
                    sv.set(queues=len(survey),
                           victims=sum(len(v) for v in survey.values()))
            victims = [pg for pg in survey.get(job.queue_id, [])
                       if pg.priority < job.priority and pg.uid != job.uid]
            victims = ssn.filter_preempt_victims(job, victims)
            if not victims:
                order.requeue_queue(job.queue_id)
                continue
            result = solve_job(ssn, job, victims,
                               ssn.validate_preempt_scenario, self.name)
            # Both series move, one of them by 0, so that a process whose
            # preemptors were all solved reads 0 unsolved and not nothing.
            METRICS.inc("preemptors_solved_total", int(result.success),
                        result="solved")
            METRICS.inc("preemptors_solved_total", int(not result.success),
                        result="unsolved")
            if result.success:
                gone = {uid for uid in result.evicted_jobs
                        if ssn.cluster.podgroups[uid]
                        .num_active_allocated() == 0}
                survey[job.queue_id] = [
                    pg for pg in survey.get(job.queue_id, [])
                    if pg.uid not in gone]
            elif ssn.config.use_scheduling_signatures:
                failed_signatures.add(sig)
            order.requeue_queue(job.queue_id)


def survey_preempt_victims(ssn) -> dict:
    """queue -> running preemptible jobs ordered weakest-first (lowest
    priority, newest); per-preemptor filtering happens at use site
    (preempt.go:126-155)."""
    survey: dict[str, list] = {}
    for pg in ssn.cluster.podgroups.values():
        if pg.is_preemptible() and pg.num_active_allocated() > 0:
            survey.setdefault(pg.queue_id, []).append(pg)
    for victims in survey.values():
        victims.sort(key=lambda pg: (pg.priority, -pg.creation_ts))
    return survey


def collect_preempt_victims(ssn, preemptor: PodGroupInfo
                            ) -> list[PodGroupInfo]:
    """Compatibility helper: per-preemptor view of the survey."""
    return [pg for pg in survey_preempt_victims(ssn).get(
        preemptor.queue_id, [])
        if pg.priority < preemptor.priority and pg.uid != preemptor.uid]
