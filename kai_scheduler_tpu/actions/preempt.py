"""Preempt action: in-queue priority preemption.

Mirrors pkg/scheduler/actions/preempt/preempt.go:46-161: a pending job may
preempt strictly-lower-priority preemptible jobs in its OWN queue (:126-155
victim filter); the scenario solver simulates eviction + re-placement and
preempt validators (minruntime) approve.
"""

from __future__ import annotations

from bisect import bisect_left

from ..api.podgroup_info import PodGroupInfo
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from .solvers import VictimOffers, solve_job, victim_offer
from .utils import INFINITE, JobsOrderByQueues

# The victim PodGroups the action read to give its preemptors their
# candidates and their budgets; the one survey's walk is not among them.
EXAMINED = "preempt_victims_examined_total"
# The least the victim filters are handed at a time once a first chunk of
# ``max_victims_considered`` came back short.
FILTER_CHUNK = 64


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
        # One ledger a queue, built from the cycle's one survey (the
        # per-job rescan of every podgroup dominates cycle time at scale)
        # and patched by every commit.
        ledgers: dict | None = None

        while not order.empty():
            job = order.pop_next_job()
            if job is None:
                break
            sig = job.scheduling_signature()
            if ssn.config.use_scheduling_signatures \
                    and sig in failed_signatures:
                order.requeue_queue(job.queue_id)
                continue
            if ledgers is None:
                # The first preemptor of the cycle pays the walk over
                # every PodGroup; the others reuse it.
                with TRACER.span("preempt:survey", kind="preempt") as sv:
                    ledgers = {queue: VictimLedger(jobs) for queue, jobs
                               in survey_preempt_victims(ssn).items()}
                    sv.set(queues=len(ledgers),
                           victims=sum(len(led.jobs)
                                       for led in ledgers.values()))
            ledger = ledgers.get(job.queue_id)
            victims, offers = (ledger.candidates(ssn, job) if ledger
                               else ([], None))
            if not victims:
                order.requeue_queue(job.queue_id)
                continue
            result = solve_job(ssn, job, victims,
                               ssn.validate_preempt_scenario, self.name,
                               offers=offers)
            # Both series move, one of them by 0, so that a process whose
            # preemptors were all solved reads 0 unsolved and not nothing.
            METRICS.inc("preemptors_solved_total", int(result.success),
                        result="solved")
            METRICS.inc("preemptors_solved_total", int(not result.success),
                        result="unsolved")
            if result.success:
                ledger.committed(ssn, job, result.evicted_jobs)
            elif ssn.config.use_scheduling_signatures:
                failed_signatures.add(sig)
            order.requeue_queue(job.queue_id)


class VictimLedger:
    """One queue's victims for one cycle of the preempt action: what its
    preemptors, one after another, take their candidates from.

    ``jobs`` is the survey's list, weakest first, and stays the survey's:
    a job leaves it when a commit took its last active pod, and none
    joins.  ``priorities`` stands beside it so that "strictly lower
    priority than this preemptor" (preempt.go:126-155) is a bisect and a
    slice; a preemptor that runs and is preemptible is in the list too,
    at its own priority, and so never inside its own slice.  ``_offers``
    holds what a victim offers a solve (``solvers.victim_offer``), read
    off its pods when a preemptor first needs it and again only after a
    commit touched the job."""

    def __init__(self, jobs: list):
        self.jobs = list(jobs)
        self.priorities = [pg.priority for pg in jobs]
        self._offers: dict = {}

    def candidates(self, ssn, preemptor) -> tuple[list, VictimOffers]:
        """The first ``max_victims_considered`` victims of strictly lower
        priority that the session's filters admit, and their offers:
        ``filter(whole slice)[:cap]``, found from the head in chunks (the
        filters' contract, ``Session.filter_preempt_victims``)."""
        cap = ssn.config.max_victims_considered
        end = bisect_left(self.priorities, preemptor.priority)
        victims: list = []
        pos = examined = 0
        while len(victims) < cap and pos < end:
            # The cap's worth first: all there is to read where no filter
            # drops a victim.
            step = cap if pos == 0 else max(cap - len(victims), FILTER_CHUNK)
            chunk = self.jobs[pos:min(end, pos + step)]
            pos += len(chunk)
            examined += len(chunk)
            victims.extend(ssn.filter_preempt_victims(preemptor, chunk))
        del victims[cap:]
        offers = VictimOffers()
        for pg in victims:
            offer = self._offers.get(pg.uid)
            if offer is None:
                offer = self._offers[pg.uid] = victim_offer(pg)
                examined += 1
            offers.append(*offer)
        METRICS.inc(EXAMINED, examined)
        return victims, offers

    def committed(self, ssn, preemptor, evicted_jobs) -> None:
        """Patch after ``preemptor``'s solve committed: the jobs it took
        from leave with their last active pod or offer what is left (a
        job that shed only its surplus), and the preemptor's own pods are
        placed now.  A solve that fails discards its statement and
        patches nothing."""
        self._offers.pop(preemptor.uid, None)
        gone = []
        for uid in evicted_jobs:
            self._offers.pop(uid, None)
            pg = ssn.cluster.podgroups[uid]
            if pg.num_active_allocated() == 0:
                gone.append(self.jobs.index(pg))
        for i in sorted(gone, reverse=True):
            del self.jobs[i], self.priorities[i]
        METRICS.inc(EXAMINED, len(evicted_jobs))


def survey_preempt_victims(ssn) -> dict:
    """queue -> running preemptible jobs ordered weakest-first (lowest
    priority, newest); per-preemptor filtering happens at use site
    (preempt.go:126-155)."""
    survey: dict[str, list] = {}
    # What the pass walked: every PodGroup is asked, and
    # ``num_active_allocated()`` reads the pods of the preemptible ones
    # whose statuses changed since they were last counted.
    pod_visits = 0
    for pg in ssn.cluster.podgroups.values():
        if pg.is_preemptible():
            pod_visits += pg.uncounted_pods()
            if pg.num_active_allocated() > 0:
                survey.setdefault(pg.queue_id, []).append(pg)
    METRICS.inc("fleet_walk_pod_visits_total", pod_visits,
                walk="victim_survey")
    TRACER.stamp("preempt:survey", podgroups=len(ssn.cluster.podgroups),
                 pod_visits=pod_visits)
    for victims in survey.values():
        victims.sort(key=lambda pg: (pg.priority, -pg.creation_ts))
    return survey


def collect_preempt_victims(ssn, preemptor: PodGroupInfo
                            ) -> list[PodGroupInfo]:
    """Compatibility helper: per-preemptor view of the survey."""
    return [pg for pg in survey_preempt_victims(ssn).get(
        preemptor.queue_id, [])
        if pg.priority < preemptor.priority and pg.uid != preemptor.uid]
