"""Reclaim action: cross-queue fair-share enforcement.

Mirrors pkg/scheduler/actions/reclaim/reclaim.go:47-143: for each pending
job whose queue is under its fair share (CanReclaimResources gate), build
the victim set from OTHER queues' preemptible running jobs, order victims
weakest-claim-first, and run the scenario solver; validation is the
proportion plugin's reclaimable rules + minruntime.  Scheduling-signature
dedup skips lookalike jobs that already failed (:74-82).

The victims are a stream (``VictimStream``): one pass over the PodGroups a
cycle, ordered in bulk, and popped only as far as a reclaimer's solver
reads (``max_victims_considered`` victims that the filters admit), never
drained to hand it a thousandth of the list.
"""

from __future__ import annotations

from ..api.podgroup_info import PodGroupInfo
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from .preempt import FILTER_CHUNK
from .solvers import solve_job
from .utils import INFINITE, JobsOrderByQueues

# The victim PodGroups the action read off its stream to give its
# reclaimers their candidates: those popped for a reclaimer and those read
# again from the kept head; the one survey's pass is not among them.
EXAMINED = "reclaim_victims_examined_total"


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
        # The victim survey is expensive (a pass over every podgroup, a key
        # a victim): one stream a cycle, built by the first reclaimer past
        # its gates and read from its head by each.  A successful reclaim
        # takes the jobs it evicted whole out of what was read; what was
        # not read yet is ordered as it is read, a queue by its share
        # after the commits so far (VictimStream says what is tolerated).
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
            # (the first reclaimer of the cycle pays it), the reading of
            # its stream through the filters, and the solver.  A job
            # turned away above opens none.
            with TRACER.span("reclaim:job", kind="reclaim", job=job.name,
                             queue=job.queue_id) as sp:
                if survey is None:
                    with TRACER.span("reclaim:survey", kind="reclaim") as sv:
                        survey = VictimStream(ssn)
                        sv.set(victims=survey.surveyed)
                victims, admitted, filtered = survey.candidates(ssn, job)
                sp.set(victims=admitted, filtered=filtered, success=False)
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
                survey.committed(ssn, result.evicted_jobs)
            elif ssn.config.use_scheduling_signatures:
                failed_signatures.add(sig)
            order.requeue_queue(job.queue_id)
        if survey is not None:
            survey.close()


class VictimStream:
    """The cycle's reclaim victims, weakest claim first, read as far as
    the reclaimers' solvers read.

    All queues' running preemptible jobs (reclaim.go:123-143) in the
    REVERSED hierarchical queue order with reversed job order inside each
    queue — the least deserving queue's weakest claim first
    (getOrderedVictimsQueue -> JobsOrderByQueues VictimQueue mode, which
    stays the one definition of the order).  One pass over the PodGroups
    and one bulk build; then the order is popped on demand.  A pop depends
    on the pops before it and on nothing after it, so the first n of a
    partial drain are the first n of the full one.

    ``read`` is what was popped so far, in its order: every reclaimer of
    the cycle starts at its head, of its own queue's complement, and the
    order is popped further only when a reader runs past it.

    Between two reclaimers of one cycle: what has been read stays read, in
    its order, less the jobs a commit took whole (an elastic victim that
    shed only its surplus stays a candidate while its core gang runs).
    What has not been read is ordered as it is read: a leaf's jobs in the
    order of the build (a commit touches only victims that were read, so
    no unread job's key has changed), a queue against its siblings by the
    key of its last attach, which for the queue just popped is the
    session's share after the commit.  The reference orders anew for every
    reclaimer; the order is advisory and the validators stay exact, so
    that drift is tolerated until the next cycle.  A job that a commit
    took whole before the stream yielded it is never yielded: it is
    skipped at the pop by ``num_active_allocated() == 0``."""

    def __init__(self, ssn):
        queues = ssn.cluster.queues
        # What the pass walked: every PodGroup is asked, and
        # ``num_active_allocated()`` reads the pods of those it is asked
        # of whose statuses changed since they were last counted.  The
        # order's keys (``ordering._below_min``) read the same kept
        # counts.
        victims, pod_visits = [], 0
        for pg in ssn.cluster.podgroups.values():
            if pg.queue_id in queues and pg.is_preemptible():
                pod_visits += pg.uncounted_pods()
                if pg.num_active_allocated() > 0:
                    victims.append(pg)
        METRICS.inc("fleet_walk_pod_visits_total", pod_visits,
                    walk="victim_survey")
        TRACER.stamp("reclaim:survey", podgroups=len(ssn.cluster.podgroups),
                     pod_visits=pod_visits)
        self.surveyed = len(victims)
        self._order = JobsOrderByQueues(ssn, victims, victim_mode=True)
        self.read: list[PodGroupInfo] = []

    def _read_to(self, end: int) -> None:
        """Pop the order until ``read`` holds ``end`` victims or it is
        empty."""
        while len(self.read) < end:
            job = self._order.pop_next_job()
            if job is None:
                return
            self._order.requeue_queue(job.queue_id)
            if job.num_active_allocated() > 0:
                self.read.append(job)

    def candidates(self, ssn, reclaimer) -> tuple[list, int, int]:
        """The first ``max_victims_considered`` victims, in stream order,
        that are not of the reclaimer's queue and that the session's
        filters admit: ``filter(whole list less own queue)[:cap]``, found
        from the head in chunks (the filters' contract,
        ``Session.filter_reclaim_victims``).  With them, how many the
        filters admitted and how many they dropped among what was read."""
        cap = ssn.config.max_victims_considered
        victims: list = []
        pos = filtered = 0
        while len(victims) < cap:
            # The cap's worth first: all there is to read where no filter
            # drops a victim and none is the reclaimer's own.
            step = cap if pos == 0 else max(cap - len(victims), FILTER_CHUNK)
            self._read_to(pos + step)
            chunk = self.read[pos:pos + step]
            if not chunk:
                break
            pos += len(chunk)
            others = [pg for pg in chunk
                      if pg.queue_id != reclaimer.queue_id]
            kept = ssn.filter_reclaim_victims(reclaimer, others)
            filtered += len(others) - len(kept)
            victims.extend(kept)
        METRICS.inc(EXAMINED, pos)
        return victims[:cap], len(victims), filtered

    def committed(self, ssn, evicted_jobs) -> None:
        """A solve committed: the jobs it took whole leave what was
        read."""
        gone = {uid for uid in evicted_jobs
                if ssn.cluster.podgroups[uid].num_active_allocated() == 0}
        self.read = [pg for pg in self.read if pg.uid not in gone]

    def drain(self) -> list[PodGroupInfo]:
        """The whole stream, read to its end."""
        self._read_to(self.surveyed)
        return list(self.read)

    def close(self) -> None:
        """The cycle's last reclaimer is done: what was not read goes now
        and not at the collector's next full pass
        (``JobsOrderByQueues.release``)."""
        self._order.release()
        self.read = []


def survey_reclaim_victims(ssn) -> list[PodGroupInfo]:
    """Every reclaim victim of the session in the stream's order; the
    per-reclaimer filtering (own queue, plugins) happens at use site."""
    stream = VictimStream(ssn)
    victims = stream.drain()
    stream.close()
    return victims
