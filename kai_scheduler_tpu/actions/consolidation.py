"""Consolidation action: defragment by relocating running preemptible pods.

Mirrors pkg/scheduler/actions/consolidation/consolidation.go:32-128: for a
pending job that won't fit as-is, try moving running preemptible pods onto
other nodes to create contiguous room.  A solution is valid ONLY if every
displaced pod is re-placed (allPodsReallocated :121-128) — consolidation
never shrinks the running set.
"""

from __future__ import annotations

import numpy as np

from ..api import resources as rs
from ..api.podgroup_info import PodGroupInfo
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from .solvers import fractional_headroom, solve_job
from .utils import INFINITE, JobsOrderByQueues


class ConsolidationAction:
    name = "consolidation"

    def execute(self, ssn) -> None:
        pending = [pg for pg in ssn.cluster.podgroups.values()
                   if pg.has_tasks_to_allocate()
                   and pg.is_ready_for_scheduling()
                   and pg.queue_id in ssn.cluster.queues]
        if not pending:
            return
        with TRACER.span("consolidation:order", kind="consolidation",
                         jobs=len(pending)):
            order = JobsOrderByQueues(
                ssn, pending,
                ssn.config.queue_depth_per_action.get(self.name, INFINITE))
        failed_signatures: set = set()

        while not order.empty():
            job = order.pop_next_job()
            if job is None:
                break
            sig = job.scheduling_signature()
            if ssn.config.use_scheduling_signatures \
                    and sig in failed_signatures:
                order.requeue_queue(job.queue_id)
                continue
            # Relocation conserves total free resources: if the gang does
            # not fit the cluster's aggregate idle+releasing space, no
            # amount of defragmentation can host it.  The dense mirrors
            # count a partially-shared device as fully used, but
            # relocating fractions CAN empty whole devices — so each
            # sharing group's unused remainder is added back before the
            # bound is applied (otherwise fractional defragmentation,
            # consolidationFractional_test.go, is unreachable).
            tasks = job.tasks_to_allocate(
                subgroup_order_fn=ssn.pod_set_order_key,
                task_order_fn=ssn.task_order_key, real_allocation=False)
            # Node-fit vector (MIG excluded from the GPU axis): MIG
            # inventory is per-profile and host-checked in simulation.
            total_req = np.sum([t.res_req.to_vec(mig_as_gpu=False)
                                for t in tasks], axis=0) if tasks else None
            headroom = np.zeros(ssn.node_idle.shape[1])
            headroom[rs.RES_GPU] = fractional_headroom(ssn)
            total_free = ssn.node_idle.sum(axis=0) \
                + ssn.node_releasing.sum(axis=0) + headroom
            # The same bound on the nodes the job's static constraints
            # admit (a selector, a required node affinity, taints): what
            # is idle on a node it may not use seats nothing, and a pod
            # pinned inside those nodes frees nothing there by moving.
            if total_req is None or np.any(total_req > total_free + 1e-9) \
                    or not ssn.relocation_can_seat(job, tasks,
                                                   total_req - headroom):
                if ssn.config.use_scheduling_signatures:
                    failed_signatures.add(sig)
                order.requeue_queue(job.queue_id)
                continue
            # The span of one job past the bound above: the walk that
            # collects what may be moved, and the solver.  A job turned
            # away above opens none.
            with TRACER.span("consolidation:job", kind="consolidation",
                             job=job.name, queue=job.queue_id) as sp:
                with TRACER.span("consolidation:victims",
                                 kind="consolidation") as sv:
                    victims = collect_consolidation_victims(ssn, job, tasks)
                    sv.set(victims=len(victims))
                sp.set(victims=len(victims), success=False)
                result = None
                if victims:
                    result = solve_job(ssn, job, victims,
                                       lambda scenario: True, self.name,
                                       require_all_victims_replaced=True)
                    sp.set(success=result.success)
            if result is not None and not result.success \
                    and ssn.config.use_scheduling_signatures:
                failed_signatures.add(sig)
            order.requeue_queue(job.queue_id)


def collect_consolidation_victims(ssn, job: PodGroupInfo, tasks
                                  ) -> list[PodGroupInfo]:
    """Running preemptible jobs from any queue — candidates to shuffle, not
    to kill (they must all land again).  Lowest priority first; within a
    priority the jobs whose leaving, alone, seats one of ``tasks`` come
    before the others, and the newest first among those.  The solver
    evicts a prefix of this list: a job that shares its nodes frees no
    seat by leaving, and ahead of one that does it is moved for nothing."""
    victims, rows_victim, rows_node, rows_vec = [], [], [], []
    # What the pass walked: every PodGroup is asked, and the running pods
    # of those past the gate are read off their pods.
    pod_visits = 0
    for pg in ssn.cluster.podgroups.values():
        if pg.uid == job.uid or not pg.is_preemptible() \
                or pg.queue_id not in ssn.cluster.queues:
            continue
        pod_visits += len(pg.pods)
        running = [t for t in pg.pods.values() if t.is_active_allocated()]
        if not running:
            continue
        for t in running:
            idx = ssn.node_index(t.node_name)
            if idx >= 0:
                rows_victim.append(len(victims))
                rows_node.append(idx)
                rows_vec.append(t.res_req.to_vec(mig_as_gpu=False))
        victims.append(pg)
    METRICS.inc("fleet_walk_pod_visits_total", pod_visits,
                walk="victim_survey")
    TRACER.stamp("consolidation:victims",
                 podgroups=len(ssn.cluster.podgroups), pod_visits=pod_visits)
    seats = _seats_a_task(ssn, tasks, len(victims), rows_victim, rows_node,
                          rows_vec)
    order = sorted(range(len(victims)), key=lambda i: (
        victims[i].priority, not seats[i], -victims[i].creation_ts))
    return [victims[i] for i in order]


def _seats_a_task(ssn, tasks, victims: int, rows_victim, rows_node,
                  rows_vec) -> np.ndarray:
    """[victims] bool: the victim's leaving gives some node the room one
    of ``tasks`` asks and does not find there now.  One row a running pod
    of a victim: its victim, its node and what it holds there."""
    seats = np.zeros(victims, bool)
    if not rows_vec:
        return seats
    nodes = len(ssn.node_idle)
    pair, of_row = np.unique(
        np.asarray(rows_victim, np.int64) * nodes + np.asarray(rows_node),
        return_inverse=True)
    freed = np.zeros((len(pair), ssn.node_idle.shape[1]))
    np.add.at(freed, of_row, np.asarray(rows_vec))
    at = pair % nodes
    room = (ssn.node_idle[at] + ssn.node_releasing[at])[:, None, :]
    asks = np.unique([t.res_req.to_vec(mig_as_gpu=False) for t in tasks],
                     axis=0)[None, :, :]
    lacked = np.any(room + 1e-9 < asks, axis=2)
    held = np.all(room + freed[:, None, :] + 1e-9 >= asks, axis=2)
    seats[pair[np.any(lacked & held, axis=1)] // nodes] = True
    return seats
