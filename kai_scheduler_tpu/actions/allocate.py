"""Allocate action: gang-allocate pending jobs in DRF order.

Mirrors pkg/scheduler/actions/allocate/allocate.go:46-116 +
actions/common/allocate.go:20-163: jobs ordered per-queue by DRF, each job's
task chunk placed all-or-nothing under a per-job statement, topology node
subsets tried with checkpoint/rollback, elastic jobs re-enqueued chunk by
chunk.  Placement proposals come from the device kernel
(ops/allocate.allocate_jobs_kernel); fractional-accelerator tasks take the
host path through the sharing-group state (gpu_sharing/gpuSharing.go:20).
"""

from __future__ import annotations

import numpy as np

from ..api.podgroup_info import PodGroupInfo
from ..framework import propose
from ..utils.tracing import TRACER
from .utils import INFINITE, JobsOrderByQueues


# Rounds of bulk mode: each re-orders the jobs still pending and places
# them in one wave.
BULK_MAX_ROUNDS = 8


class AllocateAction:
    name = "allocate"

    def execute(self, ssn) -> None:
        jobs = [pg for pg in ssn.cluster.podgroups.values()
                if pg.has_tasks_to_allocate() and pg.is_ready_for_scheduling()
                # Jobs pointing at unknown queues can't be ordered or
                # charged; skip them (snapshot.pack drops them too).
                and pg.queue_id in ssn.cluster.queues]

        threshold = ssn.config.bulk_allocation_threshold
        if threshold and len(jobs) >= threshold:
            jobs = _execute_bulk(ssn, jobs)
            if not jobs:
                return

        with TRACER.span("allocate:order", kind="allocate",
                         jobs=len(jobs)):
            order = JobsOrderByQueues(
                ssn, jobs,
                ssn.config.queue_depth_per_action.get(self.name, INFINITE))
        failed_signatures: set[str] = set()

        while not order.empty():
            job = order.pop_next_job()
            if job is None:
                break
            if (ssn.config.use_scheduling_signatures
                    and job.scheduling_signature() in failed_signatures):
                job.add_fit_error(
                    "skipped: identical job already failed this cycle")
                order.requeue_queue(job.queue_id)
                continue
            active_before = job.num_active_used()
            succeeded = attempt_to_allocate_job(ssn, job)
            if succeeded:
                # Progress guard: a "successful" attempt that placed
                # nothing (num_active_used unchanged) must not re-enter the
                # queue — re-pushing it would retry the identical attempt
                # forever.  Only elastic jobs that genuinely advanced get
                # another chunk this cycle.
                if (job.has_tasks_to_allocate()
                        and job.num_active_used() > active_before):
                    order.push_job(job)  # elastic: next chunk later
                else:
                    order.requeue_queue(job.queue_id)
            else:
                if ssn.config.use_scheduling_signatures:
                    failed_signatures.add(job.scheduling_signature())
                order.requeue_queue(job.queue_id)


def _execute_bulk(ssn, jobs):
    """Bulk mode: place every plain pending gang through one kernel call
    per round.

    The DRF job order is computed once per round (vs the reference's
    re-order after every job) — the round loop converges to the same
    fixpoint because queue shares update as placements apply and the next
    round re-orders.  Jobs needing host-side state (fractional tasks, DRA
    claims, topology subsets, extra score terms) fall back to the per-job
    path; returns those leftovers.
    """

    takes = propose.wave_filter(ssn)
    if takes is None:
        return jobs

    leftovers = []
    eligible = []
    for pg in jobs:
        tasks = pg.tasks_to_allocate(
            subgroup_order_fn=ssn.pod_set_order_key,
            task_order_fn=ssn.task_order_key, cache_ordered=True)
        (eligible if takes(pg, tasks) else leftovers).append(pg)

    for _ in range(BULK_MAX_ROUNDS):
        pending = [pg for pg in eligible if pg.has_tasks_to_allocate()]
        if not pending:
            break
        # One DRF ordering pass for the round: sort by precomputed
        # (queue key, job key) tuples when plugins provide key functions
        # (pairwise comparators cost milliseconds each at scale);
        # comparator heaps remain the strict path.
        if ssn.queue_key_fn is not None and ssn.job_key_fns \
                and ssn.job_keys_complete:
            by_queue: dict = {}
            for pg in pending:
                by_queue.setdefault(pg.queue_id, []).append(pg)
            for qjobs in by_queue.values():
                qjobs.sort(key=ssn.job_sort_key)
            # Hierarchical ordering, key form: a leaf sorts by the chain
            # of ancestor queue keys root->leaf (each ancestor keyed with
            # its subtree's best job), matching the strict
            # JobsOrderByQueues tree order — a department's standing
            # decides before its leaves do.
            best_in_subtree: dict = {}
            queues = ssn.cluster.queues
            for qid, qjobs in by_queue.items():
                node, job = qid, qjobs[0]
                while node:
                    cur = best_in_subtree.get(node)
                    if cur is None or ssn.job_sort_key(job) \
                            < ssn.job_sort_key(cur):
                        best_in_subtree[node] = job
                    node = getattr(queues.get(node), "parent", None)
            path_keys = {}
            for qid in by_queue:
                chain, node = [], qid
                while node:
                    chain.append(node)
                    node = getattr(queues.get(node), "parent", None)
                path_keys[qid] = tuple(
                    ssn.queue_key_fn(anc, best_in_subtree[anc])
                    for anc in reversed(chain))
            ordered = sorted(
                pending, key=lambda pg: (path_keys[pg.queue_id],
                                         ssn.job_sort_key(pg)))
        else:
            order = JobsOrderByQueues(ssn, pending)
            ordered = []
            while not order.empty():
                job = order.pop_next_job()
                if job is None:
                    break
                ordered.append(job)
                order.requeue_queue(job.queue_id)
                if len(ordered) >= len(pending):
                    break

        # Gate sequentially with projected allocations so one round cannot
        # admit a whole queue past its limit: each admitted job's resources
        # are charged onto the queue attrs during gating and reverted after
        # (the statements re-apply them for the jobs that actually place).
        prop = getattr(ssn, "proportion", None)
        chunks, job_allowed, charged = [], [], []
        for pg in ordered:
            tasks = pg.tasks_to_allocate(
                subgroup_order_fn=ssn.pod_set_order_key,
                task_order_fn=ssn.task_order_key, cache_ordered=True)
            gate = ssn.is_job_over_queue_capacity(pg, tasks).schedulable \
                and ssn.check_pre_predicates(tasks).schedulable \
                if tasks else False
            chunks.append(tasks)
            job_allowed.append(gate)
            if gate and prop is not None and tasks:
                req = np.sum([t.req_vec() for t in tasks], axis=0)
                prop._walk(pg.queue_id, "allocated", req)
                if not pg.is_preemptible():
                    prop._walk(pg.queue_id, "allocated_non_preemptible",
                               req)
                charged.append((pg, req))
        for pg, req in charged:
            prop._walk(pg.queue_id, "allocated", -req)
            if not pg.is_preemptible():
                prop._walk(pg.queue_id, "allocated_non_preemptible", -req)
        if not any(job_allowed):
            break

        # All chunks in one kernel call.
        proposals = propose.place_wave(ssn, list(zip(ordered, chunks)),
                                       job_allowed)
        if proposals is None:
            break

        progressed = False
        jobs_bound = ops = 0
        # One span for the wave, not two a job: a fill wave is thousands
        # of one-pod jobs, and a span costs what a tenth of one's
        # statement does (PERF.md section 6, PR 25).
        with TRACER.span("statement:bulk", kind="commit") as sp:
            for pg, proposal in zip(ordered, proposals):
                if proposal.success:
                    stmt = ssn.statement()
                    stmt.apply_bulk(proposal.placements)
                    if pg.should_pipeline():
                        stmt.convert_all_allocated_to_pipelined(pg.uid)
                    stmt.commit()
                    progressed = True
                    jobs_bound += 1
                    ops += len(proposal.placements)
            sp.set(jobs=jobs_bound, ops=ops)
        if not progressed:
            # Record failures for explainability; leave retries to the
            # scenario actions.
            for pg, tasks, proposal in zip(ordered, chunks, proposals):
                if not proposal.success and tasks:
                    _record_chunk_failure(ssn, pg, tasks)
            break

    # Unplaced jobs need fit errors for explainability (and the
    # consolidation action only considers jobs that failed here).
    for pg in eligible:
        if pg.has_tasks_to_allocate() and not pg.fit_errors:
            tasks = pg.tasks_to_allocate(
                subgroup_order_fn=ssn.pod_set_order_key,
                task_order_fn=ssn.task_order_key, cache_ordered=True)
            if tasks:
                _record_chunk_failure(ssn, pg, tasks)
    return leftovers


def attempt_to_allocate_job(ssn, job: PodGroupInfo,
                            pipeline_only: bool = False,
                            stmt=None, commit: bool = True) -> bool:
    """One gang-chunk allocation attempt (actions/common/allocate.go:20).

    Returns True iff the whole chunk placed; on failure everything this
    attempt did is rolled back.
    """
    ssn.pre_job_allocation(job)
    tasks = job.tasks_to_allocate(
        subgroup_order_fn=ssn.pod_set_order_key,
        task_order_fn=ssn.task_order_key,
        real_allocation=not pipeline_only, cache_ordered=True)
    if not tasks:
        return False

    result = ssn.is_job_over_queue_capacity(job, tasks)
    if not result.schedulable:
        if not pipeline_only:
            job.add_fit_error(result.message)
        return False

    result = ssn.check_pre_predicates(tasks)
    if not result.schedulable:
        if not pipeline_only:
            job.add_fit_error(result.message)
            ssn.cache.record_event("Unschedulable", result.message)
        return False

    # The span of one request: every placement attempt under it.  A job
    # turned away above (no tasks, queue capacity, pre-predicates) never
    # reached one and opens none.
    with TRACER.span("allocate:job", kind="allocate", job=job.name,
                     queue=job.queue_id, tasks=len(tasks)) as sp:
        placed = _place_gated_job(ssn, job, tasks, pipeline_only, stmt,
                                  commit)
        sp.set(success=placed)
    return placed


def _place_gated_job(ssn, job: PodGroupInfo, tasks, pipeline_only: bool,
                     stmt, commit: bool) -> bool:
    """The placement attempts of a job that passed its gates: node
    subsets in order under checkpoint/rollback, then the commit."""
    own_stmt = stmt is None
    if own_stmt:
        stmt = ssn.statement()

    # Per-subgroup topology constraints (allocateSubGroupSet recursion,
    # actions/common/allocate.go:38): each constrained podset resolves its
    # own node subsets; the chunk succeeds only if every podset lands.
    per_podset = any(ps.has_own_topology_constraint()
                     for ps in job.pod_sets.values())
    if per_podset:
        from ..api.pod_info import DEFAULT_SUBGROUP

        def effective_podset(name: str) -> str:
            # Tasks with undeclared subgroups are indexed into the default
            # podset (PodGroupInfo._index_task); resolve the same way.
            return name if name in job.pod_sets else DEFAULT_SUBGROUP

        cp_all = stmt.checkpoint()
        ok = True
        for ps_name in sorted({effective_podset(t.subgroup) for t in tasks},
                              key=lambda n: ssn.pod_set_order_key(
                                  job.pod_sets[n])):
            sub_tasks = [t for t in tasks
                         if effective_podset(t.subgroup) == ps_name]
            podset = job.pod_sets[ps_name]
            placed = False
            for node_subset in ssn.subset_nodes(job, sub_tasks, podset):
                cp = stmt.checkpoint()
                if _allocate_tasks_on_subset(ssn, stmt, job, sub_tasks,
                                             node_subset, pipeline_only):
                    placed = True
                    break
                stmt.rollback(cp)
            if not placed:
                ok = False
                break
        if ok:
            if job.should_pipeline():
                stmt.convert_all_allocated_to_pipelined(job.uid)
            if own_stmt and commit:
                _commit_job(stmt)
            return True
        stmt.rollback(cp_all)
        if own_stmt:
            stmt.discard()
        return False

    for node_subset in ssn.subset_nodes(job, tasks):
        cp = stmt.checkpoint()
        if _allocate_tasks_on_subset(ssn, stmt, job, tasks, node_subset,
                                     pipeline_only):
            if own_stmt and commit:
                _commit_job(stmt)
            return True
        stmt.rollback(cp)

    if own_stmt:
        stmt.discard()
    return False


def _commit_job(stmt) -> None:
    """The commit of one job's own statement."""
    with TRACER.span("statement:commit", kind="commit") as sp:
        sp.set(binds=len(stmt.commit()))


def _apply_task(stmt, task, node_name: str, pipelined: bool,
                gpu_group: str = "") -> None:
    """One task of a job the host path places task by task."""
    with TRACER.span("statement:apply", kind="commit", ops=1):
        place = stmt.pipeline if pipelined else stmt.allocate
        place(task, node_name, gpu_group=gpu_group)


def _allocate_tasks_on_subset(ssn, stmt, job, tasks, node_subset,
                              pipeline_only: bool) -> bool:
    # Fractional tasks and DRA-claim tasks need host-side state the kernel
    # doesn't model (sharing groups, claim bindings): task-by-task path.
    # host_ports: a static chunk mask cannot stop two gang members from
    # sharing a node's port; the per-task path re-masks after each
    # placement (mutation tick) and does.
    host_path = any(t.is_fractional or t.resource_claims
                    or t.res_req.mig_resources or t.host_ports
                    or t.needs_storage_scheduling()
                    for t in tasks)
    if host_path:
        ok = _allocate_task_by_task(ssn, stmt, job, tasks, node_subset,
                                    pipeline_only)
    else:
        proposal = ssn.propose_placements(
            tasks, pipeline_only=pipeline_only, node_subset=node_subset)
        if not proposal.success:
            _record_chunk_failure(ssn, job, tasks)
            return False
        with TRACER.span("statement:apply", kind="commit",
                         ops=len(proposal.placements)):
            stmt.apply_bulk(
                (task, node_name, bool(pipelined or pipeline_only))
                for task, node_name, pipelined in proposal.placements)
        ok = True
    if not ok:
        return False
    # Gang pipelining rule (job_info.go:443 + statement.go:483): once any
    # member waits on releasing resources, the whole gang waits.
    if job.should_pipeline():
        stmt.convert_all_allocated_to_pipelined(job.uid)
    return True


def _allocate_task_by_task(ssn, stmt, job, tasks, node_subset,
                           pipeline_only: bool) -> bool:
    """Host path for chunks containing fractional-GPU tasks."""
    for i, task in enumerate(tasks):
        if task.is_fractional:
            placed = _allocate_fractional(ssn, stmt, task, node_subset,
                                          pipeline_only)
        elif task.resource_claims:
            placed = _allocate_with_claims(ssn, stmt, task, node_subset,
                                           pipeline_only)
        elif task.res_req.mig_resources or task.needs_storage_scheduling():
            # MIG inventory and CSI storage capacity are both sparse
            # host-side state: scan nodes best-score-first with the full
            # NodeInfo checks (which cover both).
            placed = _allocate_mig(ssn, stmt, task, node_subset,
                                   pipeline_only)
        else:
            proposal = ssn.propose_placements(
                [task], pipeline_only=pipeline_only, node_subset=node_subset)
            placed = proposal.success
            if placed:
                t, node_name, pipelined = proposal.placements[0]
                _apply_task(stmt, t, node_name, pipelined or pipeline_only)
        if not placed:
            _record_chunk_failure(ssn, job, tasks, failed_task=task,
                                  placed_count=i)
            return False
    return True


def _nodes_best_first(ssn, task, node_subset):
    """The nodes a host-path task may take, best score first: inside the
    node subset and the task's hard mask, real (non-padding) rows only."""
    scores = ssn.score_nodes_for_task(task)[:len(ssn.snapshot.node_names)]
    hard_mask = ssn.compute_hard_mask([task])
    for node_idx in np.argsort(-scores, kind="stable"):
        if node_subset is not None and not node_subset[node_idx]:
            continue
        if hard_mask is not None and not hard_mask[0][node_idx]:
            continue
        yield ssn.cluster.nodes[ssn.snapshot.node_names[int(node_idx)]]


def _allocate_fractional(ssn, stmt, task, node_subset,
                         pipeline_only: bool) -> bool:
    """gpu_sharing.AllocateFractionalGPUTaskToNode (gpuSharing.go:20)."""
    for node in _nodes_best_first(ssn, task, node_subset):
        if not pipeline_only and node.is_task_allocatable(task):
            groups = node.find_gpu_groups_for_task(task,
                                                   allow_releasing=False)
            if groups is not None:
                _apply_task(stmt, task, node.name, False, ",".join(groups))
                return True
        if node.is_task_allocatable_on_releasing_or_idle(task):
            groups = node.find_gpu_groups_for_task(task, allow_releasing=True)
            if groups is not None:
                _apply_task(stmt, task, node.name, True, ",".join(groups))
                return True
    return False


def _allocate_mig(ssn, stmt, task, node_subset,
                  pipeline_only: bool) -> bool:
    """MIG / CSI-storage path: best-scoring node whose sparse host-side
    inventory fits — per-profile MIG room (node_info.has_mig_room;
    reference resource_info.go:153-165 scalar accounting) and CSI storage
    capacity (node_info.is_task_storage_allocatable; reference
    node_info.go:200-268), both folded into is_task_allocatable."""
    for node in _nodes_best_first(ssn, task, node_subset):
        if not pipeline_only and node.is_task_allocatable(task):
            _apply_task(stmt, task, node.name, False)
            return True
        if node.is_task_allocatable_on_releasing_or_idle(task):
            _apply_task(stmt, task, node.name, True)
            return True
    return False


def _allocate_with_claims(ssn, stmt, task, node_subset,
                          pipeline_only: bool) -> bool:
    """DRA path: best-scoring node where every referenced claim is
    available (dynamicresources.go PrePredicate + assume)."""
    dra = next((p for p in ssn.plugins
                if p.name == "dynamicresources"), None)
    for node in _nodes_best_first(ssn, task, node_subset):
        if dra is not None and not dra.claims_schedulable(task, node.name):
            continue
        if not pipeline_only and node.is_task_allocatable(task):
            _apply_task(stmt, task, node.name, False)
            return True
        if node.is_task_allocatable_on_releasing_or_idle(task):
            _apply_task(stmt, task, node.name, True)
            return True
    return False


def _record_chunk_failure(ssn, job, tasks, failed_task=None,
                          placed_count: int | None = None) -> None:
    """Explainability events (actions/common/allocate.go:198-234)."""
    gang = any(ps.min_available > 1 for ps in job.pod_sets.values())
    if failed_task is None:
        msg = (f"Resources were not found for {len(tasks)} pods of job "
               f"{job.namespace}/{job.name}")
    elif gang:
        msg = (f"Resources were found for {placed_count} pods while "
               f"{len(tasks)} are required for gang scheduling of job "
               f"{job.namespace}/{job.name}")
    else:
        msg = (f"Resources were not found for pod {failed_task.namespace}/"
               f"{failed_task.name}")
    job.add_fit_error(msg)
    # Explainability ledger: the rejection lands in the live cycle trace
    # the moment it happens (GET /explain?podgroup=<name>); the cycle
    # driver merges fit errors again at end_cycle, deduplicated.
    TRACER.note_rejection(job.name, msg)
    ssn.cache.record_event("Unschedulable", msg)
