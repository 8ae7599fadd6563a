"""PodGroup (job) info: gang semantics, subgroup tree, task selection.

Mirrors the behavioral surface of pkg/scheduler/api/podgroup_info/
(job_info.go, allocation_info.go, subgroup_info/): a job is a PodGroup plus
its tasks, organized into pod sets (leaf subgroups with their own
minAvailable) under a hierarchical subgroup tree.  Key reproduced behaviors:
gang readiness (job_info.go:434), staleness (:417), elasticity (:408),
pipelining decision (:443), task selection for the next allocation attempt
(allocation_info.go:26-177), and the scheduling-constraints signature
(:547) used to skip provably-unschedulable lookalike jobs.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from itertools import chain
from typing import Callable, Iterable, Optional

import numpy as np

from ..utils.metrics import METRICS
from . import resources as rs
from .pod_info import DEFAULT_SUBGROUP, PodInfo
from .pod_status import (_ACTIVE_ALLOCATED, _ACTIVE_USED, _ALIVE, PodStatus,
                         is_alive)

_PENDING = PodStatus.PENDING.value
_PIPELINED = PodStatus.PIPELINED.value
_SUCCEEDED = PodStatus.SUCCEEDED.value


class PodSet:
    """Leaf subgroup: a set of interchangeable tasks with a gang minimum.

    May carry its own topology constraint (subgroup_info.SubGroupInfo
    TopologyConstraint — Grove cliques pin e.g. prefill and decode to
    different racks of one zone)."""

    def __init__(self, name: str, min_available: int,
                 parent: str | None = None,
                 topology_name: str | None = None,
                 required_topology_level: str | None = None,
                 preferred_topology_level: str | None = None):
        self.name = name
        self.min_available = int(min_available)
        self.parent = parent  # name of parent SubGroupSet node, None = root
        self.topology_name = topology_name
        self.required_topology_level = required_topology_level
        self.preferred_topology_level = preferred_topology_level
        self.pods: dict[str, PodInfo] = {}

    def has_own_topology_constraint(self) -> bool:
        return bool(self.required_topology_level
                    or self.preferred_topology_level)

    def add(self, task: PodInfo) -> None:
        self.pods[task.uid] = task

    def remove(self, task: PodInfo) -> None:
        self.pods.pop(task.uid, None)

    def num_active_allocated(self) -> int:
        return sum(1 for t in self.pods.values() if t.is_active_allocated())

    def num_active_used(self) -> int:
        return sum(1 for t in self.pods.values() if t.is_active_used())

    def num_alive(self) -> int:
        return sum(1 for t in self.pods.values() if is_alive(t.status))

    def is_gang_satisfied(self) -> bool:
        return self.num_active_used() >= self.min_available

    def is_ready_for_scheduling(self) -> bool:
        return self.num_alive() >= self.min_available

    def is_elastic(self) -> bool:
        return len(self.pods) > self.min_available


@dataclass
class SubGroupNode:
    """Interior node of the hierarchical subgroup tree (Grove-style gangs)."""
    name: str
    parent: str | None = None
    children: list[str] = field(default_factory=list)   # child SubGroupNode names
    pod_sets: list[str] = field(default_factory=list)   # child PodSet names
    # Optional topology constraint levels for this gang subtree.
    required_level: str | None = None
    preferred_level: str | None = None


class PodGroupInfo:
    def __init__(self, uid: str, name: str, namespace: str = "default",
                 queue_id: str = "default", priority: int = 0,
                 min_available: int = 1, preemptible: bool = True,
                 creation_ts: float = 0.0,
                 staleness_grace_seconds: float | None = 60.0,
                 required_topology_level: str | None = None,
                 preferred_topology_level: str | None = None,
                 topology_name: str | None = None):
        self.uid = uid
        self.name = name
        self.namespace = namespace
        self.queue_id = queue_id
        self.priority = priority
        self.preemptible = preemptible
        self.creation_ts = creation_ts
        self.staleness_grace_seconds = staleness_grace_seconds
        self.last_start_ts: float | None = None
        self.pod_sets: dict[str, PodSet] = {
            DEFAULT_SUBGROUP: PodSet(DEFAULT_SUBGROUP, min_available)}
        self.subgroup_nodes: dict[str, SubGroupNode] = {}
        self.pods: dict[str, PodInfo] = {}
        self.fit_errors: list[str] = []
        self.task_fit_errors: dict[str, str] = {}
        self.required_topology_level = required_topology_level
        self.preferred_topology_level = preferred_topology_level
        self.topology_name = topology_name
        # caches (invalidated on status change, job_info.go:281);
        # _tasks_to_allocate holds (tag, [tasks]) — the tag pins which
        # ordering fns produced the list.
        self._tasks_to_allocate: Optional[tuple] = None
        self._signature: Optional[str] = None
        self._init_resource: Optional[np.ndarray] = None
        # Incremental status counters: has_tasks_to_allocate is called
        # for every job every cycle (action admission + re-push checks),
        # so it must not rescan the pod dict each time at 1M-pod scale.
        self._pending_count = 0
        self._releasing_count = 0
        # What the pods add up to, kept from one session to the next and
        # dropped with the caches above (a pod that arrives or changes
        # status goes through them): for their queue (``queue_counts``)
        # and by status (``_census``).  One walk fills both
        # (``_count_pods``); None where nobody has asked since.
        self._queue_counts: Optional[tuple] = None
        self._census: Optional[tuple] = None

    # -- structure ---------------------------------------------------------
    def set_pod_sets(self, pod_sets: Iterable[PodSet],
                     subgroup_nodes: Iterable[SubGroupNode] = ()) -> None:
        self.pod_sets = {ps.name: ps for ps in pod_sets}
        self.subgroup_nodes = {sg.name: sg for sg in subgroup_nodes}
        for task in self.pods.values():
            self._index_task(task)
        self.invalidate_caches()

    def _index_task(self, task: PodInfo) -> None:
        ps = self.pod_sets.get(task.subgroup)
        if ps is None:
            ps = self.pod_sets.get(DEFAULT_SUBGROUP)
            if ps is None:
                ps = PodSet(DEFAULT_SUBGROUP, 1)
                self.pod_sets[DEFAULT_SUBGROUP] = ps
        ps.add(task)

    def add_task(self, task: PodInfo) -> None:
        task.job_id = self.uid
        self.pods[task.uid] = task
        self._index_task(task)
        self._count_status(task.status, +1)
        self.invalidate_caches()

    def update_task_status(self, task: PodInfo, status: PodStatus) -> None:
        self._count_status(task.status, -1)
        task.status = status
        self._count_status(status, +1)
        self.invalidate_caches()

    def _count_status(self, status: PodStatus, delta: int) -> None:
        if status == PodStatus.PENDING:
            self._pending_count += delta
        elif status == PodStatus.RELEASING:
            self._releasing_count += delta

    def invalidate_caches(self) -> None:
        self._tasks_to_allocate = None
        self._signature = None
        self._init_resource = None
        self._queue_counts = None
        self._census = None

    def queue_counts(self) -> tuple:
        """``(requirements, active, pending, ...)``, three entries for
        each requirements object that an active-allocated or a pending pod
        of this PodGroup carries, in the order the pods first show it:
        how many of its pods are active-allocated and how many pending.
        What ``ClusterInfo._aggregates_by_count`` sums; counted from the
        pods where nothing is kept, which
        ``queue_aggregate_pod_visits_total`` counts."""
        if self._queue_counts is None:
            METRICS.inc("queue_aggregate_pod_visits_total", len(self.pods))
            self._count_pods()
        return self._queue_counts

    def uncounted_pods(self) -> int:
        """The pods that the next question about this PodGroup's counts
        will walk: all of them where nothing is kept, none where it is.
        What a pass over the fleet adds to its
        ``fleet_walk_pod_visits_total`` before it asks."""
        return len(self.pods) if self._census is None else 0

    def _count_pods(self) -> tuple:
        """One walk of the pods, pod set by pod set, for both kept
        things; returns the census: ``(used, allocated, succeeded,
        sets)``, how many pods are active-used, active-allocated and
        SUCCEEDED, and for each pod set, in ``pod_sets``' order, ``(used,
        allocated, alive, pipelined)``.  Counts and never verdicts:
        ``min_available`` and the grace period are read when asked, so an
        edited spec is never answered from before the edit.  Every pod is
        in one pod set (``_index_task``), so the sets' sums are the
        PodGroup's."""
        by_req: dict = {}     # id(requirements) -> [them, active, pending]
        sets = []
        used = allocated = succeeded = 0
        for ps in self.pod_sets.values():
            ps_used = ps_allocated = ps_alive = ps_pipelined = 0
            for t in ps.pods.values():
                status = t.status._value_
                if status & _ACTIVE_ALLOCATED:
                    # Active-allocated is active-used and alive as well.
                    ps_allocated += 1
                    if status == _PIPELINED:
                        ps_pipelined += 1
                    slot = 1
                elif status == _PENDING:
                    ps_alive += 1
                    slot = 2
                else:
                    if status & _ACTIVE_USED:
                        ps_used += 1
                    elif status & _ALIVE:
                        ps_alive += 1
                    elif status == _SUCCEEDED:
                        succeeded += 1
                    continue
                req = t.res_req
                entry = by_req.get(id(req))
                if entry is None:
                    entry = by_req[id(req)] = [req, 0, 0]
                entry[slot] += 1
            ps_used += ps_allocated
            ps_alive += ps_allocated
            used += ps_used
            allocated += ps_allocated
            sets.append((ps_used, ps_allocated, ps_alive, ps_pipelined))
        self._queue_counts = tuple(chain.from_iterable(by_req.values()))
        census = self._census = (used, allocated, succeeded, tuple(sets))
        return census

    # -- aggregate state: read off the census ------------------------------
    def num_active_used(self) -> int:
        return (self._census or self._count_pods())[0]

    def num_active_allocated(self) -> int:
        return (self._census or self._count_pods())[1]

    def pending_tasks(self) -> list[PodInfo]:
        return [t for t in self.pods.values() if t.status == PodStatus.PENDING]

    def is_gang_satisfied(self) -> bool:
        sets = (self._census or self._count_pods())[3]
        for ps, counts in zip(self.pod_sets.values(), sets):
            if counts[0] < ps.min_available:
                return False
        return True

    def is_ready_for_scheduling(self) -> bool:
        sets = (self._census or self._count_pods())[3]
        for ps, counts in zip(self.pod_sets.values(), sets):
            if counts[2] < ps.min_available:
                return False
        return True

    def is_elastic(self) -> bool:
        return any(ps.is_elastic() for ps in self.pod_sets.values())

    def is_stale(self) -> bool:
        """Partially-running gang below minAvailable (job_info.go:417)."""
        used, _, succeeded, _ = self._census or self._count_pods()
        if succeeded or used == 0:
            return False
        return not self.is_gang_satisfied()

    def should_pipeline(self) -> bool:
        """If any podset has a pipelined task and too few allocated for the
        gang, the whole job's new placements must pipeline (job_info.go:443)."""
        sets = (self._census or self._count_pods())[3]
        # Pipelined members don't count toward the allocated quorum
        # (the reference's if/elif excludes them, job_info.go:448-455).
        for ps, (_, allocated, _, pipelined) in zip(self.pod_sets.values(),
                                                    sets):
            if pipelined and allocated - pipelined < ps.min_available:
                return True
        return False

    def is_preemptible(self) -> bool:
        return self.preemptible

    # -- task selection for one allocation attempt -------------------------
    def _should_allocate(self, task: PodInfo, real_allocation: bool) -> bool:
        if task.status == PodStatus.PENDING:
            return True
        # During scenario simulation, releasing tasks may be re-placed.
        if not real_allocation and task.status == PodStatus.RELEASING:
            return True
        return False

    def tasks_to_allocate(self, subgroup_order_fn: Callable | None = None,
                          task_order_fn: Callable | None = None,
                          real_allocation: bool = True,
                          cache_ordered: bool = False) -> list[PodInfo]:
        """Select the next chunk of tasks to try to place.

        Mirrors GetTasksToAllocate (allocation_info.go:26): while any podset
        is below its gang minimum, only those podsets contribute, each its
        (minAvailable - allocated) chunk; once all podsets are satisfied, grow
        elastically one task at a time from one podset per attempt (:145-177).
        """
        # The cache is valid for the default orderings, or — when the
        # caller vouches its explicit ordering fns are pure functions of
        # immutable task identity (``cache_ordered``) — keyed by the fns
        # themselves: bound-method equality carries the owning session's
        # identity, so a new session (or different fns) can never be
        # served a stale chunk.  Status transitions invalidate either
        # way (invalidate_caches).
        if subgroup_order_fn is None and task_order_fn is None:
            tag = "__default__"
        elif cache_ordered:
            tag = (subgroup_order_fn, task_order_fn)
        else:
            tag = None
        cacheable = real_allocation and tag is not None
        if cacheable and self._tasks_to_allocate is not None \
                and self._tasks_to_allocate[0] == tag:
            return self._tasks_to_allocate[1]

        unsatisfied = [ps for ps in self.pod_sets.values()
                       if ps.num_active_allocated() < ps.min_available]
        if unsatisfied:
            eligible, max_subgroups = unsatisfied, len(unsatisfied)
        else:
            eligible, max_subgroups = list(self.pod_sets.values()), 1

        eligible = sorted(eligible,
                          key=(subgroup_order_fn or (lambda ps: ps.name)))
        out: list[PodInfo] = []
        taken_subgroups = 0
        for ps in eligible:
            if taken_subgroups >= max_subgroups:
                break
            candidates = [t for t in ps.pods.values()
                          if self._should_allocate(t, real_allocation)]
            if not candidates:
                continue
            candidates.sort(key=(task_order_fn or (lambda t: (t.name, t.uid))))
            allocated = ps.num_active_allocated()
            if allocated >= ps.min_available:
                take = 1
            else:
                take = ps.min_available - allocated
            out.extend(candidates[:take])
            taken_subgroups += 1

        if cacheable:
            self._tasks_to_allocate = (tag, out)
        return out

    def has_tasks_to_allocate(self, real_allocation: bool = True) -> bool:
        if real_allocation:
            return self._pending_count > 0
        return self._pending_count > 0 or self._releasing_count > 0

    def tasks_to_allocate_init_resource(self, **kw) -> np.ndarray:
        """Total request of the next chunk; cached like the reference's
        tasksToAllocateInitResource (allocation_info.go:92) — queue
        ordering evaluates it once per comparison otherwise."""
        if self._init_resource is not None and not kw:
            return self._init_resource
        total = rs.zeros()
        for t in self.tasks_to_allocate(real_allocation=False, **kw):
            total += t.req_vec()
        if not kw:
            self._init_resource = total
        return total

    # -- scheduling-constraints signature ----------------------------------
    def scheduling_signature(self) -> str:
        """Hash of everything that determines schedulability, used to skip
        jobs identical to one that already failed (job_info.go:547)."""
        if self._signature is not None:
            return self._signature
        h = hashlib.sha256()
        h.update(self.queue_id.encode())
        h.update(str(self.priority).encode())
        h.update(str(self.required_topology_level).encode())
        h.update(str(self.preferred_topology_level).encode())
        for ps_name in sorted(self.pod_sets):
            ps = self.pod_sets[ps_name]
            h.update(f"{ps_name}:{ps.min_available}".encode())
            reqs = sorted(
                (tuple(t.req_vec()), tuple(sorted(t.node_selector.items())),
                 tuple(sorted(t.tolerations)),
                 # Every other schedulability input must disambiguate, or
                 # the identical-failed-job skip wrongly fences out jobs
                 # differing only in these.
                 tuple(sorted(t.res_req.mig_resources.items())),
                 tuple(sorted(t.host_ports)),
                 tuple(sorted(t.required_configmaps)),
                 tuple(sorted(t.pvc_names)),
                 tuple(sorted(t.resource_claims)),
                 repr(t.affinity_terms), repr(t.anti_affinity_terms),
                 repr(t.node_affinity_required),
                 tuple(sorted(t.labels.items())))
                for t in ps.pods.values() if t.status == PodStatus.PENDING)
            h.update(repr(reqs).encode())
        self._signature = h.hexdigest()
        return self._signature

    # -- errors / explainability -------------------------------------------
    def add_fit_error(self, message: str) -> None:
        self.fit_errors.append(message)

    def add_task_fit_error(self, task: PodInfo, message: str) -> None:
        self.task_fit_errors[task.uid] = message

    def clone(self) -> "PodGroupInfo":
        pg = PodGroupInfo(
            self.uid, self.name, self.namespace, self.queue_id, self.priority,
            1, self.preemptible, self.creation_ts,
            self.staleness_grace_seconds, self.required_topology_level,
            self.preferred_topology_level, self.topology_name)
        pg.pod_sets = {
            n: PodSet(p.name, p.min_available, p.parent, p.topology_name,
                      p.required_topology_level, p.preferred_topology_level)
            for n, p in self.pod_sets.items()}
        pg.subgroup_nodes = {
            n: SubGroupNode(s.name, s.parent, list(s.children),
                            list(s.pod_sets), s.required_level,
                            s.preferred_level)
            for n, s in self.subgroup_nodes.items()}
        pg.last_start_ts = self.last_start_ts
        for t in self.pods.values():
            pg.add_task(t.clone())
        return pg

    def __repr__(self) -> str:
        return (f"PodGroupInfo({self.namespace}/{self.name}, queue={self.queue_id}, "
                f"pods={len(self.pods)}, active={self.num_active_used()})")
