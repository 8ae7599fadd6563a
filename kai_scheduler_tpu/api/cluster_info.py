"""Point-in-time cluster snapshot.

Mirrors the role of pkg/scheduler/api/cluster_info.go +
pkg/scheduler/cache/cluster_info/cluster_info.go:118 (Snapshot): an immutable
in-memory copy of nodes, podgroups, and queues that every action mutates only
through Statement transactions.  ``pack()`` (api/snapshot.py) produces the
dense tensor view shipped to the device once per cycle.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from . import resources as rs
from .node_info import NodeInfo
from .pod_status import PodStatus
from .podgroup_info import PodGroupInfo
from .queue_info import QueueInfo


@dataclass
class BindRequest:
    """Durable scheduler->binder command (bindrequest_types.go:12)."""
    pod_uid: str
    pod_name: str
    namespace: str
    node_name: str
    reconcile_attempts: int = 0
    gpu_groups: list = field(default_factory=list)
    backoff_limit: int = 3
    phase: str = "Pending"  # Pending | Succeeded | Failed
    # DRA: claim names + structured ResourceClaimAllocations
    # ({"name", "node", "devices"}) the binder publishes at bind time.
    resource_claims: list = field(default_factory=list)
    claim_allocations: list = field(default_factory=list)
    # Flight-recorder correlation: the trace id of the scheduling cycle
    # that produced this decision (utils/tracing.py); lands in the API
    # object as spec.traceId so `GET /debug/trace?cycle=<id>` explains
    # any bind after the fact.
    trace_id: str | None = None


class QueueAggregates(NamedTuple):
    """Per-leaf-queue sums of one pod walk (``ClusterInfo._aggregates``).
    The last three are there only where the sums were counted, which proves
    them equal to the additions in turn to the bit."""
    allocated: dict
    requested: dict
    non_preemptible: dict | None = None   # active pods of guaranteed groups
    adds: dict | None = None              # additions a pod-by-pod roll-up makes
    unit: dict | None = None              # [R] a leaf: what its requests are whole multiples of


# A sum of non-negative whole multiples of a power of two ``unit`` is exact
# in float64, in any order, while it stays under ``EXACT_BELOW * unit``:
# every partial sum is a multiple of the unit with fewer than 2**53 of them,
# hence representable.  The unit is taken column by column (milli-cores,
# bytes, whole GPUs), so 2**53 multiples of 32 Gi count where 2**53 bytes
# did not.
EXACT_BELOW = 2.0 ** 53
_ONE = np.ones(rs.NUM_RES)
_ONE.flags.writeable = False


def sums_exact(total: np.ndarray, unit: np.ndarray) -> bool:
    """Whether ``total``, summed from non-negative whole multiples of
    ``unit`` column by column, is proven exact (``EXACT_BELOW``)."""
    return bool((total < EXACT_BELOW * unit).all())


def _unit_of(vectors) -> np.ndarray:
    """Column by column the largest power of two that divides every
    non-zero entry of the whole-number ``vectors`` [D,R]; inf for a column
    of zeros, which proves nothing wrong."""
    mantissa, exponent = np.frexp(np.asarray(vectors, float))
    bits = np.ldexp(mantissa, 53).astype(np.int64)   # the 53 significant
    lowest = bits & -bits
    unit = np.ldexp(lowest.astype(float), exponent - 53)
    return np.where(lowest == 0, np.inf, unit).min(axis=0)


class ClusterInfo:
    def __init__(self, nodes: dict[str, NodeInfo] | None = None,
                 podgroups: dict[str, PodGroupInfo] | None = None,
                 queues: dict[str, QueueInfo] | None = None,
                 topologies: dict | None = None,
                 now: float = 0.0,
                 resource_claims: dict | None = None,
                 config_maps: set | None = None,
                 pvcs: dict | None = None,
                 resource_slices: dict | None = None,
                 storage_classes: dict | None = None,
                 storage_claims: dict | None = None,
                 storage_capacities: dict | None = None,
                 device_classes: dict | None = None,
                 prewired: bool = False):
        self.nodes: dict[str, NodeInfo] = nodes or {}
        self.podgroups: dict[str, PodGroupInfo] = podgroups or {}
        self.queues: dict[str, QueueInfo] = queues or {}
        self.topologies: dict = topologies or {}
        # DRA claims: name -> {"device_class", "count",
        # "allocation": {"node", "devices"} | None} (legacy keys
        # "allocated"/"node" still honored by the plugin).
        self.resource_claims: dict = resource_claims or {}
        # DRA device inventory (ResourceSlice objects):
        # node -> pool/class key -> [device name | {"name", "attributes",
        # "capacity"}].  Plain strings are attribute-less devices.
        self.resource_slices: dict = resource_slices or {}
        # DRA DeviceClasses: name -> {"selectors": [...]} — structured
        # attribute/capacity requirements (upstream selects via CEL,
        # consumed by dynamicresources.go:59-87; here the structured
        # subset: attribute equality + capacity minimums).
        self.device_classes: dict = device_classes or {}
        # ConfigMap predicate inventory: {(namespace, name)}.
        self.config_maps: set = set(config_maps or ())
        # PVC inventory for the schedule-time VolumeBinding filter:
        # (namespace, name) -> {"bound_node": str | None}.
        self.pvcs: dict = dict(pvcs or {})
        # Schedule-time CSI storage infos (api/storage_info.py; mirrors
        # cluster_info.go Snapshot storage fields).
        self.storage_classes: dict = storage_classes or {}
        self.storage_claims: dict = storage_claims or {}
        self.storage_capacities: dict = storage_capacities or {}
        self.bind_requests: list[BindRequest] = []
        self.now = now
        # Set by ClusterCache.snapshot (framework/arena.py): marks this
        # object as the arena's latest view, eligible for the incremental
        # pack path.  None (the default, and what clones/filters carry)
        # means "pack from scratch".
        self.arena_stamp: int | None = None
        # Columnar fast-path hints (controllers/cache_builder.py
        # _snapshot_columnar): exact facts about the pod population
        # ("no pod carries a selector/host port", precomputed max
        # toleration width) that let pack() and the per-cycle plugin
        # scans skip their O(pods) walks with identical results.  None on
        # every other construction path (clones, filters, tests) —
        # consumers must treat absence as "walk".
        self.columnar_hints: dict | None = None
        # The pods that carry an inter-pod term, where the builder of
        # this object has proven them (the columnar build: none); None
        # where nobody has.  Read through ``Session.term_carriers``.
        self.term_carriers: list | None = None
        # Stable orderings for tensor packing.
        self.node_order: list[str] = sorted(self.nodes)
        for i, name in enumerate(self.node_order):
            self.nodes[name].idx = i
        if not prewired:
            # The columnar snapshot path pre-wires placement accounting
            # as one vectorized segment reduction (bit-identical to this
            # walk); every other constructor wires per task here.
            self._wire_tasks_to_nodes()
        if self.storage_capacities or self.storage_claims:
            from .storage_info import link_storage_objects
            link_storage_objects(self.storage_claims,
                                 self.storage_capacities,
                                 self.podgroups, self.nodes)

    def _wire_tasks_to_nodes(self) -> None:
        """Account every already-placed task on its node (snapshot build)."""
        for pg in self.podgroups.values():
            for task in pg.pods.values():
                if task.node_name and task.node_name in self.nodes:
                    node = self.nodes[task.node_name]
                    if task.uid not in node.pod_infos:
                        node.add_task(task)

    # -- aggregates used by fair-share -------------------------------------
    def total_allocatable(self) -> np.ndarray:
        if not self.nodes:
            return rs.zeros()
        return np.sum([n.allocatable for n in self.nodes.values()], axis=0)

    def task_gpu_memory_context(self, task) -> float:
        """Per-GPU memory divisor for a task's gpu-memory request: its
        node's when placed, the cluster minimum otherwise (the reference's
        minNodeGPUMemory fallback)."""
        node = self.nodes.get(task.node_name) if task.node_name else None
        if node is not None and node.gpu_memory_per_device > 0:
            return node.gpu_memory_per_device
        return self.min_node_gpu_memory()

    def queue_allocated(self) -> dict[str, np.ndarray]:
        """Per-leaf-queue sum of active-allocated task requests.
        gpu-memory tasks charge device fractions against their node's
        per-GPU memory — the same normalization queue_requested uses, so
        the two aggregates stay comparable."""
        return self.queue_aggregates()[0]

    def invalidate_aggregates(self) -> None:
        """Drop the memoized queue aggregates.  Statement mutations call
        this so a mid-cycle reader never sees snapshot-open values after
        task statuses have moved."""
        self._queue_aggregates = None

    def _aggregates(self) -> "QueueAggregates":
        """The per-leaf-queue sums, made once for whoever needs them
        (snapshot.pack, the proportion plugin's roll-up).  Memoized until
        the next snapshot build or the next Statement mutation (which
        calls invalidate_aggregates); made again they cost a pass over
        what the PodGroups keep (``PodGroupInfo.queue_counts``) and a pod
        walk only inside those that changed, or one over every pod where
        the sums have to be taken in turn."""
        cached = getattr(self, "_queue_aggregates", None)
        if cached is None:
            cached = self._queue_aggregates = (
                self._aggregates_by_count() or self._aggregates_in_turn())
        return cached

    def queue_aggregates(self) -> tuple[dict, dict]:
        """(allocated, requested) per leaf queue."""
        return self._aggregates()[:2]

    def queue_rollup(self) -> "QueueAggregates | None":
        """The aggregates where they were counted, with the third sum and
        the number of additions the proportion plugin's roll-up wants; None
        where they were taken in turn or pre-filled by a snapshot builder,
        and the plugin walks the pods itself."""
        agg = self._aggregates()
        return agg if agg.non_preemptible is not None else None

    def _aggregates_in_turn(self) -> "QueueAggregates":
        """One vector addition a pod, in the walk's order: what the sums
        are defined as, whatever the requests look like."""
        min_gpu_mem = self.min_node_gpu_memory()
        allocated = {qid: rs.zeros() for qid in self.queues}
        requested = {qid: rs.zeros() for qid in self.queues}
        for pg in self.podgroups.values():
            qid = pg.queue_id
            if qid not in allocated:
                continue
            for t in pg.pods.values():
                if t.is_active_allocated():
                    allocated[qid] += t.req_vec(
                        self.task_gpu_memory_context(t))
                    # Request keeps the min-node normalization for every
                    # alive task (proportion.go's Request roll-up), so the
                    # refactor is behavior-preserving.
                    requested[qid] += t.req_vec(min_gpu_mem)
                elif t.status == PodStatus.PENDING:
                    requested[qid] += t.req_vec(min_gpu_mem)
        return QueueAggregates(allocated, requested)

    def _aggregates_by_count(self) -> "QueueAggregates | None":
        """The same sums where they are exact in any order: the pods of a
        fleet share a handful of requirement objects, so each PodGroup
        keeps how many of its pods carry which (``queue_counts``), the
        counts are added up by leaf, object and ``is_preemptible()``, and
        ``count * vector`` is added once for each.  That equals the
        additions in turn to the bit only while every vector is made of
        non-negative whole multiples of a power of two and every total
        stays under 2**53 of them, column by column (``EXACT_BELOW``: 2**53
        milli-cores, 2**53 times 32 Gi where every pod asks whole multiples
        of 32 Gi).  A fractional or gpu-memory request anywhere, or a leaf's
        total past that, returns None and the sums are taken in turn.

        Counted, the walk also gives what the proportion plugin rolls up
        the queue tree: the active pods of non-preemptible PodGroups (a
        PodGroup's property, so the same count), how many additions a
        pod-by-pod roll-up would have made at each leaf, and each leaf's
        unit, by which the plugin proves its ancestors' totals."""
        queues = self.queues
        # (leaf, id(requirements), guaranteed) -> [them, active, pending]
        counted: dict = {}
        for pg in self.podgroups.values():
            qid = pg.queue_id
            if qid not in queues:
                continue
            kept = pg.queue_counts()
            guaranteed = not pg.is_preemptible()
            for i in range(0, len(kept), 3):
                key = (qid, id(kept[i]), guaranteed)
                entry = counted.get(key)
                if entry is None:
                    counted[key] = [kept[i], kept[i + 1], kept[i + 2]]
                else:
                    entry[1] += kept[i + 1]
                    entry[2] += kept[i + 2]
        allocated = {qid: rs.zeros() for qid in queues}
        requested = {qid: rs.zeros() for qid in queues}
        non_preemptible = {qid: rs.zeros() for qid in queues}
        adds = dict.fromkeys(queues, 0)
        asked: dict = {}      # (leaf, id(requirements)) -> their vector
        for (qid, _, guaranteed), (req, active, waiting) in counted.items():
            vec = req.to_vec()
            if req.gpu_memory_bytes > 0.0 or (vec < 0.0).any() \
                    or (vec != np.floor(vec)).any():
                return None
            asked[qid, id(req)] = vec
            allocated[qid] += active * vec
            requested[qid] += (active + waiting) * vec
            if guaranteed:
                non_preemptible[qid] += active * vec
            adds[qid] += (3 if guaranteed else 2) * active + waiting
        # No queue asks more than all the leaves together: while that is
        # under 2**53 the unit 1 of whole numbers proves every total, and
        # the requests' own is looked for only past it.
        unit = dict.fromkeys(self.queues, _ONE)
        if not sums_exact(sum(requested.values(), rs.zeros()), _ONE):
            by_leaf: dict = {}
            for (qid, _), vec in asked.items():
                by_leaf.setdefault(qid, []).append(vec)
            for qid, vectors in by_leaf.items():
                unit[qid] = _unit_of(vectors)
            if not all(sums_exact(requested[qid], unit[qid])
                       for qid in unit):
                return None
        return QueueAggregates(allocated, requested, non_preemptible, adds,
                               unit)

    def min_node_gpu_memory(self) -> float:
        """Smallest per-GPU memory across nodes that report one — the
        divisor for converting gpu-memory requests into device fractions
        (ssn.ClusterInfo.MinNodeGPUMemory in the reference).  Memoized:
        node hardware is immutable within a snapshot."""
        cached = getattr(self, "_min_gpu_mem", None)
        if cached is None:
            mems = [n.gpu_memory_per_device for n in self.nodes.values()
                    if n.gpu_memory_per_device > 0]
            cached = self._min_gpu_mem = min(mems) if mems else 0.0
        return cached

    def queue_requested(self) -> dict[str, np.ndarray]:
        """Per-leaf-queue total demand (allocated + Pending tasks; Gated
        pods are excluded, matching proportion.go's Request roll-up)."""
        return self.queue_aggregates()[1]

    def pending_jobs(self) -> list[PodGroupInfo]:
        return [pg for pg in self.podgroups.values()
                if pg.has_tasks_to_allocate() and pg.is_ready_for_scheduling()]

    def clone(self) -> "ClusterInfo":
        # Node accounting is fully derived from task state, so clone bare
        # nodes and let __init__ re-wire the cloned tasks onto them.
        bare_nodes = {
            name: NodeInfo(node.name, node.allocatable.copy(),
                           dict(node.labels), set(node.taints),
                           node.gpu_memory_per_device, node.max_pods,
                           node.idx, dict(node.mig_capacity))
            for name, node in self.nodes.items()}
        # Storage infos are mutable (provisioned claims move with the
        # statement), so the clone gets fresh objects; cloned tasks drop
        # their claim dicts and re-link against the fresh infos.
        cloned_claims = {k: c.clone()
                         for k, c in self.storage_claims.items()}
        cloned_caps = {}
        for uid, cap in self.storage_capacities.items():
            cc = cap.clone()
            cc.provisioned_pvcs = {}  # re-derived by linking + add_task
            cloned_caps[uid] = cc
        cloned_pgs = {uid: pg.clone() for uid, pg in self.podgroups.items()}
        for pg in cloned_pgs.values():
            for task in pg.pods.values():
                task.storage_claims = {}
                task.owned_storage_claims = {}
        return ClusterInfo(
            bare_nodes, cloned_pgs,
            dict(self.queues), dict(self.topologies), self.now,
            {k: dict(v) for k, v in self.resource_claims.items()},
            set(self.config_maps),
            {k: dict(v) for k, v in self.pvcs.items()},
            {n: {c: list(d) for c, d in by_class.items()}
             for n, by_class in self.resource_slices.items()},
            dict(self.storage_classes), cloned_claims, cloned_caps,
            device_classes=dict(self.device_classes))
