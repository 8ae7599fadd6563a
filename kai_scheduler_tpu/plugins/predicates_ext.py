"""Upstream-predicate plugin: NodePorts, schedule-time VolumeBinding,
ConfigMap, MaxNodePoolResources.

Mirrors the reference's upstream-plugin adapters
(pkg/scheduler/k8s_internal/predicates/predicates.go:70-167 wires
NodePorts/VolumeBinding; config_maps.go and maxNodeResources.go are its
own PreFilter-only predicates) re-designed for the tensor path: node-level
filters contribute hard [T,N] masks (session.hard_node_mask_fns), and
cluster-level PreFilters run once per job through
session.pre_predicate_fns, failing fast with the reference's
unschedulable-message shapes.

The masks are registered apart (docs/DESIGN.md section 4.1): required node
affinity reads node labels and names alone, which no eviction changes
(``static_node_masks``, session.static_node_mask_fns); host ports, bound
PVCs and storage read what runs where (``node_masks``,
session.hard_node_mask_fns).  The scenario prescreen takes the first and
declines on the second.  The same static reading keeps reclaim victims to
the nodes the reclaimer may use (``filter_reclaim``), and tells the
consolidation action what relocation can free there at the most
(``relocation_bound``).
"""

from __future__ import annotations

import numpy as np

from ..api import resources as rs
from ..framework.session import SchedulableResult
from ..ops.predicates import NO_LABEL, NO_TAINT
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from .base import Plugin, register_plugin


def _static_key(task) -> tuple:
    """What a task's static row is made of: tasks with equal keys share
    one row."""
    return (tuple(sorted(task.node_selector.items())),
            tuple(sorted(task.tolerations)),
            repr(task.node_affinity_required))


@register_plugin("predicates")
class UpstreamPredicatesPlugin(Plugin):
    def on_session_open(self, ssn) -> None:
        self.ssn = ssn
        # MaxNodePoolResources: element-wise max over the shard's nodes
        # (maxNodeResources.go:41-43 SetMaxResource).
        nodes = list(ssn.cluster.nodes.values())
        self.max_alloc = (np.max([n.allocatable for n in nodes], axis=0)
                          if nodes else rs.zeros())
        self.max_mig: dict[str, float] = {}
        for n in nodes:
            for profile, count in n.mig_capacity.items():
                self.max_mig[profile] = max(
                    self.max_mig.get(profile, 0.0), count)
        self._ports_cache = (-1, None)  # (mutation_count, ports)
        # Node-affinity mask/score caches: node labels are immutable for
        # the session, so each distinct term spec evaluates once.
        self._node_aff_cache: dict = {}
        # Both at 0, so that a shard that builds no mask and filters no
        # victim reads 0 and not absent.
        METRICS.inc("node_affinity_masks_built_total", 0)
        METRICS.inc("reclaim_victims_filtered_total", 0,
                    reason="excluded-node")
        ssn.pre_predicate_fns.append(self.pre_predicate)
        ssn.static_node_mask_fns.append(self.static_node_masks)
        ssn.hard_node_mask_fns.append(self.node_masks)
        ssn.extra_score_fns.append(self.preferred_node_affinity_scores)
        ssn.reclaim_victim_filters.append(self.filter_reclaim)
        ssn.relocation_bound_fns.append(self.relocation_bound)

    # -- PreFilters (cluster-level, once per task) -------------------------
    def pre_predicate(self, task) -> SchedulableResult:
        res = self._max_node_resources(task)
        if not res.schedulable:
            return res
        res = self._configmaps_exist(task)
        if not res.schedulable:
            return res
        return self._pvcs_exist(task)

    def _max_node_resources(self, task) -> SchedulableResult:
        """maxNodeResources.go PreFilter: no single node in the pool can
        ever fit the request -> unschedulable without scanning nodes."""
        req = task.res_req.to_vec(mig_as_gpu=False)
        for i, name in enumerate(rs.RESOURCE_NAMES):
            if req[i] > self.max_alloc[i] + 1e-9:
                return SchedulableResult(
                    False, "MaxNodePoolResources",
                    f"pod {task.namespace}/{task.name} requires "
                    f"{req[i]:g} {name}; max available in a single node "
                    f"in this node-pool is {self.max_alloc[i]:g}")
        for profile, count in task.res_req.mig_resources.items():
            if count > self.max_mig.get(profile, 0.0) + 1e-9:
                return SchedulableResult(
                    False, "MaxNodePoolResources",
                    f"no node in this node-pool has {count:g} x {profile}")
        return SchedulableResult()

    def _configmaps_exist(self, task) -> SchedulableResult:
        """config_maps.go PreFilter: every required (non-optional)
        ConfigMap must exist."""
        missing = [cm for cm in task.required_configmaps
                   if (task.namespace, cm) not in self.ssn.cluster.config_maps]
        if missing:
            return SchedulableResult(
                False, "ConfigMap",
                f"Missing required configmaps: {missing}")
        return SchedulableResult()

    def _pvcs_exist(self, task) -> SchedulableResult:
        """volume_binding.go filter, cluster-level half: referenced PVCs
        must exist (unbound WaitForFirstConsumer ones bind later), and
        none may be mid-garbage-collection with its dead owner pod
        (isTaskStorageAllocatable's deleted-claims hard failure,
        node_info.go:212-215)."""
        missing = [name for name in task.pvc_names
                   if (task.namespace, name) not in self.ssn.cluster.pvcs]
        if missing:
            return SchedulableResult(
                False, "VolumeBinding",
                f"pod {task.namespace}/{task.name} references missing "
                f"PersistentVolumeClaims: {missing}")
        deleted = task.deleted_storage_claim_names()
        if deleted:
            return SchedulableResult(
                False, "VolumeBinding",
                f"task has deleted storage claims: {deleted}")
        return SchedulableResult()

    # -- node affinity (upstream NodeAffinity, predicates.go:70-167) -------
    def _node_affinity_mask(self, terms: list) -> np.ndarray:
        """[N] bool: nodes whose labels satisfy the required
        nodeSelectorTerms.  Node labels are session-immutable, so each
        distinct spec evaluates once; padding rows stay False."""
        key = repr(terms)
        cached = self._node_aff_cache.get(key)
        if cached is not None:
            return cached
        from ..api.pod_info import node_affinity_matches
        names = self.ssn.snapshot.node_names
        nodes = self.ssn.cluster.nodes
        mask = np.zeros(self.ssn.node_idle.shape[0], bool)
        with TRACER.span("predicates:node_affinity", kind="plugin",
                         nodes=len(names)) as sp:
            for i, name in enumerate(names):
                node = nodes.get(name)
                if node is not None and node_affinity_matches(
                        terms, node.labels or {}, name):
                    mask[i] = True
            sp.set(admitted=int(mask.sum()))
        METRICS.inc("node_affinity_masks_built_total")
        self._node_aff_cache[key] = mask
        return mask

    def preferred_node_affinity_scores(self, tasks):
        """Weighted preferred-term boosts (the NodeAffinity score plugin).
        Scale 10 per weight unit: the smallest step the grouped kernel's
        uniform-extras contract allows (extras must be multiples of 10,
        framework/session.py homogeneous gate)."""
        out = None
        for i, task in enumerate(tasks):
            prefs = getattr(task, "node_affinity_preferred", None) or []
            if not prefs:
                continue
            if out is None:
                out = np.zeros((len(tasks), self.ssn.node_idle.shape[0]))
            for term in prefs:
                spec = [{"expressions": term.get("expressions") or [],
                         "fields": term.get("fields") or []}]
                out[i] += (float(term.get("weight", 1)) * 10.0
                           * self._node_affinity_mask(spec))
        return out

    # -- node-level filters as hard masks ----------------------------------
    def static_node_masks(self, tasks):
        """[T,N] of required node affinity: node labels and names alone,
        the same before and after any eviction."""
        if not any(t.node_affinity_required for t in tasks):
            return None
        out = np.ones((len(tasks), self.ssn.node_idle.shape[0]), bool)
        for i, task in enumerate(tasks):
            if task.node_affinity_required:
                out[i] = self._node_affinity_mask(
                    task.node_affinity_required)
        return out

    def node_masks(self, tasks):
        """[T,N] of what depends on what runs where: host ports in use,
        bound PVCs, storage capacity."""
        if not any(t.host_ports or t.pvc_names
                   or t.needs_storage_scheduling() for t in tasks):
            return None
        n = self.ssn.node_idle.shape[0]
        out = np.ones((len(tasks), n), bool)
        port_masks = None
        for i, task in enumerate(tasks):
            if task.host_ports:
                if port_masks is None:
                    port_masks = self._ports_by_node()
                for port in task.host_ports:
                    occupied = port_masks.get(port)
                    if occupied is not None:
                        out[i] &= ~occupied
            for pvc_name in task.pvc_names:
                pvc = self.ssn.cluster.pvcs.get(
                    (task.namespace, pvc_name))
                bound = (pvc or {}).get("bound_node")
                if bound:
                    # Local/bound volume: the pod must follow it
                    # (volume_binding.go node-affinity filter).
                    idx = self.ssn.node_index(bound)
                    keep = np.zeros(n, bool)
                    if idx >= 0:
                        keep[idx] = True
                    out[i] &= keep
            if task.needs_storage_scheduling():
                out[i] &= self._storage_mask(task, n)
        return out

    # -- reclaim victims on nodes the reclaimer may use ---------------------
    def _static_row(self, task) -> np.ndarray:
        """[N] bool: the nodes that the task's selector, its tolerations
        against the nodes' taints and its required node affinity admit,
        by the snapshot's label and taint tables (``ops/predicates.py``
        ``hard_row`` less the pod room, in numpy)."""
        snap = self.ssn.snapshot
        n = self.ssn.node_idle.shape[0]
        _req, sel, tol = self.ssn._task_row(task)
        if sel is None:
            return np.zeros(n, bool)    # a selector key no node carries
        row = np.all((sel == NO_LABEL) | (sel == snap.node_labels), axis=1)
        taints = snap.node_taints
        tolerated = (taints[:, :, None] == tol[None, None, :]).any(axis=-1)
        row &= np.all((taints == NO_TAINT) | tolerated, axis=1)
        if task.node_affinity_required:
            row &= self._node_affinity_mask(task.node_affinity_required)
        return row

    def _admitted(self, tasks) -> "np.ndarray | None":
        """[N] bool: the nodes that at least one of ``tasks`` may use by
        its static constraints, or None where they select on nothing and
        the fleet has no taint (the whole fleet, found without a walk)."""
        if not tasks or not (
                any(t.node_affinity_required or t.node_selector
                    for t in tasks)
                or (self.ssn.snapshot.node_taints != NO_TAINT).any()):
            return None
        rows = {}
        for task in tasks:
            key = _static_key(task)
            if key not in rows:
                rows[key] = self._static_row(task)
        return np.any(list(rows.values()), axis=0)

    def _node_names(self, rows: np.ndarray) -> set:
        names = self.ssn.snapshot.node_names
        return {names[i] for i in np.flatnonzero(rows[:len(names)])}

    def filter_reclaim(self, reclaimer, victims):
        """Victim jobs with a pod on a node that one of the reclaimer's
        pending tasks may use.  Upstream's solvers take ``feasibleNodes``
        and keep victims on them; evicting elsewhere frees nothing the
        reclaimer can take.  A reclaimer that selects on nothing, on a
        fleet with no taint, gets its list back unwalked."""
        admitted = self._admitted(reclaimer.tasks_to_allocate(
            subgroup_order_fn=self.ssn.pod_set_order_key,
            task_order_fn=self.ssn.task_order_key, real_allocation=False))
        if admitted is None:
            return victims
        usable = self._node_names(admitted)
        kept = [pg for pg in victims
                if any(t.node_name in usable and t.is_active_allocated()
                       for t in pg.pods.values())]
        METRICS.inc("reclaim_victims_filtered_total",
                    len(victims) - len(kept), reason="excluded-node")
        return kept

    def relocation_bound(self, job, tasks) -> "np.ndarray | None":
        """[R]: the most that can be free, after any relocation of running
        preemptible pods, on the nodes ``tasks`` may use; None where they
        may use every node (the fleet's own total bounds them then).

        Relocation inside the admitted nodes conserves what is free there;
        only a pod that may itself run outside them can add to it by
        leaving.  So a gang that asks more than what is free there and
        what such pods hold cannot be seated by consolidation, whatever is
        idle elsewhere."""
        admitted = self._admitted(tasks)
        if admitted is None:
            return None
        ssn = self.ssn
        with TRACER.span("consolidation:bound", kind="consolidation",
                         admitted=int(admitted.sum())) as sp:
            bound = (ssn.node_idle + ssn.node_releasing)[admitted].sum(axis=0)
            usable = self._node_names(admitted)
            may_leave: dict = {}
            movable = 0
            for pg in ssn.cluster.podgroups.values():
                if pg.uid == job.uid or not pg.is_preemptible():
                    continue
                for t in pg.pods.values():
                    if t.node_name not in usable \
                            or not t.is_active_allocated():
                        continue
                    key = _static_key(t)
                    leaves = may_leave.get(key)
                    if leaves is None:
                        leaves = may_leave[key] = bool(
                            (self._static_row(t) & ~admitted).any())
                    if leaves:
                        bound = bound + t.res_req.to_vec(mig_as_gpu=False)
                        movable += 1
            sp.set(movable=movable)
        return bound

    def _storage_mask(self, task, n: int) -> np.ndarray:
        """[N] bool: nodes whose accessible CSI capacities can host the
        task's pending claims (releasing-permissive ceiling — the exact
        idle-vs-releasing split is enforced by NodeInfo checks on the
        sequential host path).  Feasibility is computed once per
        *capacity* (few), then mapped onto nodes (many); the pod-infos
        dict is memoized per mutation tick (it is O(total pods))."""
        cluster = self.ssn.cluster
        pending = task.pending_claims_by_class()
        feasible_caps: dict[str, set] = {}
        for cls, claims in pending.items():
            feasible_caps[cls] = {
                cap.uid for cap in cluster.storage_capacities.values()
                if cap.storage_class == cls
                and cap.are_pvcs_allocatable_on_releasing_or_idle(
                    claims, self._all_pod_infos())}
        keep = np.zeros(n, bool)
        for name in cluster.node_order:
            node = cluster.nodes[name]
            ok = True
            for cls in pending:
                caps = node.accessible_capacities.get(cls)
                if not caps or not any(c.uid in feasible_caps[cls]
                                       for c in caps):
                    ok = False
                    break
            if ok and 0 <= node.idx < n:
                keep[node.idx] = True
        return keep

    def _all_pod_infos(self) -> dict:
        tick = self.ssn.mutation_count
        cached = getattr(self, "_pods_cache", None)
        if cached is not None and cached[0] == tick:
            return cached[1]
        out = {}
        for pg in self.ssn.cluster.podgroups.values():
            out.update(pg.pods)
        self._pods_cache = (tick, out)
        return out

    def _ports_by_node(self) -> dict:
        """(protocol, hostPort) -> [N] bool occupied-node mask
        (nodeports.go: Fits against NodeInfo.UsedPorts), memoized per
        session mutation tick.  Boolean rows keep the per-task mask a few
        numpy ops instead of an O(N) Python scan."""
        tick = self.ssn.mutation_count
        if self._ports_cache[0] == tick:
            return self._ports_cache[1]
        n = self.ssn.node_idle.shape[0]
        out: dict = {}
        hints = getattr(self.ssn.cluster, "columnar_hints", None)
        if hints and hints.get("no_host_ports"):
            # Columnar snapshot: no pod in the population carries a host
            # port — identical (empty) occupancy, no O(pods) walk.
            self._ports_cache = (tick, out)
            return out
        for pg in self.ssn.cluster.podgroups.values():
            for t in pg.pods.values():
                if not t.host_ports or not t.node_name:
                    continue
                if not t.is_active_allocated():
                    continue
                idx = self.ssn.node_index(t.node_name)
                if idx < 0:
                    continue
                for port in t.host_ports:
                    mask = out.get(port)
                    if mask is None:
                        mask = out[port] = np.zeros(n, bool)
                    mask[idx] = True
        self._ports_cache = (tick, out)
        return out
