"""Per-node accounting.

Mirrors the behavioral surface of pkg/scheduler/api/node_info/node_info.go
(Idle/Used/Releasing accounting, task add/remove, allocatability checks) and
gpu_sharing_node_info.go (shared-GPU group fraction maps).  All quantities are
dense resource vectors so the whole node table packs into ``[N, NUM_RES]``
matrices for the device kernel; the sparse shared-GPU group state stays
host-side (SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

import itertools
import uuid
from dataclasses import dataclass, field

import numpy as np

from . import resources as rs
from .pod_info import PodInfo
from .pod_status import PodStatus

# Stamps for ``NodeInfo.version``: one process-wide, ever-growing series,
# so no two node objects and no two states of one object share a stamp.
_VERSIONS = itertools.count(1)


@dataclass
class GpuSharingGroup:
    """One physical accelerator shared by fractional tasks.

    The backing device is charged as ONE whole GPU against the node for the
    lifetime of the group (the reference reserves a whole device per sharing
    group via the resource-reservation pod — docs/gpu-sharing/README.md);
    fractions are an intra-group budget, not node-level accounting.
    """
    group_id: str
    pods: dict = field(default_factory=dict)  # uid -> (PodStatus, fraction)

    @property
    def used_fraction(self) -> float:
        return sum(frac for _, frac in self.pods.values())

    def active_fraction(self) -> float:
        """Fraction held by pods that are NOT releasing (what a pipelined
        task must fit alongside)."""
        return sum(frac for status, frac in self.pods.values()
                   if status != PodStatus.RELEASING)

    @property
    def releasing(self) -> bool:
        """The device frees once every member pod is releasing."""
        return bool(self.pods) and all(
            s == PodStatus.RELEASING for s, _ in self.pods.values())


class NodeInfo:
    def __init__(self, name: str, allocatable: np.ndarray,
                 labels: dict | None = None, taints: set | None = None,
                 gpu_memory_per_device: float = 0.0,
                 max_pods: int = 110, idx: int = -1,
                 mig_capacity: dict | None = None):
        self.name = name
        self.idx = idx
        self.allocatable = allocatable.astype(np.float64)
        self.used = rs.zeros()
        self.releasing = rs.zeros()
        self.labels = dict(labels or {})
        self.taints = set(taints or ())
        self.gpu_memory_per_device = gpu_memory_per_device
        self.max_pods = max_pods
        self.pod_infos: dict[str, PodInfo] = {}
        self.gpu_sharing_groups: dict[str, GpuSharingGroup] = {}
        # MIG inventory: per-profile scalar resources the node advertises
        # (pre-partitioned by the GPU operator; nvidia.com/mig-Ng.Mgb).
        self.mig_capacity: dict[str, float] = dict(mig_capacity or {})
        self.mig_used: dict[str, float] = {}
        self.mig_releasing: dict[str, float] = {}
        # Schedule-time CSI storage: storage_class ->
        # [StorageCapacityInfo] this node can provision from
        # (node_info.go:91 AccessibleStorageCapacities, populated by
        # api/storage_info.link_storage_objects).
        self.accessible_capacities: dict[str, list] = {}
        self.version = next(_VERSIONS)

    def touch(self) -> None:
        """Note that what ``api/snapshot.pack`` reads of this node moved:
        ``used``, ``releasing``, the pod set, or which buffer ``used`` and
        ``releasing`` are views of.  ``add_task``/``remove_task`` stamp
        themselves; code that writes those fields another way calls this.

        ``version`` is the record: a reader that kept the stamp it saw
        finds the node changed when the stamp differs (a replaced object
        never carries an old stamp), and reading takes nothing away, so
        any number of readers can hold a baseline (framework/arena.py
        ``HostArena``).  ``allocatable``, ``labels``, ``taints`` and
        ``max_pods`` do not change on a live node: a new Node manifest is
        a new object."""
        self.version = next(_VERSIONS)

    # -- derived quantities ------------------------------------------------
    @property
    def idle(self) -> np.ndarray:
        return self.allocatable - self.used

    def instantiate(self) -> "NodeInfo":
        """Fresh per-cycle instance from a parsed template (the
        incremental ClusterCache re-parses a Node manifest only when its
        resourceVersion moves; every cycle in between starts from here).
        ``allocatable`` is shared BY REFERENCE — node hardware is
        immutable within a snapshot (only ``used``/``releasing`` move) —
        while every container a cycle mutates is fresh."""
        n = NodeInfo.__new__(NodeInfo)
        n.name = self.name
        n.idx = -1
        n.allocatable = self.allocatable
        n.used = rs.zeros()
        n.releasing = rs.zeros()
        n.labels = dict(self.labels)
        n.taints = set(self.taints)
        n.gpu_memory_per_device = self.gpu_memory_per_device
        n.max_pods = self.max_pods
        n.pod_infos = {}
        n.gpu_sharing_groups = {}
        n.mig_capacity = dict(self.mig_capacity)
        n.mig_used = {}
        n.mig_releasing = {}
        n.accessible_capacities = {}
        n.version = next(_VERSIONS)
        return n

    def clone(self) -> "NodeInfo":
        n = NodeInfo(self.name, self.allocatable.copy(), dict(self.labels),
                     set(self.taints), self.gpu_memory_per_device,
                     self.max_pods, self.idx, dict(self.mig_capacity))
        n.used = self.used.copy()
        n.releasing = self.releasing.copy()
        n.mig_used = dict(self.mig_used)
        n.mig_releasing = dict(self.mig_releasing)
        n.pod_infos = {uid: p for uid, p in self.pod_infos.items()}
        n.gpu_sharing_groups = {
            gid: GpuSharingGroup(g.group_id, dict(g.pods))
            for gid, g in self.gpu_sharing_groups.items()}
        return n

    # -- task accounting ---------------------------------------------------
    def _req(self, task: PodInfo) -> np.ndarray:
        """Vector charged against node idle/used/releasing.

        Fractional tasks charge cpu/mem here; their GPU devices are charged
        whole-device per sharing group by _add_to_gpu_group.
        """
        req = task.res_req.to_vec(self.gpu_memory_per_device,
                                  mig_as_gpu=False)
        if task.is_fractional and task.gpu_group:
            req = req.copy()
            req[rs.RES_GPU] = 0.0
        return req

    def add_task(self, task: PodInfo) -> None:
        req = self._req(task)
        if task.status == PodStatus.RELEASING:
            self.releasing += req
            self.used += req
            self._mig_account(task, used=+1, releasing=+1)
        elif task.status == PodStatus.PIPELINED:
            # Pipelined tasks claim resources that are still being released.
            self.releasing -= req
            self._mig_account(task, releasing=-1)
        elif task.is_active_allocated():
            self.used += req
            self._mig_account(task, used=+1)
        self.pod_infos[task.uid] = task
        self.version = next(_VERSIONS)
        self._add_task_storage(task)
        if task.is_fractional and task.gpu_group:
            self._add_to_gpu_group(task)

    def remove_task(self, task: PodInfo) -> None:
        req = self._req(task)
        if task.status == PodStatus.RELEASING:
            self.releasing -= req
            self.used -= req
            self._mig_account(task, used=-1, releasing=-1)
        elif task.status == PodStatus.PIPELINED:
            self.releasing += req
            self._mig_account(task, releasing=+1)
        elif task.is_active_allocated():
            self.used -= req
            self._mig_account(task, used=-1)
        self.pod_infos.pop(task.uid, None)
        self.version = next(_VERSIONS)
        self._remove_task_storage(task)
        if task.is_fractional and task.gpu_group:
            self._remove_from_gpu_group(task)

    # -- schedule-time CSI storage (node_info.go:200-268,438-463,553-570) --
    def _add_task_storage(self, task: PodInfo) -> None:
        """addTaskStorage: charge the task's pending claims into every
        accessible capacity of their class (idempotent dict insert)."""
        if not self.accessible_capacities or not task.storage_claims:
            return
        for cls, claims in task.pending_claims_by_class().items():
            for cap in self.accessible_capacities.get(cls, []):
                for claim in claims:
                    cap.provisioned_pvcs[claim.key] = claim

    def _remove_task_storage(self, task: PodInfo) -> None:
        """removeTaskStorage: the inverse."""
        if not self.accessible_capacities or not task.storage_claims:
            return
        for cls, claims in task.pending_claims_by_class().items():
            for cap in self.accessible_capacities.get(cls, []):
                for claim in claims:
                    cap.provisioned_pvcs.pop(claim.key, None)

    def is_task_storage_allocatable(self, task: PodInfo,
                                    allow_releasing: bool = False,
                                    pod_infos: dict | None = None) -> bool:
        """isTaskStorageAllocatable(-OnReleasingOrIdle): every pending
        claim's class must have an accessible capacity here that fits the
        class's total pending demand.  Deleted-owner claims are a hard
        no (the PVC is being garbage-collected with its pod)."""
        if not task.storage_claims:
            return True
        if task.deleted_storage_claim_names():
            return False
        for cls, claims in task.pending_claims_by_class().items():
            caps = self.accessible_capacities.get(cls)
            if not caps:
                return False
            if allow_releasing:
                ok = all(cap.are_pvcs_allocatable_on_releasing_or_idle(
                    claims, pod_infos if pod_infos is not None
                    else self.pod_infos) for cap in caps)
            else:
                # Demand could land on any one capacity: feasible if ANY
                # fits (isTaskStorageAllocatableOnCapacities).
                ok = any(cap.are_pvcs_allocatable(claims) for cap in caps)
            if not ok:
                return False
        return True

    def _mig_account(self, task: PodInfo, used: int = 0,
                     releasing: int = 0) -> None:
        """Per-profile MIG scalar accounting (resource_info.go:153-165
        scalarResources add/sub), mirroring the vector used/releasing."""
        for profile, count in task.res_req.mig_resources.items():
            if used:
                self.mig_used[profile] = \
                    self.mig_used.get(profile, 0.0) + used * count
            if releasing:
                self.mig_releasing[profile] = \
                    self.mig_releasing.get(profile, 0.0) + releasing * count

    def has_mig_room(self, task: PodInfo, allow_releasing: bool) -> bool:
        """Every requested profile fits the node's remaining inventory."""
        for profile, count in task.res_req.mig_resources.items():
            free = self.mig_capacity.get(profile, 0.0) \
                - self.mig_used.get(profile, 0.0)
            if allow_releasing:
                free += self.mig_releasing.get(profile, 0.0)
            if count > free + 1e-9:
                return False
        return True

    # -- allocatability ----------------------------------------------------
    def is_task_allocatable(self, task: PodInfo) -> bool:
        """Can the task run now on idle resources?

        Mirrors NodeInfo.IsTaskAllocatable (node_info.go:168).
        """
        if len(self.pod_infos) >= self.max_pods:
            return False
        if not self.is_task_storage_allocatable(task):
            return False
        if task.is_fractional:
            return self._fits_fraction(task, allow_releasing=False)
        if not self.has_mig_room(task, allow_releasing=False):
            return False
        return rs.less_equal(self._req(task), self.idle)

    def is_task_allocatable_on_releasing_or_idle(self, task: PodInfo) -> bool:
        """Can the task be pipelined onto resources that are being released?

        Mirrors IsTaskAllocatableOnReleasingOrIdle (node_info.go:190).
        """
        if len(self.pod_infos) >= self.max_pods:
            return False
        if not self.is_task_storage_allocatable(task, allow_releasing=True):
            return False
        if task.is_fractional:
            return self._fits_fraction(task, allow_releasing=True)
        if not self.has_mig_room(task, allow_releasing=True):
            return False
        return rs.less_equal(self._req(task), self.idle + self.releasing)

    # -- fractional GPU groups (host-side, sparse) -------------------------
    def task_fraction(self, task: PodInfo) -> float:
        r = task.res_req
        if r.gpu_fraction > 0.0:
            return r.gpu_fraction
        if r.gpu_memory_bytes > 0.0 and self.gpu_memory_per_device > 0.0:
            return min(1.0, r.gpu_memory_bytes / self.gpu_memory_per_device)
        return 1.0

    def _fits_fraction(self, task: PodInfo, allow_releasing: bool) -> bool:
        base = task.res_req.base.copy()
        base[rs.RES_GPU] = 0.0
        budget = self.idle + (self.releasing if allow_releasing else 0.0)
        if not rs.less_equal(base, budget):
            return False
        return self.find_gpu_groups_for_task(task, allow_releasing) is not None

    def find_gpu_groups_for_task(self, task: PodInfo,
                                 allow_releasing: bool) -> list[str] | None:
        """Pick shared-GPU group(s) able to host the task's fraction(s).

        Mirrors GetNodePreferableGpuForSharing (gpu_sharing/gpuSharing.go:38):
        prefer an already-shared device with room (bin-pack the fractions),
        else claim a fresh whole device from idle GPUs.  Returns group ids
        (new uuid = fresh device) or None if it doesn't fit.
        """
        frac = self.task_fraction(task)
        needed = task.res_req.num_fraction_devices
        chosen: list[str] = []
        # Existing groups with room, fullest-first (pack).  When pipelining
        # (allow_releasing), releasing pods' fractions don't count against
        # the group budget — they'll be gone by bind time.
        def budget_used(g: GpuSharingGroup) -> float:
            return g.active_fraction() if allow_releasing else g.used_fraction

        groups = sorted(self.gpu_sharing_groups.values(),
                        key=lambda g: -budget_used(g))
        for g in groups:
            if len(chosen) == needed:
                break
            if g.releasing and not allow_releasing:
                continue
            if budget_used(g) + frac <= 1.0 + 1e-9:
                chosen.append(g.group_id)
        # Fresh whole devices for the remainder.
        whole_budget = self.idle[rs.RES_GPU]
        if allow_releasing:
            whole_budget += self.releasing[rs.RES_GPU]
        fresh_needed = needed - len(chosen)
        if fresh_needed > 0:
            if whole_budget + 1e-9 < fresh_needed:
                return None
            chosen.extend(f"gpugroup-{uuid.uuid4().hex[:8]}"
                          for _ in range(fresh_needed))
        return chosen

    def _charge_device(self, amount: float, releasing_group: bool) -> None:
        """Charge/refund one whole backing device for a sharing group."""
        self.used[rs.RES_GPU] += amount
        if releasing_group:
            self.releasing[rs.RES_GPU] += amount

    def _add_to_gpu_group(self, task: PodInfo) -> None:
        frac = self.task_fraction(task)
        for gid in task.gpu_group.split(","):
            g = self.gpu_sharing_groups.get(gid)
            if g is None:
                g = GpuSharingGroup(gid)
                self.gpu_sharing_groups[gid] = g
                self._charge_device(1.0, releasing_group=False)
            was_releasing = g.releasing
            g.pods[task.uid] = (task.status, frac)
            self._sync_group_releasing(was_releasing, g.releasing)

    def _remove_from_gpu_group(self, task: PodInfo) -> None:
        for gid in task.gpu_group.split(","):
            g = self.gpu_sharing_groups.get(gid)
            if g is None:
                continue
            was_releasing = g.releasing
            g.pods.pop(task.uid, None)
            if not g.pods:
                del self.gpu_sharing_groups[gid]
                self._charge_device(-1.0, releasing_group=was_releasing)
            else:
                self._sync_group_releasing(was_releasing, g.releasing)

    def _sync_group_releasing(self, was: bool, now: bool) -> None:
        """Keep node.releasing in step with a group's releasing transitions:
        a fully-releasing group's device is available for pipelining."""
        if now and not was:
            self.releasing[rs.RES_GPU] += 1.0
        elif was and not now:
            self.releasing[rs.RES_GPU] -= 1.0

    def fitting_error(self, task: PodInfo) -> str:
        """Human explanation of why the task doesn't fit (node_info.go:274)."""
        req = self._req(task)
        idle = self.idle
        parts = []
        for i, rn in enumerate(rs.RESOURCE_NAMES):
            if req[i] > idle[i] + 1e-9:
                parts.append(f"insufficient {rn}: requested {req[i]:g}, idle {idle[i]:g}")
        for profile, count in task.res_req.mig_resources.items():
            free = self.mig_capacity.get(profile, 0.0) \
                - self.mig_used.get(profile, 0.0)
            if count > free + 1e-9:
                parts.append(f"insufficient {profile}: requested {count:g}, "
                             f"free {free:g}")
        if len(self.pod_infos) >= self.max_pods:
            parts.append(f"node is at max pods ({self.max_pods})")
        return "; ".join(parts) or "node did not satisfy predicates"

    def __repr__(self) -> str:
        return (f"NodeInfo({self.name}, idle={rs.humanize(self.idle)}, "
                f"used={rs.humanize(self.used)}, releasing={rs.humanize(self.releasing)})")
