"""Proportion plugin: hierarchical DRF fairness, quota gates, reclaim rules.

The policy heart of the scheduler, mirroring
pkg/scheduler/plugins/proportion/ (proportion.go:99-124 registrations):

- builds per-queue attributes (deserved/limit/over-quota-weight, allocated,
  allocated-non-preemptible, request, historical usage) with parent-chain
  roll-ups (proportion.go:378-401);
- computes hierarchical fair share on-device via ops.fairshare;
- registers the DRF queue-order comparator (queue_order/queue_order.go:19),
  queue capacity gates (capacity_policy/), reclaim legality
  (reclaimable/reclaimable.go + strategies.go), and allocate/deallocate
  event handlers that keep queue shares current as statements mutate state.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..api import resources as rs
from ..api.cluster_info import sums_exact
from ..api.podgroup_info import PodGroupInfo
from ..framework.session import SchedulableResult
from ..ops import fairshare as fsops
from ..utils.tracing import TRACER
from .base import Plugin, register_plugin

UNLIMITED = rs.UNLIMITED
NO_FAIR_SHARE_DRF_MULTIPLIER = 1000.0


@dataclass
class QueueAttributes:
    uid: str
    name: str
    parent: str | None
    children: list
    priority: int
    creation_ts: float
    deserved: np.ndarray
    limit: np.ndarray
    over_quota_weight: np.ndarray
    allocated: np.ndarray = field(default_factory=rs.zeros)
    allocated_non_preemptible: np.ndarray = field(default_factory=rs.zeros)
    request: np.ndarray = field(default_factory=rs.zeros)
    usage: np.ndarray = field(default_factory=rs.zeros)
    fair_share: np.ndarray = field(default_factory=rs.zeros)
    # Mutation stamp + sort-key memo: with a large backlog of identical
    # pending jobs, the DRF queue key is recomputed per requeue although
    # nothing changed — version bumps on every _walk touch.
    version: int = 0
    sort_key_cache: tuple | None = None

    def clone(self) -> "QueueAttributes":
        return QueueAttributes(
            self.uid, self.name, self.parent, list(self.children),
            self.priority, self.creation_ts, self.deserved.copy(),
            self.limit.copy(), self.over_quota_weight.copy(),
            self.allocated.copy(), self.allocated_non_preemptible.copy(),
            self.request.copy(), self.usage.copy(), self.fair_share.copy())

    def allocatable_share(self) -> np.ndarray:
        """GetAllocatableShare (resource_share.go:52-62)."""
        base = np.maximum(self.deserved, self.fair_share)
        capped = np.where(self.limit == UNLIMITED, base,
                          np.minimum(self.limit, base))
        return np.where(self.deserved == UNLIMITED, self.limit, capped)

    def dominant_share(self, total: np.ndarray,
                       extra_allocated: np.ndarray | None = None) -> float:
        """GetDominantResourceShare (queue_resource_share.go:142-162)."""
        allocated = self.allocated.copy()
        if extra_allocated is not None:
            allocated = allocated + extra_allocated
        alloc_share = self.allocatable_share()
        alloc_share = np.where(alloc_share == UNLIMITED, total, alloc_share)
        vals = np.where(alloc_share > 0,
                        allocated / np.where(alloc_share > 0, alloc_share, 1),
                        allocated * NO_FAIR_SHARE_DRF_MULTIPLIER)
        return float(vals.max())


def _less(a: np.ndarray, b: np.ndarray) -> bool:
    """ResourceQuantities.Less: strictly less in EVERY dimension
    (resource_quantities.go:50-57) — one equal dimension (e.g. cpu fair
    share == cpu allocated) already defeats it.  The over-utilized queue
    check rides on this exact semantic."""
    b_eff = np.where(b == UNLIMITED, np.inf, b)
    a_eff = np.where(a == UNLIMITED, np.inf, a)
    return bool(np.all(a_eff < b_eff - 1e-9))


def _less_equal(a: np.ndarray, b: np.ndarray) -> bool:
    b_eff = np.where(b == UNLIMITED, np.inf, b)
    a_eff = np.where(a == UNLIMITED, np.inf, a)
    return bool(np.all(a_eff <= b_eff + 1e-9))


@register_plugin("proportion")
class ProportionPlugin(Plugin):
    def __init__(self, args=None):
        super().__init__(args)
        self.queues: dict[str, QueueAttributes] = {}
        self.total = rs.zeros()
        self.saturation_multiplier = 1.0
        self.min_gpu_mem = 0.0
        # How the session's roll-up was taken: columnar, counted, walked.
        self.rollup = ""

    # -- session wiring ----------------------------------------------------
    def on_session_open(self, ssn) -> None:
        self.ssn = ssn
        self.total = ssn.cluster.total_allocatable()
        self.saturation_multiplier = ssn.config.saturation_multiplier
        self._build_queue_attributes(ssn)
        self._set_fair_share(ssn)
        ssn.queue_order_fns.append(self.queue_order_fn)
        ssn.queue_key_fn = self.queue_sort_key
        ssn.over_capacity_fns.append(self.is_job_over_queue_capacity)
        ssn.non_preemptible_over_quota_fns.append(
            self.is_non_preemptible_over_quota)
        ssn.can_reclaim_fns.append(self.can_reclaim_resources)
        ssn.reclaim_scenario_validators.append(self.reclaim_scenario_valid)
        ssn.allocate_handlers.append(self.on_allocate)
        ssn.deallocate_handlers.append(self.on_deallocate)
        ssn.job_solution_start_fns.append(self.on_job_solution_start)
        self.sim_queues: dict[str, QueueAttributes] = self.queues
        ssn.proportion = self  # expose queue attrs to actions/metrics

    def on_job_solution_start(self) -> None:
        """Clone queue state before a scenario simulation so the validator
        reads pre-eviction attributes (proportion.go:131-136)."""
        self.sim_queues = {qid: q.clone() for qid, q in self.queues.items()}

    @staticmethod
    def _qattr_store(cache) -> dict | None:
        """Persistent per-cache QueueAttributes store (the churn-ring
        queue-axis trim): attribute objects and gauge last-writes
        survive across cycles so a 10k-queue fleet rebuilds only DIRTY
        queues and re-emits only CHANGED gauges.  Single-writer: the
        scheduler thread inside on_session_open (same contract as the
        cache's mirrors)."""
        store = getattr(cache, "_proportion_store", None)
        if store is None:
            store = {"attrs": {}, "sig": {}, "usage_sig": {},
                     "gauges": {}}
            try:
                cache._proportion_store = store
            except Exception:
                return None
        return store

    @staticmethod
    def _queue_sig(q) -> tuple:
        """Value signature of everything a QueueAttributes derives from
        the QueueInfo: any change (spec edit, re-parent, children drift,
        even an in-place quota tweak the per-cycle copy would hide from
        identity checks) rebuilds the entry."""
        return (q.parent, q.priority, q.creation_ts, tuple(q.children),
                q.quota.deserved.tobytes(), q.quota.limit.tobytes(),
                q.quota.over_quota_weight.tobytes())

    def _build_queue_attributes(self, ssn) -> None:
        from ..utils.metrics import METRICS
        cluster = ssn.cluster
        # Usage staleness (docs/DEGRADATION.md): a stale snapshot means
        # the recorder/scraper stopped feeding data — the documented
        # degraded mode IGNORES usage (zeros, the no-penalty division)
        # and counts the cycle, instead of trusting decayed-to-zero
        # values as authoritative history.
        usage_stale = bool(getattr(ssn.queue_usage, "stale", False))
        if usage_stale:
            METRICS.inc("usage_stale_cycles_total")
        store = self._qattr_store(ssn.cache)
        attrs = store["attrs"] if store is not None else {}
        sigs = store["sig"] if store is not None else {}
        usage_sigs = store["usage_sig"] if store is not None else {}
        reused = rebuilt = 0
        self.queues = {}
        for qid, q in cluster.queues.items():
            usage_row = None if usage_stale \
                else ssn.queue_usage.get(qid)
            sig = self._queue_sig(q)
            at = attrs.get(qid)
            if at is not None and sigs.get(qid) == sig:
                # Clean queue: reset the per-cycle accumulators in
                # place instead of re-deriving the whole object (the
                # 10k-queue churn ring re-paid construction + three
                # array conversions per queue per cycle).
                at.allocated[:] = 0.0
                at.allocated_non_preemptible[:] = 0.0
                at.request[:] = 0.0
                usage_sig = None if usage_row is None \
                    else usage_row.tobytes()
                if usage_sigs.get(qid) != usage_sig:
                    at.usage = (rs.zeros() if usage_row is None
                                else np.asarray(usage_row, float))
                    usage_sigs[qid] = usage_sig
                # The reset is a state change: stale DRF sort keys must
                # not survive it.
                at.version += 1
                reused += 1
            else:
                at = QueueAttributes(
                    uid=qid, name=q.name, parent=q.parent,
                    children=list(q.children), priority=q.priority,
                    creation_ts=q.creation_ts,
                    deserved=np.asarray(q.quota.deserved, float).copy(),
                    limit=np.asarray(q.quota.limit, float).copy(),
                    over_quota_weight=np.asarray(
                        q.quota.over_quota_weight, float).copy(),
                    usage=(rs.zeros() if usage_row is None
                           else np.asarray(usage_row, float)))
                attrs[qid] = at
                sigs[qid] = sig
                usage_sigs[qid] = None if usage_row is None \
                    else usage_row.tobytes()
                rebuilt += 1
            self.queues[qid] = at
        if store is not None and len(attrs) > len(self.queues):
            for gone in set(attrs) - set(self.queues):
                attrs.pop(gone, None)
                sigs.pop(gone, None)
                usage_sigs.pop(gone, None)
                store["gauges"].pop(gone, None)
        if reused:
            METRICS.inc("queue_attrs_reused_total", reused)
        if rebuilt:
            METRICS.inc("queue_attrs_rebuilt_total", rebuilt)
        # Roll allocated/non-preemptible/request up the parent chain
        # (proportion.go:347-401), by the cheapest way that is proven to
        # give the pod-by-pod walk's sums to the bit; the span says which,
        # and the counter how often it was the walk (registered first, so a
        # fleet that never walks reads 0 and not nothing).
        METRICS.inc("proportion_rollup_walked_total", 0)
        self.min_gpu_mem = cluster.min_node_gpu_memory()
        batch = getattr(cluster, "columnar_batch", None)
        if batch is not None and self._roll_up_columnar(batch):
            self.rollup = "columnar"
        elif self._roll_up_counted(cluster.queue_rollup()):
            self.rollup = "counted"
        else:
            self._roll_up_walked(cluster)
            self.rollup = "walked"
            METRICS.inc("proportion_rollup_walked_total")
        TRACER.stamp(f"plugin:{self.name}", rollup=self.rollup)

    def _roll_up_walked(self, cluster) -> None:
        """One ``_walk`` a pod and attribute: what the roll-up is defined
        as.  Pending gpu-memory requests are charged gpu_memory /
        MinNodeGPUMemory devices rather than a whole GPU."""
        min_gpu_mem = self.min_gpu_mem
        for pg in cluster.podgroups.values():
            if pg.queue_id not in self.queues:
                continue
            for t in pg.pods.values():
                # Placed tasks resolve gpu-memory against their node's
                # per-GPU memory; pending ones against the cluster minimum.
                req = t.req_vec(cluster.task_gpu_memory_context(t)
                                if t.node_name else min_gpu_mem)
                if t.is_active_allocated():
                    self._walk(pg.queue_id, "allocated", req)
                    self._walk(pg.queue_id, "request", req)
                    if not pg.is_preemptible():
                        self._walk(pg.queue_id, "allocated_non_preemptible",
                                   req)
                elif t.status.name == "PENDING":
                    # Only Pending (not Gated) demand counts toward Request
                    # (proportion.go updateQueuesCurrentResourceUsage) —
                    # unschedulable gated pods must not inflate fair share.
                    self._walk(pg.queue_id, "request", req)

    def _roll_up_counted(self, counted) -> bool:
        """The roll-up from the leaf sums the cycle's one pod walk already
        took (``ClusterInfo.queue_rollup``, which the pack has just asked
        for): each leaf's three vectors go to the leaf and its ancestors.
        The cluster hands them over only where it has proven them the
        additions in turn to the bit (non-negative whole multiples of a
        power of two, under 2**53 of them, no gpu-memory request, so every
        normalisation above is the identity); the same proof is asked of
        every ancestor's total here, in the smallest unit of the leaves
        beneath it.  False, with nothing written, where either is missing."""
        if counted is None:
            return False
        totals: dict = {}   # qid -> [attributes, three sums [3,R], adds, unit]
        for leaf, adds in counted.adds.items():
            if not adds:
                continue
            sums = np.stack((counted.allocated[leaf], counted.requested[leaf],
                             counted.non_preemptible[leaf]))
            unit = counted.unit[leaf]
            q = self.queues.get(leaf)
            while q is not None:
                entry = totals.get(q.uid)
                if entry is None:
                    totals[q.uid] = [q, sums, adds, unit]
                else:
                    entry[1] = entry[1] + sums
                    entry[2] += adds
                    entry[3] = np.minimum(entry[3], unit)
                q = self.queues.get(q.parent) if q.parent else None
        if not all(sums_exact(sums[1], unit)
                   for _q, sums, _n, unit in totals.values()):
            return False
        for q, sums, adds, _u in totals.values():
            # The accumulators were zeroed above, as the walk finds them.
            q.allocated = q.allocated + sums[0]
            q.request = q.request + sums[1]
            q.allocated_non_preemptible = \
                q.allocated_non_preemptible + sums[2]
            q.version += adds
        return True

    def _roll_up_columnar(self, batch: dict) -> bool:
        """Vectorized ``_walk`` roll-up over the columnar snapshot batch
        (DESIGN §11): per pod, its request is added to its queue and
        every ancestor — expressed as one ``np.add.at`` per attribute
        over ancestor-expanded indices in pod order, which applies the
        exact same sequential float folds as the per-pod walk (each
        accumulator starts at zero and receives its adds in the same
        order), so fair-share inputs are bit-identical.  The batch only
        exists on simple-pod columnar snapshots, where every request
        vector is context-free (no gpu-memory/MIG resolution)."""
        q_uids = batch["q_uids"]
        if list(self.queues) != q_uids:
            return False  # queue view drifted: take the object walk
        qidx = np.asarray(batch["qidx"])
        reqs = batch["reqs"]
        n_q = len(q_uids)
        if n_q == 0 or qidx.size == 0:
            return True
        anc = batch.get("queue_anc")
        if anc is None or anc.shape[0] != n_q:
            # The batch's ancestor table (built with the queue columns,
            # aligned with q_uids) is the one source of chains; without
            # it — or on a shape drift — the object walk is the truth.
            return False
        depth = anc.shape[1]
        valid = qidx >= 0
        exp = anc[np.where(valid, qidx, 0)]       # [P, D]
        exp[~valid] = -1
        flat = exp.reshape(-1)
        ok = flat >= 0
        rep = np.repeat(reqs, depth, axis=0)
        active = np.asarray(batch["active"])
        pending = np.asarray(batch["pending"])
        non_preempt = active & ~np.asarray(batch["preemptible"])
        versions = np.zeros(n_q, np.int64)
        for attr, mask in (("allocated", active),
                           ("request", active | pending),
                           ("allocated_non_preemptible", non_preempt)):
            m = np.repeat(mask, depth) & ok
            if not m.any():
                continue
            mat = np.zeros((n_q, reqs.shape[1]))
            np.add.at(mat, flat[m], rep[m])
            counts = np.bincount(flat[m], minlength=n_q)
            versions += counts
            for i in np.nonzero(counts)[0].tolist():
                # Accumulators start at rs.zeros(), so the add.at fold
                # (same adds, same order, from zero) IS the walked value.
                setattr(self.queues[q_uids[i]], attr, mat[i])
        for i in np.nonzero(versions)[0].tolist():
            self.queues[q_uids[i]].version += int(versions[i])
        return True

    def _walk(self, qid: str, attr: str, req: np.ndarray) -> None:
        q = self.queues.get(qid)
        while q is not None:
            setattr(q, attr, getattr(q, attr) + req)
            q.version += 1
            q = self.queues.get(q.parent) if q.parent else None

    def _set_fair_share(self, ssn) -> None:
        """Run the hierarchical division kernel (proportion.go:403-440):
        ONE jitted dispatch for the whole queue hierarchy, with the host
        prep (hierarchy build, dense level layout, weight-tensor upload)
        cached across cycles keyed on the queue set + weights
        (ops/fairshare.prepared_forest) — a steady 10k-queue forest pays
        one hash and one dispatch.  Property-tested bit-identical to the
        per-level reference ``fair_share_levels``."""
        from ..utils.metrics import METRICS
        qids = sorted(self.queues)
        index = {qid: i for i, qid in enumerate(qids)}
        n = len(qids)
        if n == 0:
            return
        parent = np.array([index.get(self.queues[q].parent, -1)
                           if self.queues[q].parent else -1
                           for q in qids], np.int64)
        priority = np.array([self.queues[q].priority for q in qids])
        creation = np.array([self.queues[q].creation_ts for q in qids])
        stack = lambda attr: np.stack(
            [getattr(self.queues[q], attr) for q in qids])
        deserved, limit = stack("deserved"), stack("limit")
        oqw = stack("over_quota_weight")
        request, usage = stack("request"), stack("usage")
        validate = lambda r: getattr(r, "shape", (0,))[0] >= n
        # Guarded like every other device dispatch: session open must
        # degrade to the CPU fallback on a dead device, not wedge the
        # cycle before its first action.
        with TRACER.span("fairshare", kind="fairshare", queues=n) as sp:
            # The prep (hierarchy build + layout/weight uploads) lives
            # INSIDE the guarded thunk: its jnp.asarray calls touch the
            # device, and on a guard fallback the thunk re-runs on the
            # CPU backend AFTER fallback_calls bumped — so
            # prepared_forest's GuardWatch drops the dead-device cache
            # entry and rebuilds host-side.
            info: dict = {}

            def forest_thunk():
                prep = fsops.prepared_forest(
                    parent, priority, creation, qids, deserved,
                    limit, oqw, out_info=info)
                info["prep"] = prep
                return fsops.fair_share_forest(
                    self.total, ssn.config.k_value, prep, request,
                    usage)

            fair = ssn.dispatch_kernel(forest_thunk, label="fair_share",
                                       validate=validate)
            prep = info.get("prep")
            if prep is not None:
                sp.set(levels=prep.spec.num_levels,
                       bands=prep.spec.num_bands,
                       prep_reused=bool(info.get("reused")))
        fair = fsops.restore_exact(fair, deserved, limit, request)
        store = self._qattr_store(ssn.cache)
        gauges = store["gauges"] if store is not None else {}
        deduped = 0
        for qid, i in index.items():
            self.queues[qid].fair_share = fair[i]
            # Queue fair-share/usage gauges (metrics.UpdateQueueFairShare,
            # resource_division.go:44-90).  Deduped against the per-cache
            # last-written values: at 10k queues the three unconditional
            # writes per queue per cycle (label formatting included) were
            # a named churn-ring bottleneck, while steady-state values
            # barely move.
            q = self.queues[qid]
            vals = (float(q.fair_share[rs.RES_GPU]),
                    float(q.fair_share[rs.RES_CPU])
                    / rs.MILLI_CPU_TO_CORES,
                    float(q.allocated[rs.RES_GPU]))
            if gauges.get(qid) == vals:
                deduped += 1
                continue
            gauges[qid] = vals
            METRICS.set_gauge("queue_fair_share_gpu", vals[0], queue=qid)
            METRICS.set_gauge("queue_fair_share_cpu_cores", vals[1],
                              queue=qid)
            METRICS.set_gauge("queue_allocated_gpus", vals[2], queue=qid)
        if deduped:
            METRICS.inc("queue_gauge_writes_deduped_total", deduped)

    # -- event handlers (proportion.go:446-476) ----------------------------
    def on_allocate(self, task) -> None:
        pg = self.ssn.cluster.podgroups.get(task.job_id)
        if pg is None or pg.queue_id not in self.queues:
            return
        # Same gpu-memory normalization as the roll-up, or within-cycle
        # allocated totals drift from the snapshot's accounting.
        req = task.req_vec(self.ssn.cluster.task_gpu_memory_context(task)
                           if task.node_name else self.min_gpu_mem)
        self._walk(pg.queue_id, "allocated", req)
        if not pg.is_preemptible():
            self._walk(pg.queue_id, "allocated_non_preemptible", req)

    def on_deallocate(self, task, prev_status) -> None:
        pg = self.ssn.cluster.podgroups.get(task.job_id)
        if pg is None or pg.queue_id not in self.queues:
            return
        req = -task.req_vec(self.ssn.cluster.task_gpu_memory_context(task)
                            if task.node_name else self.min_gpu_mem)
        self._walk(pg.queue_id, "allocated", req)
        if not pg.is_preemptible():
            self._walk(pg.queue_id, "allocated_non_preemptible", req)

    def queue_sort_key(self, qid: str, peek_job) -> tuple:
        """Scalar key mirroring queue_order_fn's comparator stages, for
        bulk-mode sorting (pairwise numpy comparisons are too slow at
        thousands of queues x jobs).  The allocatable-share tie-break
        collapses to a sum — a total-order approximation of the partial
        order the comparator uses."""
        q = self.queues[qid]
        req = _job_req(peek_job)
        stamp = (q.version, req.tobytes())
        if q.sort_key_cache is not None and q.sort_key_cache[0] == stamp:
            return q.sort_key_cache[1]
        over = _less(q.fair_share, q.allocated)
        with_job = q.allocated + req
        starved = _less_equal(with_job, q.deserved)
        viol = _zero_share_violation(q, with_job)
        share_with_job = q.dominant_share(self.total, req)
        share0 = q.dominant_share(self.total)
        alloc_sum = float(np.where(q.allocatable_share() == UNLIMITED,
                                   self.total,
                                   q.allocatable_share()).sum())
        # +alloc_sum: the smaller allocatable share wins the tie-break,
        # matching queue_order_fn and prioritizeBasedOnAllocatableShare
        # (queue_order.go).
        key = (over, not starved, -q.priority, viol, share_with_job,
               share0, alloc_sum, q.creation_ts)
        q.sort_key_cache = (stamp, key)
        return key

    # -- queue ordering (queue_order/queue_order.go:19-242) ----------------
    def queue_order_fn(self, l: str, r: str, l_job, r_job,
                       l_victims, r_victims) -> int:
        lq, rq = self.queues[l], self.queues[r]

        l_over = _less(lq.fair_share, lq.allocated)
        r_over = _less(rq.fair_share, rq.allocated)
        if not l_over and r_over:
            return -1
        if l_over and not r_over:
            return 1

        l_with_job = lq.allocated + _job_req(l_job)
        r_with_job = rq.allocated + _job_req(r_job)
        l_starved = _less_equal(l_with_job, lq.deserved)
        r_starved = _less_equal(r_with_job, rq.deserved)
        if l_starved and not r_starved:
            return -1
        if r_starved and not l_starved:
            return 1

        if lq.priority != rq.priority:
            return -1 if lq.priority > rq.priority else 1

        l_viol = _zero_share_violation(lq, l_with_job)
        r_viol = _zero_share_violation(rq, r_with_job)
        if l_viol and not r_viol:
            return 1
        if r_viol and not l_viol:
            return -1

        l_share = lq.dominant_share(
            self.total, _job_req(l_job) - _victims_req(l_victims))
        r_share = rq.dominant_share(
            self.total, _job_req(r_job) - _victims_req(r_victims))
        if l_share != r_share:
            return -1 if l_share < r_share else 1

        l_share0 = lq.dominant_share(self.total)
        r_share0 = rq.dominant_share(self.total)
        if l_share0 != r_share0:
            return -1 if l_share0 < r_share0 else 1

        la, ra = lq.allocatable_share(), rq.allocatable_share()
        if _less(la, ra):
            return -1
        if _less(ra, la):
            return 1

        return -1 if lq.creation_ts <= rq.creation_ts else 1

    # -- capacity gates (capacity_policy/) ---------------------------------
    def is_job_over_queue_capacity(self, job: PodGroupInfo,
                                   tasks) -> SchedulableResult:
        res = self._over_limit(job, tasks)
        if not res.schedulable:
            return res
        return self.is_non_preemptible_over_quota(job, tasks)

    def _over_limit(self, job, tasks) -> SchedulableResult:
        req = _tasks_req(tasks)
        q = self.queues.get(job.queue_id)
        while q is not None:
            over = (q.limit != UNLIMITED) & (req > 1e-9) \
                & (q.limit < q.allocated + req - 1e-9)
            if np.any(over):
                i = int(np.argmax(over))
                return SchedulableResult(
                    False, "OverLimit",
                    f"queue {q.name} over limit on "
                    f"{rs.RESOURCE_NAMES[i]}: limit {q.limit[i]:g}, "
                    f"allocated {q.allocated[i]:g}, requested {req[i]:g}")
            q = self.queues.get(q.parent) if q.parent else None
        return SchedulableResult()

    def is_non_preemptible_over_quota(self, job, tasks) -> SchedulableResult:
        if job.is_preemptible():
            return SchedulableResult()
        req = _tasks_req(tasks)
        q = self.queues.get(job.queue_id)
        while q is not None:
            deserved = np.where(q.deserved == UNLIMITED, np.inf, q.deserved)
            if np.any(q.allocated_non_preemptible + req > deserved + 1e-9):
                return SchedulableResult(
                    False, "NonPreemptibleOverQuota",
                    f"non-preemptible job over quota in queue {q.name}")
            q = self.queues.get(q.parent) if q.parent else None
        return SchedulableResult()

    # -- reclaim legality (reclaimable/) -----------------------------------
    def can_reclaim_resources(self, job: PodGroupInfo) -> bool:
        """CanReclaimResources (reclaimable.go:30-55)."""
        q = self.queues.get(job.queue_id)
        if q is None:
            return False
        req = job.tasks_to_allocate_init_resource()
        if not _less_equal(q.allocated + req, q.fair_share):
            return False
        if job.is_preemptible():
            return True
        return _less_equal(q.allocated_non_preemptible + req, q.deserved)

    def reclaim_scenario_valid(self, scenario) -> bool:
        """Reclaimable (reclaimable.go:57-165): simulate post-reclaim
        allocations and check the strategy + sibling saturation rules."""
        queues = self.sim_queues  # pre-simulation clone (OnJobSolutionStart)
        reclaimer = scenario.pending_job
        victims_by_queue: dict[str, list[np.ndarray]] = {}
        for vjob, vtasks in scenario.victims:
            victims_by_queue.setdefault(vjob.queue_id, []).extend(
                t.req_vec() for t in vtasks)

        req = _tasks_req(scenario.pending_tasks)
        remaining: dict[str, np.ndarray] = {}
        involved: dict[str, set] = {}

        def rem(qid):
            if qid not in remaining:
                remaining[qid] = queues[qid].allocated.copy()
            return remaining[qid]

        for qid, reqs in victims_by_queue.items():
            if qid not in queues:
                return False
            reclaimee = queues[qid]
            involved.setdefault(qid, set())
            for v in reqs:
                involved[qid] |= {i for i in range(rs.NUM_RES) if v[i] > 0}
                if not self._fits_reclaim_strategy(req, reclaimer, reclaimee,
                                                   rem(qid)):
                    return False
                # subtract up the chain
                q = reclaimee
                while q is not None:
                    rem(q.uid)
                    remaining[q.uid] = remaining[q.uid] - v
                    involved.setdefault(q.uid, set()).update(involved[qid])
                    q = queues.get(q.parent) if q.parent else None

        # Reclaiming queue chain must stay within boundaries (:134-190).
        involved_reclaimer = {i for i in range(rs.NUM_RES) if req[i] > 0}
        q = queues.get(reclaimer.queue_id)
        while q is not None:
            my_remaining = remaining.get(q.uid, q.allocated.copy()) + req
            for sib_id in list(remaining):
                sib = queues.get(sib_id)
                if sib is None or sib.parent != q.parent or sib.uid == q.uid:
                    continue
                inv = involved.get(sib_id, set()) | involved_reclaimer
                if not self._saturation_lower(
                        inv, my_remaining, q.fair_share,
                        remaining.get(sib_id, sib.allocated), sib.fair_share):
                    return False
            if not reclaimer.is_preemptible():
                deserved = np.where(q.deserved == UNLIMITED, np.inf,
                                    q.deserved)
                if np.any(q.allocated_non_preemptible + req > deserved + 1e-9):
                    return False
            q = queues.get(q.parent) if q.parent else None
        return True

    def _fits_reclaim_strategy(self, reclaimer_req, reclaimer_job, reclaimee,
                               reclaimee_remaining) -> bool:
        """strategies.go: MaintainFairShare OR GuaranteeDeservedQuota."""
        # Maintain fair share: reclaimee currently over its allocatable share.
        if not _less_equal(reclaimee_remaining, reclaimee.allocatable_share()):
            return True
        # Guarantee deserved quota: reclaimer stays under quota, reclaimee
        # above quota in at least one resource.
        rq = self.sim_queues.get(reclaimer_job.queue_id)
        if rq is None:
            return False
        if not _less_equal(rq.allocated + reclaimer_req, rq.deserved):
            return False
        return not _less_equal(reclaimee_remaining, reclaimee.deserved)

    def _saturation_lower(self, involved, rec_alloc, rec_fair, sib_alloc,
                          sib_fair) -> bool:
        """isFairShareSaturationLowerPerResource (reclaimable.go:195-218)."""
        for i in involved:
            rf, sf = rec_fair[i], sib_fair[i]
            if rf == UNLIMITED and sf == UNLIMITED:
                continue
            ratio_rec = _saturation_ratio(rec_alloc[i], rf)
            ratio_sib = _saturation_ratio(sib_alloc[i], sf)
            if (ratio_rec > 1 and sf > 0
                    and ratio_rec * self.saturation_multiplier >= ratio_sib):
                return False
        return True


def _saturation_ratio(allocated: float, fair: float) -> float:
    if fair == 0:
        return np.inf if allocated > 0 else 0.0
    if fair == UNLIMITED:
        return 0.0
    return allocated / fair


def _job_req(job) -> np.ndarray:
    if job is None:
        return rs.zeros()
    return job.tasks_to_allocate_init_resource()


def _victims_req(victims) -> np.ndarray:
    if not victims:
        return rs.zeros()
    total = rs.zeros()
    for vjob in victims:
        for t in vjob.pods.values():
            if t.is_active_allocated():
                total += t.req_vec()
    return total


def _tasks_req(tasks) -> np.ndarray:
    total = rs.zeros()
    for t in tasks:
        total += t.req_vec()
    return total


def _zero_share_violation(q: QueueAttributes,
                          allocated_with_job: np.ndarray) -> bool:
    alloc_share = q.allocatable_share()
    return bool(np.any((alloc_share == 0) & (allocated_with_job > 0)))
