"""Snapshot -> dense tensor packing: the host<->device seam.

The reference keeps dense ``ResourceVector`` mirrors alongside its pointer
graph precisely so state can be serialized cheaply
(pkg/scheduler/api/node_info/node_info.go:82-89,
resource_info/resource_vector.go:15).  Here that seam is primary: once per
cycle the ClusterInfo packs into the arrays below and ships to the device,
where the predicate mask, score matrix, fair-share vectors, and gang
allocation run as one jitted program (SURVEY.md §7).

Label/taint constraints are encoded through a vocabulary codec so that the
node-affinity and toleration predicates become pure integer-compare tensor
ops (no strings on device).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from . import resources as rs
from .cluster_info import ClusterInfo
from .pod_info import PodInfo
from .podgroup_info import PodGroupInfo

NO_LABEL = -1      # node lacks the label / task doesn't constrain it
NO_TAINT = -1

# Monotonic pack counter for epoch-validated task row indices.
_PACK_EPOCH = 0


class LabelCodec:
    """Maps (label key -> column, label value -> int code) and taints -> codes."""

    def __init__(self):
        self.key_cols: dict[str, int] = {}
        self.value_codes: dict[tuple[str, str], int] = {}
        self.taint_codes: dict[str, int] = {}

    def key_col(self, key: str) -> int:
        if key not in self.key_cols:
            self.key_cols[key] = len(self.key_cols)
        return self.key_cols[key]

    def value_code(self, key: str, value: str) -> int:
        k = (key, value)
        if k not in self.value_codes:
            self.value_codes[k] = len(self.value_codes)
        return self.value_codes[k]

    def taint_code(self, taint: str) -> int:
        if taint not in self.taint_codes:
            self.taint_codes[taint] = len(self.taint_codes)
        return self.taint_codes[taint]

    @property
    def num_cols(self) -> int:
        return len(self.key_cols)


@dataclass
class SnapshotTensors:
    """Dense, device-ready view of one scheduling cycle's inputs."""
    # --- nodes [N, ...] ---
    node_allocatable: np.ndarray   # [N,R] f64
    node_idle: np.ndarray          # [N,R]
    node_releasing: np.ndarray     # [N,R]
    node_labels: np.ndarray        # [N,L] int32, NO_LABEL where absent
    node_taints: np.ndarray        # [N,Tt] int32, NO_TAINT padding
    node_pod_room: np.ndarray      # [N] f64 remaining pod slots
    # --- tasks (pending, candidate set) [T, ...] ---
    task_req: np.ndarray           # [T,R] f64
    task_job: np.ndarray           # [T] int32 job index
    task_selector: np.ndarray      # [T,L] int32, NO_LABEL = unconstrained
    task_tolerations: np.ndarray   # [T,Tl] int32, NO_TAINT padding
    task_rank: np.ndarray          # [T] int32 MPI gang rank, -1 unranked
    # --- jobs [J, ...] ---
    job_queue: np.ndarray          # [J] int32 queue index
    job_min_available: np.ndarray  # [J] int32
    job_task_start: np.ndarray     # [J] int32 offset into task arrays
    job_task_count: np.ndarray     # [J] int32
    # --- queues [Q, ...] ---
    queue_deserved: np.ndarray     # [Q,R] f64 (UNLIMITED = -1)
    queue_limit: np.ndarray        # [Q,R]
    queue_over_quota_weight: np.ndarray  # [Q,R]
    queue_priority: np.ndarray     # [Q] int32
    queue_parent: np.ndarray       # [Q] int32, -1 for top queues
    queue_creation: np.ndarray     # [Q] f64
    queue_allocated: np.ndarray    # [Q,R] f64
    queue_requested: np.ndarray    # [Q,R] f64
    queue_usage: np.ndarray        # [Q,R] f64 normalized historical usage
    # --- index maps (host-side only) ---
    node_names: list = field(default_factory=list)
    task_uids: list = field(default_factory=list)
    job_uids: list = field(default_factory=list)
    queue_uids: list = field(default_factory=list)
    codec: "LabelCodec | None" = None
    # Epoch stamped onto packed tasks' tensor_epoch: a task's tensor_idx
    # is valid for THIS snapshot only if its epoch matches (row_of).
    pack_epoch: int = 0

    def row_of(self, task) -> int:
        """The task's row in the task arrays, or -1 when it wasn't packed
        in this snapshot (stale index from an earlier pack)."""
        if getattr(task, "tensor_epoch", -1) == self.pack_epoch:
            return task.tensor_idx
        return -1

    @property
    def num_nodes(self) -> int:
        return self.node_allocatable.shape[0]

    @property
    def num_tasks(self) -> int:
        return self.task_req.shape[0]


def build_codec(cluster: ClusterInfo,
                tasks: list[PodInfo]) -> LabelCodec:
    codec = LabelCodec()
    # Label keys constrained by ANY pod need columns — scenario simulation
    # re-encodes evicted (non-candidate) tasks for re-placement, so the
    # vocabulary must cover every pod (candidates included), not just this
    # cycle's candidate list.  A columnar snapshot proves the whole pod
    # population selector-free up front (DESIGN §11) — same empty key
    # set, no O(pods) walk.
    hints = getattr(cluster, "columnar_hints", None)
    if not (hints and hints.get("no_selectors")):
        for pg in cluster.podgroups.values():
            for t in pg.pods.values():
                if t.node_selector:
                    for k in t.node_selector:
                        codec.key_col(k)
    for node in cluster.nodes.values():
        if node.labels:
            for k, v in node.labels.items():
                if k in codec.key_cols:
                    codec.value_code(k, v)
        for taint in node.taints:
            codec.taint_code(taint)
    return codec


def survey_pods(cluster: ClusterInfo) -> tuple[list, list]:
    """``(vocabulary pods, term carriers)`` from one walk over every pod.

    The first is the pods' part of ``vocabulary_signature``.  The term
    carriers are the pods that carry an inter-pod term (required or
    preferred, affinity or anti-affinity; any status, any node), in walk
    order: what ``Session.term_carriers`` hands the pod-affinity gate so
    that it asks and does not list every running pod."""
    pods, carriers = [], []
    pod_visits = 0
    for pg in cluster.podgroups.values():
        pod_visits += len(pg.pods)
        for t in pg.pods.values():
            if t.node_selector or t.tolerations:
                pods.append((t.uid, tuple(t.node_selector.items()),
                             tuple(sorted(t.tolerations))))
            if (t.affinity_terms or t.anti_affinity_terms
                    or t.preferred_affinity_terms
                    or t.preferred_anti_affinity_terms):
                carriers.append(t)
    METRICS.inc("fleet_walk_pod_visits_total", pod_visits, walk="pod_survey")
    return pods, carriers


def vocabulary_signature(cluster: ClusterInfo) -> tuple[tuple, list]:
    """``(signature, term carriers)``: what of the cluster the label
    codec, the codec-derived widths and the label and taint rows of the
    nodes are made from, in the order ``pack`` meets it: every pod that
    carries a selector or a toleration, and every node that carries a
    taint or a label some pod selects on; and, since the walk over every
    pod is made here before a pack knows whether it patches, what else
    ``survey_pods`` found on it.

    Two packs of one cluster whose signatures are equal share the codec,
    ``max_tols``, ``max_taints``, ``node_labels`` and ``node_taints``:
    the proof ``pack_incremental`` wants of a caller that has no watch
    stream to tell it (framework/arena.py ``HostArena``).  A fleet that
    selects on nothing and taints nothing reads ``((), ())`` after two
    plain walks; an unequal signature costs a full pack, never a wrong
    one."""
    pods, carriers = survey_pods(cluster)
    keys = {k for _uid, selector, _tols in pods for k, _v in selector}
    # Without a selector anywhere no label has a column: taints alone.
    nodes = [
        (name, tuple((k, v) for k, v in node.labels.items() if k in keys),
         tuple(node.taints))
        for name, node in cluster.nodes.items()
        if node.taints or node.labels] if keys else [
        (name, (), tuple(node.taints))
        for name, node in cluster.nodes.items() if node.taints]
    return (pods, nodes), carriers


def _select_jobs(cluster: ClusterInfo,
                 jobs: list[PodGroupInfo] | None) -> list[PodGroupInfo]:
    if jobs is None:
        jobs = sorted(cluster.pending_jobs(), key=lambda j: j.uid)
    # A job pointing at an unknown queue must not alias onto queue 0.
    return [pg for pg in jobs if pg.queue_id in cluster.queues]


def _select_tasks(jobs: list[PodGroupInfo], real_allocation: bool
                  ) -> tuple[list[PodInfo], list[int], list[int]]:
    # Pack every candidate task (not just the first gang chunk): actions
    # may allocate a job in several chunks per cycle (elastic growth), and
    # each chunk slices rows out of these arrays by tensor_idx.
    tasks: list[PodInfo] = []
    job_start, job_count = [], []
    for pg in jobs:
        start = len(tasks)
        sel = sorted((t for t in pg.pods.values()
                      if pg._should_allocate(t, real_allocation)),
                     key=lambda t: (t.name, t.uid))
        tasks.extend(sel)
        job_start.append(start)
        job_count.append(len(sel))
    return tasks, job_start, job_count


def _stamp_tasks(tasks: list[PodInfo]) -> int:
    # Row indices are epoch-stamped: a task whose tensor_epoch doesn't
    # match this pack's epoch has a stale tensor_idx (consumers check via
    # SnapshotTensors.row_of) — O(1) invalidation instead of a walk over
    # every pod in the cluster.
    global _PACK_EPOCH
    _PACK_EPOCH += 1
    epoch = _PACK_EPOCH
    for i, t in enumerate(tasks):
        t.tensor_idx = i
        t.tensor_epoch = epoch
    return epoch


def _pack_task_arrays(tasks: list[PodInfo], jobs: list[PodGroupInfo],
                      codec: LabelCodec, L: int, max_tols: int) -> tuple:
    t_count = len(tasks)
    task_req = np.zeros((max(t_count, 1), rs.NUM_RES))
    task_job = np.zeros(max(t_count, 1), np.int32)
    task_sel = np.full((max(t_count, 1), L), NO_LABEL, np.int32)
    task_tol = np.full((max(t_count, 1), max_tols), NO_TAINT, np.int32)
    task_rank = np.full(max(t_count, 1), -1, np.int32)
    job_index = {pg.uid: j for j, pg in enumerate(jobs)}
    key_cols = codec.key_cols
    taint_codes = codec.taint_codes
    if tasks:
        # Node-fit vectors: MIG profiles are per-node scalar inventory
        # checked host-side, not whole-GPU draws (MIG jobs route to the
        # host path in actions/allocate).  Stacked in one pass; the
        # memoized to_vec returns shared read-only rows.
        task_req[:t_count] = np.stack(
            [t.res_req.to_vec(mig_as_gpu=False) for t in tasks])
        task_job[:t_count] = np.fromiter(
            (job_index[t.job_id] for t in tasks), np.int32, count=t_count)
        task_rank[:t_count] = np.fromiter(
            (t.rank for t in tasks), np.int32, count=t_count)
    for i, t in enumerate(tasks):
        if t.node_selector:
            for k, v in t.node_selector.items():
                task_sel[i, key_cols[k]] = codec.value_code(k, v)
        if t.tolerations:
            for j, tol in enumerate(sorted(t.tolerations)):
                if tol in taint_codes:
                    task_tol[i, j] = taint_codes[tol]
    return task_req, task_job, task_sel, task_tol, task_rank


def _pack_queue_arrays(cluster: ClusterInfo,
                       queue_usage: dict | None) -> tuple:
    queue_uids = sorted(cluster.queues)
    q_index = {qid: i for i, qid in enumerate(queue_uids)}
    q = max(len(queue_uids), 1)
    q_deserved = np.zeros((q, rs.NUM_RES))
    q_limit = np.full((q, rs.NUM_RES), rs.UNLIMITED)
    q_oqw = np.ones((q, rs.NUM_RES))
    q_prio = np.zeros(q, np.int32)
    q_parent = np.full(q, -1, np.int32)
    q_creation = np.zeros(q)
    q_alloc = np.zeros((q, rs.NUM_RES))
    q_req = np.zeros((q, rs.NUM_RES))
    q_usage = np.zeros((q, rs.NUM_RES))
    with TRACER.span("snapshot:aggregates", kind="snapshot_part"):
        allocated, requested = cluster.queue_aggregates()
    for qid, i in q_index.items():
        info = cluster.queues[qid]
        q_deserved[i] = info.quota.deserved
        q_limit[i] = info.quota.limit
        q_oqw[i] = info.quota.over_quota_weight
        q_prio[i] = info.priority
        q_parent[i] = q_index.get(info.parent, -1) if info.parent else -1
        q_creation[i] = info.creation_ts
        q_alloc[i] = allocated.get(qid, rs.zeros())
        q_req[i] = requested.get(qid, rs.zeros())
        if queue_usage and qid in queue_usage:
            q_usage[i] = queue_usage[qid]
    return (queue_uids, q_index, q_deserved, q_limit, q_oqw, q_prio,
            q_parent, q_creation, q_alloc, q_req, q_usage)


def _pack_job_arrays(jobs: list[PodGroupInfo], q_index: dict) -> tuple:
    job_q = np.array([q_index[pg.queue_id] for pg in jobs] or [0], np.int32)
    job_min = np.array(
        [sum(ps.min_available for ps in pg.pod_sets.values()) for pg in jobs]
        or [0], np.int32)
    return job_q, job_min


def pack(cluster: ClusterInfo,
         jobs: list[PodGroupInfo] | None = None,
         queue_usage: dict[str, np.ndarray] | None = None,
         pad_nodes_to: int | None = None,
         real_allocation: bool = True) -> SnapshotTensors:
    """Pack the snapshot; ``jobs`` selects the candidate pending jobs
    (defaults to all jobs with tasks to allocate).  ``pad_nodes_to`` rounds
    the node axis up to a bucket size to avoid recompilation across cycles.
    ``real_allocation=False`` additionally admits RELEASING tasks as
    candidates — only scenario simulation wants that.
    """
    jobs = _select_jobs(cluster, jobs)
    tasks, job_start, job_count = _select_tasks(jobs, real_allocation)
    epoch = _stamp_tasks(tasks)

    codec = build_codec(cluster, tasks)
    L = max(1, codec.num_cols)
    max_taints = max([len(n.taints) for n in cluster.nodes.values()] + [1])
    # Toleration width covers every pod (scenario re-encoding needs it);
    # a columnar snapshot carries the exact width as a hint (the same
    # max over the same population, reduced on the column).
    hints = getattr(cluster, "columnar_hints", None)
    if hints and "max_tols" in hints:
        max_tols = hints["max_tols"]
    else:
        max_tols = max([len(t.tolerations)
                        for pg in cluster.podgroups.values()
                        for t in pg.pods.values()] + [1])

    node_names = cluster.node_order
    n = len(node_names)
    n_pad = max(pad_nodes_to or n, n)

    node_alloc = np.zeros((n_pad, rs.NUM_RES))
    node_idle = np.zeros((n_pad, rs.NUM_RES))
    node_rel = np.zeros((n_pad, rs.NUM_RES))
    node_labels = np.full((n_pad, L), NO_LABEL, np.int32)
    node_taints = np.full((n_pad, max_taints), NO_TAINT, np.int32)
    node_room = np.zeros(n_pad)
    # Stacked-vector fill: one C-level stack per matrix instead of a
    # Python row-assignment loop (the loop was ~40% of pack at 100k
    # nodes); label/taint encoding skips unlabeled nodes.
    node_objs = [cluster.nodes[name] for name in node_names]
    if node_objs:
        node_alloc[:n] = np.stack([nd.allocatable for nd in node_objs])
        used = np.stack([nd.used for nd in node_objs])
        node_idle[:n] = node_alloc[:n] - used
        node_rel[:n] = np.stack([nd.releasing for nd in node_objs])
        node_room[:n] = np.fromiter(
            (max(0, nd.max_pods - len(nd.pod_infos)) for nd in node_objs),
            float, count=n)
    key_cols = codec.key_cols
    value_codes = codec.value_codes
    taint_codes = codec.taint_codes
    for i, node in enumerate(node_objs):
        if node.labels and key_cols:
            for k, v in node.labels.items():
                col = key_cols.get(k)
                if col is not None:
                    node_labels[i, col] = value_codes[(k, v)]
        if node.taints:
            for j, taint in enumerate(sorted(node.taints)):
                node_taints[i, j] = taint_codes[taint]

    task_req, task_job, task_sel, task_tol, task_rank = _pack_task_arrays(
        tasks, jobs, codec, L, max_tols)

    (queue_uids, q_index, q_deserved, q_limit, q_oqw, q_prio, q_parent,
     q_creation, q_alloc, q_req, q_usage) = _pack_queue_arrays(
        cluster, queue_usage)

    job_q, job_min = _pack_job_arrays(jobs, q_index)

    return SnapshotTensors(
        node_allocatable=node_alloc, node_idle=node_idle,
        node_releasing=node_rel, node_labels=node_labels,
        node_taints=node_taints, node_pod_room=node_room,
        task_req=task_req, task_job=task_job, task_selector=task_sel,
        task_tolerations=task_tol, task_rank=task_rank,
        job_queue=job_q, job_min_available=job_min,
        job_task_start=np.array(job_start or [0], np.int32),
        job_task_count=np.array(job_count or [0], np.int32),
        queue_deserved=q_deserved, queue_limit=q_limit,
        queue_over_quota_weight=q_oqw, queue_priority=q_prio,
        queue_parent=q_parent, queue_creation=q_creation,
        queue_allocated=q_alloc, queue_requested=q_req, queue_usage=q_usage,
        node_names=list(node_names), task_uids=[t.uid for t in tasks],
        job_uids=[pg.uid for pg in jobs], queue_uids=queue_uids,
        codec=codec, pack_epoch=epoch,
    )


def pack_incremental(cluster: ClusterInfo, prev: SnapshotTensors,
                     dirty_nodes: set,
                     queue_usage: dict[str, np.ndarray] | None = None,
                     pad_nodes_to: int | None = None,
                     reuse_tasks: bool = False
                     ) -> tuple[SnapshotTensors, np.ndarray]:
    """Delta pack against the previous cycle's tensors (framework/arena).

    Bit-identical to ``pack(cluster, queue_usage=..., pad_nodes_to=...)``
    under the caller's preconditions (ClusterArena verifies them from the
    watch-event-derived dirty state before calling, HostArena from the
    ``NodeInfo.version`` stamps and ``vocabulary_signature``):

    - the node set and order are unchanged and no Node object changed
      (else: topology change, full rebuild);
    - the label/taint/toleration vocabulary is unchanged — no
      selector- or toleration-bearing pod was added/modified/removed —
      so ``prev.codec`` and every codec-derived array width still hold;
    - ``pad_nodes_to`` matches the previous pack (pow2 bucket growth
      forces a rebuild);
    - ``dirty_nodes`` is a superset of every node whose pod set, pod
      manifests, or accounting changed since ``prev`` was packed.

    Static node arrays (allocatable/labels/taints) are shared BY
    REFERENCE with ``prev`` — that identity is what lets the device
    arena key its uploaded copies by generation.  Mutable state arrays
    are copied and only the dirty rows recomputed.  Task/job/queue
    arrays rebuild from the live cluster (they are small next to the
    node axis) unless ``reuse_tasks`` proves nothing feeding them
    changed, in which case they are shared too.

    Returns ``(tensors, changed_row_indices)``.
    """
    jobs = _select_jobs(cluster, None)
    tasks, job_start, job_count = _select_tasks(jobs, True)
    epoch = _stamp_tasks(tasks)

    codec = prev.codec
    L = prev.node_labels.shape[1]
    max_tols = prev.task_tolerations.shape[1]

    node_names = cluster.node_order
    node_idle = prev.node_idle.copy()
    node_rel = prev.node_releasing.copy()
    node_room = prev.node_pod_room.copy()
    node_alloc = prev.node_allocatable
    rows = sorted(cluster.nodes[nm].idx for nm in dirty_nodes
                  if nm in cluster.nodes)
    if rows:
        # The full pack's own fill over the dirty rows alone: the same
        # stacked gathers and the same float expressions, elementwise
        # identical on identical inputs.
        objs = [cluster.nodes[node_names[i]] for i in rows]
        node_idle[rows] = node_alloc[rows] - np.stack(
            [nd.used for nd in objs])
        node_rel[rows] = np.stack([nd.releasing for nd in objs])
        node_room[rows] = np.fromiter(
            (max(0, nd.max_pods - len(nd.pod_infos)) for nd in objs),
            float, count=len(rows))

    if reuse_tasks \
            and [pg.uid for pg in jobs] == prev.job_uids \
            and [t.uid for t in tasks] == prev.task_uids \
            and prev.job_task_count.tolist() == (job_count or [0]) \
            and sorted(cluster.queues) == prev.queue_uids:
        # Nothing feeding the task/job/queue families changed: share the
        # previous arrays outright (the uid checks are the cheap
        # defensive proof the candidate sets really match).
        task_req, task_job = prev.task_req, prev.task_job
        task_sel, task_tol = prev.task_selector, prev.task_tolerations
        task_rank = prev.task_rank
        queue_uids = prev.queue_uids
        q_deserved, q_limit = prev.queue_deserved, prev.queue_limit
        q_oqw, q_prio = prev.queue_over_quota_weight, prev.queue_priority
        q_parent, q_creation = prev.queue_parent, prev.queue_creation
        q_alloc, q_req = prev.queue_allocated, prev.queue_requested
        q_usage = prev.queue_usage
        job_q, job_min = prev.job_queue, prev.job_min_available
        job_start_arr = prev.job_task_start
        job_count_arr = prev.job_task_count
        task_uids, job_uids = prev.task_uids, prev.job_uids
    else:
        (task_req, task_job, task_sel, task_tol,
         task_rank) = _pack_task_arrays(tasks, jobs, codec, L, max_tols)
        (queue_uids, q_index, q_deserved, q_limit, q_oqw, q_prio, q_parent,
         q_creation, q_alloc, q_req, q_usage) = _pack_queue_arrays(
            cluster, queue_usage)
        job_q, job_min = _pack_job_arrays(jobs, q_index)
        job_start_arr = np.array(job_start or [0], np.int32)
        job_count_arr = np.array(job_count or [0], np.int32)
        task_uids = [t.uid for t in tasks]
        job_uids = [pg.uid for pg in jobs]

    snap = SnapshotTensors(
        node_allocatable=node_alloc, node_idle=node_idle,
        node_releasing=node_rel, node_labels=prev.node_labels,
        node_taints=prev.node_taints, node_pod_room=node_room,
        task_req=task_req, task_job=task_job, task_selector=task_sel,
        task_tolerations=task_tol, task_rank=task_rank,
        job_queue=job_q, job_min_available=job_min,
        job_task_start=job_start_arr, job_task_count=job_count_arr,
        queue_deserved=q_deserved, queue_limit=q_limit,
        queue_over_quota_weight=q_oqw, queue_priority=q_prio,
        queue_parent=q_parent, queue_creation=q_creation,
        queue_allocated=q_alloc, queue_requested=q_req, queue_usage=q_usage,
        node_names=prev.node_names, task_uids=task_uids,
        job_uids=job_uids, queue_uids=queue_uids,
        codec=codec, pack_epoch=epoch,
    )
    return snap, np.asarray(rows, np.int64)


# -- fragmentation gauges (ROADMAP item 4a) ---------------------------------
#
# Per-cycle fragmentation facts computed from the packed feasibility arrays:
#
#   stranded_resource_total{resource}  idle capacity on real nodes where NO
#                                      pending job's representative task fits
#                                      (selector + taint + pod-room + resource
#                                      mirror of ops/predicates.feasibility_row)
#   largest_placeable_gang             max over pending jobs of how many of
#                                      that job's replicas the cluster could
#                                      place right now (bounded per node by
#                                      resource and pod-room capacity)
#
# The kernel is a numpy mirror of the device-side feasibility predicate; it
# runs once per cycle on the already-packed snapshot, so cost is O(J*N*R)
# with a Python loop only over pending jobs (J <= FRAG_MAX_JOBS).

FRAG_EPS = 1e-9
FRAG_MAX_NODES = 16384
FRAG_MAX_JOBS = 512


def _frag_resource_names(n: int) -> list[str]:
    names = list(rs.RESOURCE_NAMES[:n])
    while len(names) < n:
        names.append(f"res{len(names)}")
    return names


def fragmentation_stats(snap: SnapshotTensors,
                        max_nodes: int = FRAG_MAX_NODES,
                        max_jobs: int = FRAG_MAX_JOBS) -> dict | None:
    """Fragmentation facts for the packed snapshot, or None when skipped.

    Returns ``{"stranded": {resource: amount}, "largest_placeable_gang": int,
    "stranded_nodes": int}``.  Each pending job is represented by its first
    task row (gangs are homogeneous per replica spec), matching the
    device-side predicate semantics.  Oversized snapshots are skipped (with
    ``fragmentation_stats_skipped_total``) rather than risking a multi-second
    numpy pass inside the cycle.
    """
    idle = snap.node_idle
    n_nodes, n_res = idle.shape
    names = _frag_resource_names(n_res)
    pending_jobs = np.nonzero(snap.job_task_count > 0)[0]
    if pending_jobs.size == 0:
        return {"stranded": {nm: 0.0 for nm in names},
                "largest_placeable_gang": 0, "stranded_nodes": 0}
    if n_nodes > max_nodes or pending_jobs.size > max_jobs:
        METRICS.inc("fragmentation_stats_skipped_total")
        return None

    labels = snap.node_labels
    taints = snap.node_taints
    room = snap.node_pod_room
    real = snap.node_allocatable.sum(axis=1) > 0
    floor_room = np.floor(np.maximum(room, 0.0))
    any_fit = np.zeros(n_nodes, dtype=bool)
    largest = 0
    for j in pending_jobs:
        rep = int(snap.job_task_start[j])
        if rep >= snap.task_req.shape[0]:
            continue
        req = snap.task_req[rep]
        sel = snap.task_selector[rep]
        tol = snap.task_tolerations[rep]
        sel_ok = np.all((sel == NO_LABEL) | (sel == labels), axis=1)
        tol_ok = (taints[:, :, None] == tol[None, None, :]).any(axis=2)
        taint_ok = np.all((taints == NO_TAINT) | tol_ok, axis=1)
        fit = (sel_ok & taint_ok & (room >= 1.0)
               & np.all(req[None, :] <= idle + FRAG_EPS, axis=1))
        any_fit |= fit
        if not fit.any():
            continue
        pos = req > FRAG_EPS
        if pos.any():
            cap = np.floor((idle[:, pos] + FRAG_EPS) / req[pos]).min(axis=1)
            cap = np.minimum(cap, floor_room)
        else:
            cap = floor_room
        total = float(np.clip(cap[fit], 0.0, None).sum())
        largest = max(largest, int(min(float(snap.job_task_count[j]), total)))

    stranded_mask = real & ~any_fit
    stranded = {nm: float(np.maximum(idle[stranded_mask, r], 0.0).sum())
                for r, nm in enumerate(names)}
    return {"stranded": stranded,
            "largest_placeable_gang": largest,
            "stranded_nodes": int(stranded_mask.sum())}
