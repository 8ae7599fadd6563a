"""Session: one scheduling cycle's world view + composed extension points.

Mirrors pkg/scheduler/framework/session.go: OpenSession snapshots the
cluster, lets each configured plugin register callbacks, and hands the
composed dispatchers to the actions.  The big departure from the reference:
``OrderedNodesByTask``'s goroutine-per-node scoring loop (session.go:234)
is replaced by the jitted gang-allocation kernel — the session keeps dense
numpy mirrors of node state (single writer: the Statement) and calls the
device kernel to propose placements for whole gangs at once.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..api.cluster_info import ClusterInfo
from ..api.pod_info import PodInfo
from ..api.podgroup_info import PodGroupInfo
from ..api.snapshot import SnapshotTensors, pack, survey_pods
from ..ops.scoring import BINPACK
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from . import propose
from .propose import Proposal
from .statement import Statement


@dataclass
class SchedulableResult:
    schedulable: bool = True
    reason: str = ""
    message: str = ""


class InMemoryCache:
    """Side-effect executor for tests and offline replay — the analog of
    cache.Bind/Evict (pkg/scheduler/cache/cache.go:267, evictor)."""

    # Optional control-plane hooks (same surface as ClusterCache): a
    # crash-safe bind journal and a fencing-epoch provider; statements
    # consult both at commit time.  ``arena`` may be set to a
    # framework.arena.ClusterArena to opt a test/offline session into
    # device residency and a pack fed by ``note_*`` calls.  Left None,
    # a Scheduler's sessions still carry the host half of the pack from
    # one to the next (its own HostArena reads what moved off the
    # NodeInfo stamps); only the device half needs the ClusterArena.
    commitlog = None
    epoch_provider = None
    arena = None

    def __init__(self):
        self.bound = []     # (task_uid, node_name)
        self.evicted = []   # task_uid
        self.events = []    # (kind, message)
        self.pipelined = []  # (task_uid, node_name)

    def task_pipelined(self, task, node_name, gpu_group="") -> None:
        self.pipelined.append((task.uid, node_name))

    def bind(self, task, node_name, bind_request) -> None:
        self.bound.append((task.uid, node_name))

    def evict(self, task) -> None:
        self.evicted.append(task.uid)

    def record_event(self, kind: str, message: str) -> None:
        self.events.append((kind, message))


def _and_masks(fns, tasks) -> "np.ndarray | None":
    """AND of the [T,N] bool contributions of ``fns`` for ``tasks``, None
    where none of them constrains anything."""
    mask = None
    for fn in fns:
        contrib = fn(tasks)
        if contrib is not None:
            mask = contrib if mask is None else (mask & contrib)
    return mask


def _unpack_allocation(result, t: int):
    """(placed [t], piped [t], success [J]) from an AllocationResult.

    When the kernel fused its outputs (result.packed: placements ++
    pipelined ++ job_success, ops/allocate.py), ONE device->host fetch
    serves all three — three separate fetches are three transfers and
    three waits.  The layout is sliced here and nowhere else.  The fallback
    exists for results whose arrays are already host-side (the grouped
    kernels return numpy) or hand-built results in tests.

    ``seam:wait`` is the host waiting for the device (an upload still in
    flight, then the kernel); ``seam:download`` the copy back and the
    slicing."""
    packed = result.packed is not None
    arrays = ((result.packed,) if packed else
              (result.placements[:t], result.pipelined[:t],
               result.job_success))
    with TRACER.span("seam:wait", kind="seam"):
        # This IS the guarded sync: it runs in the `_fetch` thunk, under
        # the guard's watchdog (the retry path calls it on a result the
        # guard materialized already).
        # kailint: disable=KAI002 — inside the guard's watchdog window
        jax.block_until_ready(arrays)
    with TRACER.span("seam:download", kind="seam") as sp:
        host = [np.asarray(a) for a in arrays]
        nbytes = sum(a.nbytes for a in host)
        sp.set(bytes=nbytes)
        METRICS.inc("device_download_bytes", nbytes)
        if not packed:
            return tuple(host)
        flat = host[0]
        tp = result.placements.shape[0]
        return (flat[:t], flat[tp:tp + t].astype(bool),
                flat[2 * tp:].astype(bool))


class Session:
    def __init__(self, cluster: ClusterInfo, config=None, cache=None,
                 queue_usage: dict | None = None, host_arena=None):
        """``host_arena``: the ``framework.arena.HostArena`` of the
        ``Scheduler`` that builds its sessions one after another; it
        serves where the cache brings no arena of its own."""
        from .conf import SchedulerConfig  # local import to avoid cycle
        self.cluster = cluster
        self.config = config or SchedulerConfig()
        self.cache = cache or InMemoryCache()
        # NOT `queue_usage or {}`: an EMPTY usage snapshot can still
        # carry the stale verdict (total scrape outage — the most
        # degraded case), and `or` would replace it with a plain dict,
        # silently dropping the flag the degraded mode keys on.
        self.queue_usage = {} if queue_usage is None else queue_usage
        # --- extension points (session.go:51-95 function slices) ---
        self.queue_order_fns: list[Callable] = []
        self.job_order_fns: list[Callable] = []
        # Key-function mirrors of the comparators: plugins that can express
        # their ordering as a sort key register here too, letting bulk and
        # heap paths sort by precomputed tuples instead of pairwise
        # callbacks.  Register PAIRS via add_job_order_fn — an order fn
        # without a matching key disables key mode for the whole session
        # (job_keys_complete), never silently mis-orders.
        self.job_key_fns: list[Callable] = []
        self.job_keys_complete: bool = True
        self.queue_key_fn: Callable | None = None
        # Contract: registered fns must be pure functions of immutable
        # task identity (name/subgroup/uid).  task_order_key memoizes
        # per uid for the whole session, and chunks sorted by these keys
        # are cached per session (tasks_to_allocate cache_ordered) — a
        # state-dependent ordering fn would be silently frozen at its
        # first evaluation.
        self.task_order_fns: list[Callable] = []
        self._task_order_key_cache: dict = {}
        self.pod_set_order_fns: list[Callable] = []
        self.over_capacity_fns: list[Callable] = []
        self.non_preemptible_over_quota_fns: list[Callable] = []
        self.can_reclaim_fns: list[Callable] = []
        self.reclaim_scenario_validators: list[Callable] = []
        self.preempt_scenario_validators: list[Callable] = []
        self.reclaim_victim_filters: list[Callable] = []
        self.preempt_victim_filters: list[Callable] = []
        # ``fn(job, tasks) -> [R] | None``: the most that relocating
        # running pods can leave free on the nodes the tasks' static
        # constraints admit (the consolidation action's bound).
        self.relocation_bound_fns: list[Callable] = []
        self.allocate_handlers: list[Callable] = []
        self.deallocate_handlers: list[Callable] = []
        self.subset_nodes_fns: list[Callable] = []
        # ``fn(job) -> ops.topology.TopologySession.required_domains``:
        # the domains of a job's required topology level, for the
        # scenario prescreen's domain form (the topology plugin's).
        self.required_domain_fns: list[Callable] = []
        self.extra_score_fns: list[Callable] = []
        # Rank-aware placement (ops/rankplace.py): post-fill permutation
        # of an interchangeable gang chunk's (task, node, piped) pairs so
        # consecutive MPI ranks land topology-adjacent.  Registered by
        # the topology plugin; consulted only on paths that proved the
        # chunk homogeneous (grouped fast path, bulk action).
        self.rank_assign_fns: list[Callable] = []
        # Hard [T,N] feasibility contributions and self-anti-affinity
        # domain rows.  ``static_node_mask_fns``: what depends on node
        # labels and names alone (required node affinity), which no
        # eviction changes; ``hard_node_mask_fns``: what depends on what
        # runs where (host ports, bound PVCs, storage, inter-pod terms).
        self.static_node_mask_fns: list[Callable] = []
        self.hard_node_mask_fns: list[Callable] = []
        self.anti_domain_fns: list[Callable] = []
        self.affinity_domain_fns: list[Callable] = []
        # Cluster-level PreFilters (ConfigMap/MaxNodePoolResources/PVC
        # existence): fail a task before any node scan.
        self.pre_predicate_fns: list[Callable] = []
        self.pre_job_allocation_fns: list[Callable] = []
        self.job_solution_start_fns: list[Callable] = []
        self.gpu_order_fns: list[Callable] = []
        self.plugins = []
        # --- packed snapshot + mutable dense mirrors ---
        pad = None
        bucket = self.config.node_pad_bucket
        if bucket:
            pad = max(bucket, -(-len(cluster.nodes) // bucket) * bucket)
        # A device mesh needs the node axis divisible by its size.
        self.mesh = None
        d = self.config.mesh_devices or 0
        if d > 1:
            from ..parallel import cluster_mesh
            self.mesh = cluster_mesh(d)  # raises when JAX has fewer
            base = pad or max(len(cluster.nodes), 1)
            pad = -(-base // d) * d
        # Persistent arena (framework/arena.py): when the cache carries
        # one (ClusterCache does), the pack is incremental against the
        # previous cycle's arrays and the device tensors stay resident
        # across sessions.  Otherwise the scheduler's host arena carries
        # the host half (packed arrays, node table, name index) from its
        # session before, wherever it can prove the cluster is the one it
        # packed; a session nobody handed either (tests, offline replay)
        # packs from scratch.
        self._arena = getattr(self.cache, "arena", None)
        host = host_arena if self._arena is None else None
        self.pack_stats: dict | None = None
        # Stale usage never reaches the packed tensors: the degraded
        # mode (docs/DEGRADATION.md) is "ignore usage", enforced here
        # for every tensor consumer and by the proportion plugin for
        # the host-side attributes (which also counts the cycle).
        pack_usage = {} if getattr(queue_usage, "stale", False) \
            else queue_usage
        # What a plugin may take over from the session before
        # (docs/DESIGN.md section 8): ``patched_rows`` are the node rows
        # the arena patched, None where it packed from scratch or there is
        # no arena; ``products`` is the arena's place for what plugins
        # derive from rows it proves unchanged, emptied by a full pack and
        # this session's own where nothing outlives it.
        self.patched_rows: np.ndarray | None = None
        self.products: dict = {}
        arena = self._arena or host
        if arena is not None:
            self.snapshot, self.pack_stats = arena.pack(
                cluster, queue_usage=pack_usage, pad_nodes_to=pad)
            self.patched_rows = arena.patched_rows
            self.products = arena.products
        else:
            self.snapshot: SnapshotTensors = pack(
                cluster, queue_usage=pack_usage, pad_nodes_to=pad)
        # ``term_carriers`` (below): the host arena's walk over every pod
        # has just found them; a snapshot builder that proves them says so
        # on the cluster (``ClusterCache``'s columnar build); a session
        # nobody told (None) walks once, when first asked.
        self._term_carriers: list | None = (
            cluster.term_carriers if host is None else host.term_carriers)
        snap = self.snapshot
        table, dirty_rows, node_index = (
            host.carried(snap) if host is not None else (None, None, None))
        # Dense mutable mirrors: backed by the native C++ state store
        # (contiguous C-owned tables, zero-copy views) unless the machine
        # has no compiler to build it with, then plain numpy.  ``/healthz``
        # reports which one a daemon runs on.
        self._native = None
        from ..native import NativeNodeTable, native_available
        if native_available():
            if table is None:
                table = NativeNodeTable(snap.node_allocatable.shape[0],
                                        snap.node_allocatable.shape[1])
                table.bulk_load(
                    snap.node_allocatable,
                    snap.node_allocatable - snap.node_idle,
                    snap.node_releasing, snap.node_pod_room)
                to_bind = cluster.nodes.values()
            else:
                # The table of the session before: its used and
                # releasing rows are what the NodeInfo objects have
                # been writing to since, so only the pod room and the
                # binding of the rows that moved are stale.
                table.room[dirty_rows] = snap.node_pod_room[dirty_rows]
                to_bind = [cluster.nodes[snap.node_names[i]]
                           for i in dirty_rows]
            self._native = table
            # Single source of truth: rebind each NodeInfo's
            # used/releasing to zero-copy VIEWS of its table row.
            # Statement accounting then updates the object graph
            # and the packed kernel inputs in one native write —
            # no per-task copy-back (the dominant host cost at
            # 100k-node scale).  All in-tree mutations are
            # in-place (+=/-=); clone() detaches via .copy().
            used_rows = table.used
            rel_rows = table.releasing
            for node in to_bind:
                i = node.idx
                if 0 <= i < table.n_nodes and \
                        node.used.shape[0] == table.n_res:
                    used_rows[i] = node.used
                    rel_rows[i] = node.releasing
                    node.used = used_rows[i]
                    node.releasing = rel_rows[i]
                    # Whose rows these views are is part of what a
                    # carried table rests on: a session that re-binds
                    # a node says so.
                    node.touch()
        if self._native is None:
            self._np_idle = self.snapshot.node_idle.copy()
            self._np_releasing = self.snapshot.node_releasing.copy()
            self._np_room = self.snapshot.node_pod_room.copy()
        self._node_index = node_index if node_index is not None else {
            n: i for i, n in enumerate(self.snapshot.node_names)}
        self.gpu_strategy = BINPACK
        self.cpu_strategy = BINPACK
        propose.register_declines()
        # At 0 from a session's opening, like the families above: a cycle
        # with no preemptor, or no reclaimer past its gates, reads 0 and
        # not absent (actions/preempt.py, actions/reclaim.py).
        METRICS.inc("preempt_victims_examined_total", 0)
        METRICS.inc("reclaim_victims_examined_total", 0)
        # The scenario prescreen's domain form (actions/solvers.py).
        METRICS.inc("scenario_prescreen_domain_calls_total", 0)
        METRICS.inc("scenario_prescreen_domain_pruned_total", 0)
        METRICS.inc("scenario_prescreen_pool_cells_total", 0)
        # The pods counted anew for the queue sums
        # (``PodGroupInfo.queue_counts``).
        METRICS.inc("queue_aggregate_pod_visits_total", 0)
        # The pods each pass over the whole fleet read its answers off:
        # every PodGroup asked whether it is a stale gang
        # (actions/stalegangeviction.py), the pass that keeps the running
        # preemptible PodGroups for the cycle's first reclaimer,
        # consolidator or preemptor, and ``survey_pods`` at every pack.
        # ``len(pg.pods)`` once a pass for a PodGroup whose pods were
        # walked, 0 for one answered from what it keeps
        # (``PodGroupInfo.uncounted_pods``); one increment a pass, never
        # one a PodGroup (docs/OBSERVABILITY.md).
        for walk in ("stale_gangs", "victim_survey", "pod_survey"):
            METRICS.inc("fleet_walk_pod_visits_total", 0, walk=walk)
        # Whether the topology trees outlived the session before
        # (plugins/topology.py): a session that reuses builds 0, not none.
        METRICS.inc("topology_tree_built_total", 0)
        METRICS.inc("topology_tree_reused_total", 0)
        # Sessions are scheduler-thread-owned end to end: statements
        # mutate mirrors on the cycle path only (commit I/O ships OUT of
        # the session to the executor; it never writes back in).
        # kairace: single-writer=main
        self.mutation_count = 0
        # kairace: single-writer=main
        self.statements: list[Statement] = []
        # Flight-recorder correlation: the cycle's trace id (set by the
        # scheduler); Statement.commit stamps it onto BindRequests so a
        # bind is traceable back to the cycle that produced it.
        self.trace_id: str | None = None
        # Whole-cycle deadline (absolute clock value, set by the
        # scheduler's run_once): past it, every kernel dispatch aborts
        # with CycleDeadlineExceeded instead of starting new device work.
        self.cycle_deadline_at: float | None = None
        # Overlapped pipeline: commit executor for stage-C write batches
        # (framework/pipeline.py), armed per cycle by the scheduler.
        # None = synchronous commits (the serial path).
        self.commit_executor = None
        # Device-array caches.  With an arena, static tensors and mutable
        # state live THERE, resident across sessions, and mutable-row
        # deltas apply by scatter; the session-local dicts below are the
        # fallback for arena-less sessions (full re-upload when any row
        # dirtied, the original behavior).  ``_dirty_rows`` tracks which
        # node rows statements touched since the last device sync — the
        # scatter path ships only those ``[K,R]`` rows.
        self._static_dev: dict = {}
        self._state_dev: dict = {}
        self._dirty_rows: set[int] = set()
        # Releasing-pool hint memo for the fused grouped kernel (see
        # has_releasing): (tick, value), recomputed only after mutations.
        self._rel_hint: tuple[int, bool] | None = None
        if host is not None:
            host.settle(self)

    # -- lifecycle ---------------------------------------------------------
    def open(self) -> "Session":
        from ..plugins import build_plugins
        self.plugins = build_plugins(self.config)
        for plugin in self.plugins:
            with TRACER.span(f"plugin:{plugin.name}", kind="plugin",
                             plugin=plugin.name):
                plugin.on_session_open(self)
        return self

    def close(self) -> None:
        for plugin in self.plugins:
            plugin.on_session_close(self)

    def statement(self) -> Statement:
        st = Statement(self)
        self.statements.append(st)
        return st

    def abort_uncommitted(self) -> int:
        """Roll back every statement that never committed — the cycle
        driver's consistency hook when a device death (or the cycle
        deadline) aborts an action mid-flight: the dense mirrors, object
        graph, and cache must show no phantom allocations."""
        n = 0
        for st in self.statements:
            if not st.committed and st.ops:
                st.discard()
                n += 1
        return n

    # -- guarded device dispatch ------------------------------------------
    def dispatch_kernel(self, thunk, label: str, validate=None,
                        blocking: bool = True):
        """Route one device-kernel dispatch through the device guard:
        watchdog deadline, retry, circuit breaker, CPU degradation
        (utils/deviceguard.py).  All session/solver kernel call sites go
        through here so fault handling is uniform and the whole-cycle
        deadline is enforced at dispatch granularity.  Each dispatch is a
        flight-recorder span carrying the guard's verdict (device vs
        CPU-fallback, breaker state) for post-mortem triage.  The guard
        runs the thunk on its worker thread and knows nothing of tracing:
        the trace is handed across here, so spans the thunk opens
        (``seam:*``) are children of this dispatch's span.

        ``blocking=False`` is the pipelined mode: the dispatch returns at
        ENQUEUE time without forcing device completion, so the caller can
        overlap host work (or further enqueues) with device execution and
        synchronize once, at its own guarded fetch — one device round
        trip instead of a completion wait plus a transfer.  ``validate``
        then sees lazy arrays (metadata checks only)."""
        from ..utils.deviceguard import device_guard
        guard = device_guard()
        with TRACER.span(f"dispatch:{label}", kind="kernel",
                         kernel=label, pipelined=not blocking) as sp, \
                TRACER.hand_off() as seam:
            fb0, to0 = guard.fallback_calls, guard.timeouts
            try:
                return guard.call(
                    seam.adopting(thunk), label=label, validate=validate,
                    record_event=getattr(self.cache, "record_event", None),
                    cycle_deadline_at=self.cycle_deadline_at,
                    materialize=blocking)
            finally:
                sp.set(fallback=guard.fallback_calls > fb0,
                       timed_out=guard.timeouts > to0,
                       breaker=guard.breaker.state)

    def _dispatch_and_fetch(self, thunk, label: str, validate, t: int):
        """Pipelined allocation dispatch: enqueue the kernel without
        blocking, then pay ONE guarded wait for the fused ``packed``
        fetch (placements ++ pipelined ++ job_success).  The blocking
        path waits twice — for completion inside the dispatch, then for
        the transfer at unpack.

        An asynchronous device failure surfaces at the fetch; the repair
        path re-runs the whole kernel through a blocking dispatch, where
        the guard's breaker/CPU-fallback machinery takes over — so fault
        coverage is identical to the blocking path, just deferred."""
        from ..utils.deviceguard import (CycleDeadlineExceeded,
                                        DeviceGuardError)
        result = self.dispatch_kernel(thunk, label=label, validate=validate,
                                      blocking=False)
        try:
            return self.dispatch_kernel(
                lambda: _unpack_allocation(result, t),
                label=f"{label}_fetch",
                validate=lambda r: getattr(r[0], "shape", (0,))[0] == t)
        except CycleDeadlineExceeded:
            raise
        except DeviceGuardError:
            # The enqueue's lazy result is poisoned (the failure happened
            # after enqueue, so the first dispatch never saw it): re-run
            # end to end, blocking, letting the guard degrade if needed.
            result = self.dispatch_kernel(thunk, label=f"{label}_retry",
                                          validate=validate)
            return _unpack_allocation(result, t)

    # -- dense mirrors (single writer: the Statement via sync_node) --------
    @property
    def node_idle(self) -> np.ndarray:
        if self._native is not None:
            return self._native.idle
        return self._np_idle

    @property
    def node_releasing(self) -> np.ndarray:
        if self._native is not None:
            return self._native.releasing
        return self._np_releasing

    @property
    def node_room(self) -> np.ndarray:
        if self._native is not None:
            return self._native.room
        return self._np_room

    @property
    def node_store(self) -> str:
        """Backing of the dense node mirrors: ``native`` (the C++ state
        store) or ``numpy`` (no compiler on this machine)."""
        return "native" if self._native is not None else "numpy"

    def has_releasing(self) -> bool:
        """Host-verified hint: does ANY node row carry releasing
        capacity?  Feeds the fused grouped kernel's no-releasing
        specialization (ops/allocate_grouped) straight from the host
        mirrors — the resident device copy is never fetched for a hint.
        Memoized on the mutation tick: statements that pipeline/evict
        bump it, so the memo can never serve a stale False."""
        if self._rel_hint is None or self._rel_hint[0] != self.mutation_count:
            self._rel_hint = (self.mutation_count,
                              bool(self.node_releasing.any()))
        return self._rel_hint[1]

    def sync_node(self, node) -> None:
        # Monotonic mutation tick: plugins key their cluster-scan caches
        # (active pods, occupied host ports) on it so repeated per-task
        # mask computations don't rescan an unchanged cluster.
        self.mutation_count += 1
        i = node.idx
        if i < 0:
            return
        if self._native is not None:
            if i < self._native.n_nodes:
                self._native.used[i] = node.used
                self._native.releasing[i] = node.releasing
                self._native.room[i] = max(
                    0, node.max_pods - len(node.pod_infos))
                self._dirty_rows.add(i)
        elif i < self._np_idle.shape[0]:
            self._np_idle[i] = node.idle
            self._np_releasing[i] = node.releasing
            self._np_room[i] = max(0, node.max_pods - len(node.pod_infos))
            self._dirty_rows.add(i)

    def _device_arrays(self):
        """(allocatable, idle, releasing, labels, taints, room) as device
        arrays.  With an arena: served from the cross-session resident
        cache, dirty rows applied by guarded scatter.  Without: static
        arrays upload once per session and mutable state re-uploads in
        full when any row dirtied (the original behavior).  Callers run
        this on the cycle thread, OUTSIDE dispatch thunks, so the arena's
        own guarded dispatches never nest inside another guarded call."""
        snap = self.snapshot
        if self._arena is not None:
            return self._arena.device_arrays(snap, self)
        if not self._static_dev:
            self._static_dev = {
                "alloc": jnp.asarray(snap.node_allocatable),
                "labels": jnp.asarray(snap.node_labels),
                "taints": jnp.asarray(snap.node_taints),
            }
        if self._dirty_rows or not self._state_dev:
            self._state_dev = {
                "idle": jnp.asarray(self.node_idle),
                "rel": jnp.asarray(self.node_releasing),
                "room": jnp.asarray(self.node_room),
            }
            self._dirty_rows.clear()
        s, st = self._static_dev, self._state_dev
        return (s["alloc"], st["idle"], st["rel"], s["labels"], s["taints"],
                st["room"])

    # -- composed dispatchers (session_plugins.go:117-300) -----------------
    def compare_queues(self, l, r, l_job=None, r_job=None,
                       l_victims=None, r_victims=None) -> int:
        for fn in self.queue_order_fns:
            res = fn(l, r, l_job, r_job, l_victims, r_victims)
            if res != 0:
                return res
        return 0

    def add_job_order_fn(self, order_fn: Callable,
                         key_fn: Callable | None = None) -> None:
        """Register a job comparator with (optionally) its sort-key
        mirror.  Key-based ordering stays enabled only while every
        registered comparator has a paired key."""
        self.job_order_fns.append(order_fn)
        if key_fn is None:
            self.job_keys_complete = False
        else:
            self.job_key_fns.append(key_fn)

    def job_sort_key(self, job: PodGroupInfo):
        return tuple(fn(job) for fn in self.job_key_fns) + (
            job.creation_ts, job.uid)

    def compare_jobs(self, l: PodGroupInfo, r: PodGroupInfo) -> int:
        for fn in self.job_order_fns:
            res = fn(l, r)
            if res != 0:
                return res
        if l.creation_ts != r.creation_ts:
            return -1 if l.creation_ts < r.creation_ts else 1
        return -1 if l.uid < r.uid else (1 if l.uid > r.uid else 0)

    def task_order_key(self, task: PodInfo):
        # Memoized per session: the registered fns are fixed once the
        # session opens and the key depends only on immutable task
        # identity — while one allocation cycle can sort the same task
        # many times (eligibility split, per-round gating, fit errors).
        cache = self._task_order_key_cache
        key = cache.get(task.uid)
        if key is None:
            key = tuple(fn(task) for fn in self.task_order_fns) + (
                task.name, task.uid)
            cache[task.uid] = key
        return key

    def pod_set_order_key(self, ps):
        return tuple(fn(ps) for fn in self.pod_set_order_fns) + (ps.name,)

    def is_job_over_queue_capacity(self, job, tasks) -> SchedulableResult:
        for fn in self.over_capacity_fns:
            res = fn(job, tasks)
            if not res.schedulable:
                return res
        return SchedulableResult()

    @property
    def term_carriers(self) -> list:
        """The pods of the cluster that carry an inter-pod term (required
        or preferred, affinity or anti-affinity; any status, any node), as
        the snapshot layer found them for this session (docs/DESIGN.md
        section 8).  Terms do not change inside a session; status and node
        do, so a reader filters at the call."""
        if self._term_carriers is None:
            self._term_carriers = survey_pods(self.cluster)[1]
        return self._term_carriers

    def compute_hard_mask(self, tasks) -> "np.ndarray | None":
        """AND of every static and state-dependent hard-mask
        contribution: [T,N] bool or None when unconstrained.  Host-side
        allocation paths (fractional, MIG, DRA) consult this too — the
        kernel and host paths must agree on feasibility."""
        return _and_masks(
            self.static_node_mask_fns + self.hard_node_mask_fns, tasks)

    def compute_static_mask(self, tasks) -> "np.ndarray | None":
        """The part of ``compute_hard_mask`` that node labels and names
        alone decide: the same [T,N] before and after any eviction."""
        return _and_masks(self.static_node_mask_fns, tasks)

    def compute_state_mask(self, tasks) -> "np.ndarray | None":
        """The part of ``compute_hard_mask`` that depends on what runs
        where, which an eviction may relax."""
        return _and_masks(self.hard_node_mask_fns, tasks)

    def check_pre_predicates(self, tasks) -> SchedulableResult:
        """Run cluster-level PreFilter predicates over a job's tasks
        (PrePredicateFn per task, predicates.go PreFilter chain)."""
        for fn in self.pre_predicate_fns:
            for task in tasks:
                res = fn(task)
                if not res.schedulable:
                    return res
        return SchedulableResult()

    def is_non_preemptible_over_quota(self, job, tasks) -> SchedulableResult:
        for fn in self.non_preemptible_over_quota_fns:
            res = fn(job, tasks)
            if not res.schedulable:
                return res
        return SchedulableResult()

    def can_reclaim_resources(self, job) -> bool:
        return all(fn(job) for fn in self.can_reclaim_fns)

    def validate_reclaim_scenario(self, scenario) -> bool:
        return all(fn(scenario) for fn in self.reclaim_scenario_validators)

    def validate_preempt_scenario(self, scenario) -> bool:
        return all(fn(scenario) for fn in self.preempt_scenario_validators)

    def filter_reclaim_victims(self, reclaimer, victims) -> list:
        """The victims every registered filter admits for ``reclaimer``.

        ``reclaim_victim_filters`` carry the contract of
        ``preempt_victim_filters`` (``filter_preempt_victims``): a filter
        takes ``(reclaimer, victims)`` and returns the victims it admits
        IN THE ORDER IT WAS GIVEN THEM, EACH JUDGED ALONE, by what the
        reclaimer and that one victim are and by nothing else of the
        list.  So ``filter(a + b) == filter(a) + filter(b)``, and the
        reclaim action filters its victim stream from the head in chunks
        until the solver has as many as it reads
        (``actions/reclaim.py`` ``VictimStream.candidates``), never the
        whole survey.  A filter may hand back the list it was given when
        it drops nothing; nobody writes to either."""
        for fn in self.reclaim_victim_filters:
            victims = fn(reclaimer, victims)
        return victims

    def relocation_can_seat(self, job, tasks, total_req) -> bool:
        """False where some registered bound shows that no relocation can
        free ``total_req`` [R] on the nodes ``tasks`` may use."""
        for fn in self.relocation_bound_fns:
            bound = fn(job, tasks)
            if bound is not None and np.any(total_req > bound + 1e-9):
                return False
        return True

    def filter_preempt_victims(self, preemptor, victims) -> list:
        """The victims every registered filter admits for ``preemptor``.

        The contract of ``preempt_victim_filters``: a filter takes
        ``(preemptor, victims)`` and returns the victims it admits IN THE
        ORDER IT WAS GIVEN THEM, EACH JUDGED ALONE, by what the preemptor
        and that one victim are and by nothing else of the list
        (upstream builds one predicate a preemptor and asks it job by
        job, preempt.go:126-155).  So ``filter(a + b) == filter(a) +
        filter(b)``, and the preempt action filters its ordered victims
        from the head in chunks until the solver has as many as it reads,
        never the whole list.  A filter may hand back the list it was
        given when it drops nothing; nobody writes to either."""
        for fn in self.preempt_victim_filters:
            victims = fn(preemptor, victims)
        return victims

    def fire_allocate_handlers(self, task: PodInfo) -> None:
        for fn in self.allocate_handlers:
            fn(task)

    def fire_deallocate_handlers(self, task: PodInfo,
                                 prev_status) -> None:
        for fn in self.deallocate_handlers:
            fn(task, prev_status)

    def pre_job_allocation(self, job: PodGroupInfo) -> None:
        for fn in self.pre_job_allocation_fns:
            fn(job)

    def on_job_solution_start(self) -> None:
        """Scenario solvers call this before simulating: plugins snapshot
        any state the validators must read pre-simulation
        (proportion.OnJobSolutionStartFn, proportion.go:131)."""
        for fn in self.job_solution_start_fns:
            fn()

    def subset_nodes(self, job, tasks, podset=None) -> list:
        """Topology plugin hook: ordered list of candidate node-index sets
        (None = all nodes).  Mirrors ssn.SubsetNodesFn; ``podset`` scopes
        the constraint to one subgroup (allocateSubGroupSet recursion)."""
        if not self.subset_nodes_fns:
            return [None]
        with TRACER.span("topology:subset_nodes", kind="topology") as sp:
            for fn in self.subset_nodes_fns:
                sets = fn(job, tasks, podset)
                if sets is not None:
                    break
            else:
                sets = [None]
            first = sets[0] if sets else None
            sp.set(sets=len(sets),
                   nodes_in_first=(self.node_idle.shape[0] if first is None
                                   else int(np.count_nonzero(first))))
        return sets

    def apply_rank_placement(self, tasks, placements):
        """Rank-aware reorder of one gang chunk's placements: the first
        registered fn that returns a permuted list wins; None keeps the
        rank-oblivious assignment.  Callers must only pass chunks whose
        tasks are interchangeable under the placement (the registered
        fns re-verify before permuting)."""
        if not getattr(self.config, "rank_aware_placement", True):
            return placements
        for fn in self.rank_assign_fns:
            out = fn(tasks, placements)
            if out is not None:
                return out
        return placements

    # -- device-kernel placement proposals ---------------------------------
    def _sum_extra_scores(self, tasks) -> "np.ndarray | None":
        """Sum of every registered extra-score fn over ONE job chunk, in
        registration order and in the host's float, each call (not the
        sum) under a span of its own.

        A fn returns None, one [N] row (it holds for every task of the
        chunk) or [T,N] (rows that differ by task).  The sum keeps the
        cheapest form that holds what it was given: None, [N], or
        [len(tasks), N] once any fn returned [T,N] (rows broadcast into
        it, so the values are those a [T,N] sum gives).  Nothing [T,N] is
        made that no fn asked for."""
        acc = None
        for fn in self.extra_score_fns:
            # Named as the plugin's ``plugin:<name>`` span is; a bare
            # function (tests) has no plugin to name.
            plugin = getattr(getattr(fn, "__self__", None), "name", "fn")
            with TRACER.span(f"extra_scores:{plugin}",
                             kind="topology") as sp:
                contrib = fn(tasks)
                sp.set(bytes=getattr(contrib, "nbytes", 0))
            if contrib is None:
                continue
            contrib = np.asarray(contrib)
            if acc is None:
                # A copy: a plugin may hand over a row it keeps.
                acc = contrib.astype(np.float64)
            elif acc.ndim >= contrib.ndim:
                acc += contrib
            else:
                acc = acc + contrib
        return acc

    def propose_placements_multi(self, job_chunks,
                                 pipeline_only: bool = True,
                                 node_subset=None):
        """Place SEVERAL jobs' chunks in ONE kernel call (the scenario
        confirm pass: pending job + victim re-placements together instead
        of one device round trip per job).

        ``job_chunks``: [(job, tasks)]; a job may bring several chunks in
        a row (a victim placed again pod for pod), each tried only where
        the one before it succeeded.  Returns a ``Proposal`` a chunk, in
        the chunks' order, with per-chunk gang atomicity (the kernel's
        per-job success gating), or None when any chunk needs per-job
        machinery the concatenated call cannot express (domain rows from
        anti/affinity plugins) or holds a task that cannot be encoded.
        ``node_subset`` holds the FIRST chunk alone (a topology domain
        for the pending job)."""
        return propose.propose(self, job_chunks, "multi",
                               pipeline_only=pipeline_only,
                               node_subset=node_subset)

    def propose_placements(self, tasks: list[PodInfo],
                           pipeline_only: bool = False,
                           allow_pipeline: bool = True,
                           node_subset: np.ndarray | None = None
                           ) -> Proposal:
        """Run the gang-allocation kernel for one job's task chunk against
        the current (statement-mutated) node state: the one-chunk call of
        ``framework/propose.py``."""
        out = propose.propose(self, [(None, tasks)], "single",
                              pipeline_only=pipeline_only,
                              allow_pipeline=allow_pipeline,
                              node_subset=node_subset)
        return Proposal(False, []) if out is None else out[0]

    def _task_row(self, task: PodInfo):
        """(req [R], selector [L], tolerations [Tl]) for any task: packed
        rows for this cycle's candidates, codec re-encoding for others
        (evicted victims in scenario simulation)."""
        snap = self.snapshot
        i = snap.row_of(task)
        if i >= 0:
            return (snap.task_req[i], snap.task_selector[i],
                    snap.task_tolerations[i])
        codec = snap.codec
        sel = np.full(snap.task_selector.shape[1], -1, np.int32)
        for k, v in task.node_selector.items():
            col = codec.key_cols.get(k) if codec else None
            if col is None:
                return None, None, None
            # A value no node carries can never match: poison code -2.
            sel[col] = codec.value_codes.get((k, v), -2)
        tol = np.full(snap.task_tolerations.shape[1], -1, np.int32)
        j = 0
        for t in sorted(task.tolerations):
            code = codec.taint_codes.get(t) if codec else None
            if code is not None and j < tol.shape[0]:
                tol[j] = code
                j += 1
        return task.res_req.to_vec(mig_as_gpu=False), sel, tol

    def score_nodes_for_task(self, task: PodInfo) -> np.ndarray:
        """[N] score row for host-side paths (fractional GPU placement)."""
        return propose.score_nodes(self, task)

    def node_index(self, name: str) -> int:
        return self._node_index.get(name, -1)
