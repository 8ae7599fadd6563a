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
from ..api.snapshot import SnapshotTensors, pack
from ..ops.allocate import allocate_jobs_kernel
from ..ops.scoring import BINPACK
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from .statement import Statement


@dataclass
class SchedulableResult:
    schedulable: bool = True
    reason: str = ""
    message: str = ""


@dataclass
class Proposal:
    """A gang placement proposal from the device kernel."""
    success: bool
    placements: list  # [(task, node_name, pipelined)]


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


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pad_rows(rows, t_pad: int, fill):
    """A per-task [t, N] operand padded to the kernel's [t_pad, N] with
    ``fill`` rows for the padding tasks; None stays None."""
    if rows is None or rows.shape[0] == t_pad:
        return rows
    out = np.full((t_pad,) + rows.shape[1:], fill, rows.dtype)
    out[:rows.shape[0]] = rows
    return out


def _note_operand_forms(span, extras: str, mask: str) -> None:
    """Say on ``propose:operands``, and count, which form the score and
    hard-mask operands of this dispatch took: ``none`` (no operand),
    ``row`` (one [N] row a job) or ``dense`` ([T,N])."""
    span.set(extras=extras, mask=mask)
    METRICS.inc("propose_operand_form_total", extras=extras, mask=mask)


def _allocation_shape_check(t_pad: int):
    """Device-guard validator for allocation results: the task axis must
    match what was dispatched (a truncated/garbled device answer — the
    ``badshape`` fault class — must read as a device failure, never be
    silently unpacked)."""
    def ok(result) -> bool:
        try:
            if result.placements.shape[0] < t_pad:
                return False
            packed = getattr(result, "packed", None)
            if packed is not None and \
                    packed.shape[0] != 2 * result.placements.shape[0] \
                    + result.job_success.shape[0]:
                # packed is placements ++ pipelined ++ job_success
                # ([T + T + J], ops/allocate.py AllocationResult).
                return False
            return True
        except Exception:
            return False
    return ok


def _stage(*operands):
    """The host operands of an exact-kernel call as device arrays, in the
    order given (None stays None, a tuple of arrays comes back a tuple).

    Runs inside the dispatch thunk, on the guard's worker: ``jnp.asarray``
    converts a host array whose dtype the regime narrows (f64 to f32
    without x64) on the host, then enqueues the upload.  The one place
    host operands cross to the device, so the bytes are counted here:
    ``device_upload_bytes`` (device side, every operand) and
    ``host_convert_bytes`` (host side, the operands whose dtype changed)."""
    with TRACER.span("seam:stage", kind="seam") as sp:
        host = device = converted = count = 0

        def put(a):
            nonlocal host, device, converted, count
            if isinstance(a, jax.Array):
                return a
            out = jnp.asarray(a)
            a = np.asarray(a)
            count += 1
            host += a.nbytes
            device += out.nbytes
            if out.dtype != a.dtype:
                converted += a.nbytes
            return out

        staged = jax.tree_util.tree_map(put, operands)
        sp.set(bytes_host=host, bytes_device=device,
               bytes_converted=converted, operands=count)
    METRICS.inc("device_upload_bytes", device)
    METRICS.inc("host_convert_bytes", converted)
    return staged


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
        self.allocate_handlers: list[Callable] = []
        self.deallocate_handlers: list[Callable] = []
        self.subset_nodes_fns: list[Callable] = []
        self.extra_score_fns: list[Callable] = []
        # Rank-aware placement (ops/rankplace.py): post-fill permutation
        # of an interchangeable gang chunk's (task, node, piped) pairs so
        # consecutive MPI ranks land topology-adjacent.  Registered by
        # the topology plugin; consulted only on paths that proved the
        # chunk homogeneous (grouped fast path, bulk action).
        self.rank_assign_fns: list[Callable] = []
        # Hard [T,N] feasibility contributions (podaffinity terms,
        # upstream predicates) and self-anti-affinity domain rows.
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
        # Per-phase cycle timing (the e2e_scheduling_latency breakdown the
        # reference gets from per-plugin/action histograms,
        # metrics/metrics.go:65): filled here and by open()/run_once.
        import time as _time
        self.phase_timings: dict[str, float] = {}
        _t = _time.perf_counter()
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
        if self._arena is not None or host is not None:
            self.snapshot, self.pack_stats = (self._arena or host).pack(
                cluster, queue_usage=pack_usage, pad_nodes_to=pad)
        else:
            self.snapshot: SnapshotTensors = pack(
                cluster, queue_usage=pack_usage, pad_nodes_to=pad)
        self.phase_timings["snapshot_pack"] = _time.perf_counter() - _t
        snap = self.snapshot
        table, dirty_rows, node_index = (
            host.carried(snap) if host is not None else (None, None, None))
        # Dense mutable mirrors: backed by the native C++ state store
        # (contiguous C-owned tables, zero-copy views) unless the machine
        # has no compiler to build it with, then plain numpy.  ``/healthz``
        # reports which one a daemon runs on.
        self._native = None
        if self.config.use_native_store:
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
        import time as _time

        from ..plugins import build_plugins
        t0 = _time.perf_counter()
        self.plugins = build_plugins(self.config)
        for plugin in self.plugins:
            t = _time.perf_counter()
            with TRACER.span(f"plugin:{plugin.name}", kind="plugin",
                             plugin=plugin.name):
                plugin.on_session_open(self)
            dt = _time.perf_counter() - t
            if dt >= 0.005:  # only phases that matter in the breakdown
                self.phase_timings[f"plugin_{plugin.name}"] = \
                    self.phase_timings.get(f"plugin_{plugin.name}", 0.0) + dt
        self.phase_timings["plugins_open"] = _time.perf_counter() - t0
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

    def compute_hard_mask(self, tasks) -> "np.ndarray | None":
        """AND of every hard_node_mask_fns contribution: [T,N] bool or
        None when unconstrained.  Host-side allocation paths (fractional,
        MIG, DRA) consult this too — the kernel and host paths must agree
        on feasibility."""
        mask = None
        for fn in self.hard_node_mask_fns:
            contrib = fn(tasks)
            if contrib is not None:
                mask = contrib if mask is None else (mask & contrib)
        return mask

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
        for fn in self.reclaim_victim_filters:
            victims = fn(reclaimer, victims)
        return victims

    def filter_preempt_victims(self, preemptor, victims) -> list:
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
                                 pipeline_only: bool = True):
        """Place SEVERAL jobs' chunks in ONE kernel call (the scenario
        confirm pass: pending job + victim re-placements together instead
        of one device round trip per job).

        ``job_chunks``: [(job, tasks)].  Returns {job_uid: Proposal} with
        per-job gang atomicity (the kernel's per-job success gating), or
        None when any chunk needs per-job machinery the concatenated call
        cannot express (domain rows from anti/affinity plugins)."""
        METRICS.inc("device_kernel_calls")
        snap = self.snapshot
        all_tasks = [t for _job, tasks in job_chunks for t in tasks]
        t = len(all_tasks)
        if t == 0:
            return {}
        n_nodes = self.node_idle.shape[0]
        t_pad = _next_pow2(t)
        with TRACER.span("propose:operands", kind="propose", t=t,
                         t_pad=t_pad, nodes=n_nodes,
                         path="multi") as operands_span:
            for fn in self.anti_domain_fns + self.affinity_domain_fns:
                if fn(all_tasks) is not None:
                    return None

            task_req = np.zeros((t_pad, snap.task_req.shape[1]))
            task_sel = np.full((t_pad, snap.task_selector.shape[1]), -1,
                               np.int32)
            task_tol = np.full((t_pad, snap.task_tolerations.shape[1]), -1,
                               np.int32)
            task_job = np.full(t_pad, len(job_chunks), np.int32)  # padding
            # Bucket the job axis too (KJT001): [J+1] exact would retrace
            # the allocate kernel per distinct live gang count.  Padding
            # jobs are gated out (allowed=False) and own only padding
            # tasks, so nothing the kernel reads of them is used;
            # consumers index success[j] for real jobs only.
            j_pad = _next_pow2(len(job_chunks) + 1)
            job_allowed = np.ones(j_pad, bool)
            job_allowed[len(job_chunks):] = False
            # Each chunk's extra scores are its own job's: a row goes to
            # the job's row, per-task rows to the chunk's task rows.
            job_extra = task_extra = None
            row = 0
            for j, (_job, tasks) in enumerate(job_chunks):
                extra = self._sum_extra_scores(tasks)
                if extra is not None and extra.ndim == 1:
                    if job_extra is None:
                        job_extra = np.zeros((j_pad, n_nodes))
                    job_extra[j] = extra
                elif extra is not None:
                    if task_extra is None:
                        task_extra = np.zeros((t_pad, n_nodes))
                    task_extra[row:row + len(tasks)] = extra
                for task in tasks:
                    req, sel, tol = self._task_row(task)
                    if req is None:
                        return None
                    task_req[row], task_sel[row, :len(sel)] = req, sel
                    task_tol[row, :len(tol)] = tol
                    task_job[row] = j
                    row += 1

            mask_pad = _pad_rows(self.compute_hard_mask(all_tasks), t_pad,
                                 True)
            _note_operand_forms(
                operands_span,
                extras="dense" if task_extra is not None else
                "row" if job_extra is not None else "none",
                mask="none" if mask_pad is None else "dense")

        node_arrays = self._device_arrays()

        def thunk():
            (d_req, d_job, d_sel, d_tol, d_allowed, d_extra, d_mask,
             d_job_extra) = _stage(task_req, task_job, task_sel, task_tol,
                                   job_allowed, task_extra, mask_pad,
                                   job_extra)
            with TRACER.span("seam:launch", kind="seam",
                             kernel="allocate_jobs_multi"):
                return allocate_jobs_kernel(
                    *node_arrays, d_req, d_job, d_sel, d_tol, d_allowed,
                    d_extra, task_node_mask=d_mask,
                    job_extra_scores=d_job_extra,
                    gpu_strategy=self.gpu_strategy,
                    cpu_strategy=self.cpu_strategy,
                    allow_pipeline=True, pipeline_only=pipeline_only)

        placed, piped, success = self._dispatch_and_fetch(
            thunk, label="allocate_jobs_multi",
            validate=_allocation_shape_check(t_pad), t=t)
        with TRACER.span("propose:unpack", kind="propose", t=t):
            out = {}
            row = 0
            for j, (job, tasks) in enumerate(job_chunks):
                rows = range(row, row + len(tasks))
                row += len(tasks)
                if not bool(success[j]) or any(placed[r] < 0
                                               for r in rows):
                    out[job.uid] = Proposal(False, [])
                    continue
                out[job.uid] = Proposal(True, [
                    (task, snap.node_names[int(placed[r])], bool(piped[r]))
                    for task, r in zip(tasks, rows)])
        return out

    def propose_placements(self, tasks: list[PodInfo],
                           pipeline_only: bool = False,
                           allow_pipeline: bool = True,
                           node_subset: np.ndarray | None = None
                           ) -> Proposal:
        """Run the gang-allocation kernel for one job's task chunk against
        the current (statement-mutated) node state."""
        METRICS.inc("device_kernel_calls")
        snap = self.snapshot
        t = len(tasks)
        t_pad = _next_pow2(max(t, 1))
        n_nodes = self.node_idle.shape[0]

        with TRACER.span("propose:operands", kind="propose", t=t,
                         t_pad=t_pad, nodes=n_nodes) as operands_span:
            task_req = np.zeros((t_pad, snap.task_req.shape[1]))
            task_sel = np.full((t_pad, snap.task_selector.shape[1]), -1,
                               np.int32)
            task_tol = np.full((t_pad, snap.task_tolerations.shape[1]), -1,
                               np.int32)
            for i, task in enumerate(tasks):
                req, sel, tol = self._task_row(task)
                if req is None:
                    return Proposal(False, [])
                task_req[i], task_sel[i, :len(sel)] = req, sel
                task_tol[i, :len(tol)] = tol
            task_job = np.zeros(t_pad, np.int32)
            task_job[t:] = 1  # padding rows: a gated-out dummy job
            job_allowed = np.array([True, False])

            # None, one [N] row for the whole chunk, or [t, N].
            extra = self._sum_extra_scores(tasks)

            # Hard per-task node masks (inter-pod affinity terms, upstream
            # predicate verdicts): False = infeasible, enforced in-kernel.
            mask = self.compute_hard_mask(tasks)
            # The topology node subset is a hard mask too (matching the
            # fractional/MIG handlers, which skip out-of-subset nodes
            # unconditionally): an out-of-subset node is infeasible, not
            # a soft last resort.  It is the job's row, ANDed with the
            # per-task mask wherever both exist, on every path.
            subset = (None if node_subset is None
                      else np.asarray(node_subset, bool))
            _note_operand_forms(
                operands_span,
                extras="none" if extra is None else
                "row" if extra.ndim == 1 else "dense",
                mask="dense" if mask is not None else
                "none" if subset is None else "row")
            # Self-anti-affinity domain rows (spread-one-per-domain gangs).
            anti_dom = None
            for fn in self.anti_domain_fns:
                contrib = fn(tasks)
                if contrib is not None:
                    anti_dom = contrib
                    break
            # In-gang required-affinity domain rows (co-locate gangs).
            aff_dom = None
            for fn in self.affinity_domain_fns:
                contrib = fn(tasks)
                if contrib is not None:
                    aff_dom = contrib
                    break

            # Homogeneous chunks take the grouped fill-plan kernel: one
            # scan step instead of one per task.  Extra score terms and
            # hard masks ride along when per-job uniform (one [N] row for
            # the whole chunk) — extras must be tier constants (multiples
            # of 10) for the fill plan's ordering invariance
            # (allocate_groups_kernel); a node subset becomes a hard mask
            # row.
            homogeneous = (
                t > 1 and anti_dom is None and aff_dom is None
                and self.gpu_strategy == BINPACK
                and self.cpu_strategy == BINPACK
                and (task_req[1:t] == task_req[0]).all()
                and (task_sel[1:t] == task_sel[0]).all()
                and (task_tol[1:t] == task_tol[0]).all())
            row_extra = row_mask = None
            if homogeneous and extra is not None and extra.any():
                row = extra if extra.ndim == 1 else extra[0]
                if (extra.ndim == 1 or (extra[1:] == row).all()) and bool(
                        np.all(np.remainder(row, 10.0) == 0.0)):
                    row_extra = row[None, :]
                else:
                    homogeneous = False
            if homogeneous and mask is not None:
                if (mask[1:] == mask[0]).all():
                    row_mask = (mask[:1] if subset is None
                                else mask[:1] & subset)
                else:
                    homogeneous = False
            elif homogeneous and subset is not None:
                row_mask = subset[None, :]
            # Multi-chip exact kernel (parallel/sharded.py): node axis
            # sharded over the mesh, bit-identical tie-breaks.  Domain
            # rows, extra score terms, and pipeline-only proposals stay
            # on the single-chip kernel (unsupported under shard_map).
            sharded = (not homogeneous and self.mesh is not None
                       and anti_dom is None and aff_dom is None
                       and not pipeline_only and extra is None)
            operands_span.set(path="grouped" if homogeneous else
                              "sharded" if sharded else "exact")
            if not homogeneous:
                dom_pad = aff_pad = None
                # A job's rows are [2,N]: row 1 is the padding job's,
                # read by the padding tasks and never used.
                job_extra = task_extra = job_mask = None
                if extra is not None and extra.ndim == 1:
                    job_extra = np.zeros((2, n_nodes))
                    job_extra[0] = extra
                else:
                    task_extra = _pad_rows(extra, t_pad, 0.0)
                if subset is not None and sharded:
                    # The sharded kernel takes no job rows: its [T,N]
                    # mask is built here, for this path alone.
                    mask = (np.broadcast_to(subset, (t, n_nodes))
                            if mask is None else mask & subset)
                elif subset is not None:
                    job_mask = np.ones((2, n_nodes), bool)
                    job_mask[0] = subset
                mask_pad = _pad_rows(mask, t_pad, True)
                if anti_dom is not None:
                    doms, marks, avoids = anti_dom
                    d = np.full((t_pad, n_nodes), -1, np.int32)
                    d[:t] = doms
                    m = np.zeros(t_pad, bool)
                    m[:t] = marks
                    a = np.zeros(t_pad, bool)
                    a[:t] = avoids
                    dom_pad = (d, m, a)
                if aff_dom is not None:
                    doms, marks, avoids, static_ok, boot = aff_dom
                    d = np.full((t_pad, n_nodes), -1, np.int32)
                    d[:t] = doms
                    m = np.zeros(t_pad, bool)
                    m[:t] = marks
                    a = np.zeros(t_pad, bool)
                    a[:t] = avoids
                    st = np.ones((t_pad, n_nodes), bool)
                    st[:t] = static_ok
                    b = np.zeros(t_pad, bool)
                    b[:t] = boot
                    aff_pad = (d, m, a, st, b)

        node_arrays = self._device_arrays()
        if homogeneous:
            from ..ops import allocate_grouped as ag
            # The span helper stamps the guard verdict on the cycle
            # thread; the wrapper stamps the rung it resolved.
            with ag.fused_dispatch_span():
                result = self.dispatch_kernel(
                    lambda: ag.allocate_grouped(
                        node_arrays, task_req[:t], np.zeros(t, np.int32),
                        task_sel[:t], task_tol[:t], np.ones(1, bool),
                        gpu_strategy=self.gpu_strategy,
                        cpu_strategy=self.cpu_strategy,
                        allow_pipeline=allow_pipeline,
                        pipeline_only=pipeline_only,
                        extra_scores=row_extra,
                        node_mask=row_mask,
                        has_releasing=self.has_releasing()),
                    label="allocate_grouped",
                    validate=_allocation_shape_check(t))
            if not bool(result.job_success[0]):
                return Proposal(False, [])
            with TRACER.span("propose:unpack", kind="propose", t=t):
                placements = []
                placed = np.asarray(result.placements)
                piped = np.asarray(result.pipelined)
                for i, task in enumerate(tasks):
                    node_idx = int(placed[i])
                    if node_idx < 0:
                        return Proposal(False, [])
                    placements.append((task, snap.node_names[node_idx],
                                       bool(piped[i])))
                # The homogeneous check above proved the chunk's tasks
                # interchangeable — the one precondition rank reorder
                # needs.
                return Proposal(True, self.apply_rank_placement(
                    tasks, placements))

        if sharded:
            from ..parallel.sharded import sharded_allocate_jobs

            def thunk():
                d_req, d_job, d_sel, d_tol, d_allowed, d_mask = _stage(
                    task_req, task_job, task_sel, task_tol, job_allowed,
                    mask_pad)
                with TRACER.span("seam:launch", kind="seam",
                                 kernel="allocate_jobs_sharded"):
                    return sharded_allocate_jobs(
                        self.mesh, *node_arrays, d_req, d_job, d_sel,
                        d_tol, d_allowed, task_node_mask=d_mask,
                        gpu_strategy=self.gpu_strategy,
                        cpu_strategy=self.cpu_strategy,
                        allow_pipeline=allow_pipeline)
            label = "allocate_jobs_sharded"
        else:
            def thunk():
                (d_req, d_job, d_sel, d_tol, d_allowed, d_extra, d_mask,
                 d_dom, d_aff, d_job_extra, d_job_mask) = _stage(
                    task_req, task_job, task_sel, task_tol, job_allowed,
                    task_extra, mask_pad, dom_pad, aff_pad, job_extra,
                    job_mask)
                with TRACER.span("seam:launch", kind="seam",
                                 kernel="allocate_jobs"):
                    return allocate_jobs_kernel(
                        *node_arrays, d_req, d_job, d_sel, d_tol,
                        d_allowed, d_extra, task_node_mask=d_mask,
                        task_anti_domain=d_dom, task_aff_domain=d_aff,
                        job_extra_scores=d_job_extra,
                        job_node_mask=d_job_mask,
                        gpu_strategy=self.gpu_strategy,
                        cpu_strategy=self.cpu_strategy,
                        allow_pipeline=allow_pipeline,
                        pipeline_only=pipeline_only)
            label = "allocate_jobs"
        placed, piped, success = self._dispatch_and_fetch(
            thunk, label=label, validate=_allocation_shape_check(t_pad),
            t=t)
        if not bool(success[0]):
            return Proposal(False, [])
        with TRACER.span("propose:unpack", kind="propose", t=t):
            placements = []
            for i, task in enumerate(tasks):
                node_idx = int(placed[i])
                if node_idx < 0:
                    return Proposal(False, [])
                if node_subset is not None and not node_subset[node_idx]:
                    return Proposal(False, [])
                placements.append((task, snap.node_names[node_idx],
                                   bool(piped[i])))
        return Proposal(True, placements)

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
        from ..ops.predicates import feasibility_masks
        from ..ops.scoring import score_matrix
        snap = self.snapshot
        req_row, sel_row, tol_row = self._task_row(task)
        if req_row is None:
            return np.zeros(self.node_idle.shape[0])
        req = req_row[None, :]
        alloc, idle, rel, labels, taints, room = self._device_arrays()
        n_nodes = self.node_idle.shape[0]

        def score_thunk():
            # Fractional tasks: capacity-check the cpu/mem axes; GPU
            # device fit is decided host-side by the sharing-group logic.
            fit_now, fit_future = feasibility_masks(
                idle, rel, labels, taints, room, jnp.asarray(req),
                jnp.asarray(sel_row[None, :]),
                jnp.asarray(tol_row[None, :]))
            score = score_matrix(
                alloc, idle, jnp.asarray(req), fit_now, fit_future,
                gpu_strategy=self.gpu_strategy,
                cpu_strategy=self.cpu_strategy)
            return np.asarray(score[0]).copy()

        out = self.dispatch_kernel(
            score_thunk, label="score_nodes",
            validate=lambda r: getattr(r, "shape", (0,))[0] == n_nodes)
        # Plugin score terms apply to host-side paths too: without them a
        # nominated (pipelined-last-cycle) fractional task loses its
        # sticky node and flaps between devices across cycles; preferred
        # node affinity would likewise be ignored.
        for fn in self.extra_score_fns:
            contrib = fn([task])
            if contrib is not None:
                contrib = np.asarray(contrib)
                # One [N] row for the chunk, or the one task's of [1,N].
                out += contrib if contrib.ndim == 1 else contrib[0]
        return out

    def node_index(self, name: str) -> int:
        return self._node_index.get(name, -1)
