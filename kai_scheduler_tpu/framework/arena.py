"""Persistent device arena: cross-cycle snapshot and device-state residency.

The reference scheduler rebuilds its world every cycle; our port inherited
that at the host<->device seam — every new ``Session`` re-uploaded the
whole packed snapshot (including the immutable ``allocatable``/``labels``/
``taints`` tensors) and any single touched node row re-shipped all of
``idle``+``releasing``+``room``.  Every one of those is a host->device
transfer plus its dispatch, paid each cycle for state that had not
changed.

The arena keeps cluster state resident across cycles and updates it by
deltas instead of rebuilding:

- **incremental snapshot pack** — the previous cycle's packed numpy arrays
  persist here (``pack``); ``ClusterCache.snapshot`` feeds the arena the
  dirty set it derives from the watch-event stream (resourceVersion
  diffing of the watched store, resync boundaries invalidating wholesale),
  and ``api/snapshot.pack_incremental`` patches only the changed node rows
  — bit-identical to a from-scratch ``pack()`` (tests/test_snapshot_delta.py
  proves it property-style);
- **static device residency** — ``allocatable``/``labels``/``taints``
  upload once per arena *generation* (bumped only on a full rebuild) and
  are reused across Session objects;
- **scatter-based state updates** — ``idle``/``releasing``/``room`` stay
  resident on device; dirty rows (tracked by ``Session.sync_node`` and the
  cross-cycle snapshot diff) are applied by the jitted
  ``ops/arena.apply_deltas_kernel`` scatter (``[K]`` rows + ``[K,R]``
  values) instead of a full ``[N,R]`` re-upload.

Degraded-mode contract: every device-touching step dispatches through the
device guard (``Session.dispatch_kernel`` — watchdog, breaker, CPU
fallback), and the arena drops its device caches on breaker/CPU-fallback
transitions so degraded mode never reads a stale TPU buffer
(docs/DEGRADATION.md).  The arena is single-writer: only the scheduler
thread that runs the cycle touches it, like the Session mirrors it backs.

The host half also serves a scheduler whose cache is not a
``ClusterCache``: ``HostArena`` (one per ``Scheduler``) carries the packed
arrays, the native node table and the name index from one session to the
next and reads what moved off the ``NodeInfo`` stamps instead of a watch
stream (docs/DESIGN.md section 8).  The device half stays with
``ClusterArena``.

Observability: ``snapshot_delta``/``arena_scatter`` tracing spans,
``snapshot_delta_ratio`` gauge, ``arena_full_rebuild_total`` /
``arena_scatter_rows`` / ``arena_device_invalidation_total`` counters, and
pack stats on ``GET /debug/cycles`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import time
import weakref
from operator import attrgetter

import numpy as np

from ..api.snapshot import (SnapshotTensors, pack, pack_incremental,
                            vocabulary_signature)
from ..ops.allocate_grouped import _next_pow2
from ..utils.logging import LOG
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER

# Above this fraction of dirty rows a scatter loses to one contiguous
# upload (scatter pays gather+kernel overhead per row; a bulk transfer
# streams).  Conservative midpoint; the bench's steady_state config
# measures the real crossover per deployment.
SCATTER_MAX_FRACTION = 0.5

# Above this fraction of dirty rows ``HostArena`` packs from scratch.  The
# patch gathers ``used``, ``releasing`` and the pod count of every dirty
# node as the full pack does of every node, and re-binds it, on top of a
# fixed scan of the stamps and the vocabulary; what it saves is the static
# half (allocatable, labels, taints, codec) and the clean rows.  At 65,536
# nodes a patched session costs about 85 + 250 f ms at dirty fraction f
# and a session from scratch 305 (CPU sandbox, an ordering, PERF.md
# section 6, PR 30): they cross near 0.9, and a full pack renews the
# whole baseline besides.
DELTA_MAX_FRACTION = 0.75

_IDX = attrgetter("idx")
_VERSION = attrgetter("version")


class GuardWatch:
    """Detects device-guard transitions the arena must invalidate on.

    A breaker state change (device died, or recovered via a half-open
    probe) means cached device buffers may live on the wrong/dead side of
    the fallback boundary; a CPU-fallback call while the breaker is still
    CLOSED (threshold not yet hit) is the same hazard one call earlier.
    ``resync`` re-reads the counters after the arena's own guarded
    uploads so the arena's fallbacks don't count as fresh transitions
    (that would re-invalidate every call while degraded)."""

    def __init__(self):
        self._mark = None

    def _read(self, guard) -> tuple:
        return (guard.breaker.state, guard.fallback_calls)

    def transitioned(self, guard) -> bool:
        mark = self._read(guard)
        if self._mark is None:
            self._mark = mark
            return False
        prev_state, prev_fallbacks = self._mark
        state, fallbacks = mark
        self._mark = mark
        if state != prev_state:
            return True
        # Fallbacks with the breaker still closed: transient degradation
        # that already ran host-side against possibly-device buffers.
        from ..utils.deviceguard import CLOSED
        return state == CLOSED and fallbacks != prev_fallbacks

    def resync(self, guard) -> None:
        self._mark = self._read(guard)


class DeviceStateCache:
    """Device-resident mutable node state (idle/releasing/room) updated by
    row scatters.

    ``_host`` mirrors exactly what the device arrays hold, so a new
    Session adopting the cache can diff its (snapshot-fresh) mirrors
    against it and scatter only the rows that actually moved — whether
    they moved because the watch stream delivered cluster changes or
    because the previous cycle's statements committed placements."""

    def __init__(self):
        # Device residency is scheduler-thread-owned (single-writer):
        # dispatch/scatter happen on the cycle path only.
        # kairace: single-writer=main
        self._dev: tuple | None = None    # (idle, rel, room) device arrays
        # kairace: single-writer=main
        self._host: tuple | None = None   # matching host copies
        self._owner = None                # session the cache is synced to

    @property
    def resident(self) -> bool:
        return self._dev is not None

    def invalidate(self) -> None:
        self._dev = None
        self._host = None
        self._owner = None

    def _upload(self, session, idle, rel, room) -> tuple:
        import jax.numpy as jnp

        def thunk():
            return (jnp.asarray(idle), jnp.asarray(rel), jnp.asarray(room))

        self._dev = session.dispatch_kernel(thunk, label="arena_state_upload")
        self._host = (np.array(idle, np.float64),
                      np.array(rel, np.float64),
                      np.array(room, np.float64))
        return self._dev

    def _changed_rows(self, session) -> np.ndarray:
        """Rows whose host mirrors differ from what the device holds."""
        if self._owner is session:
            # In-session mutations are tracked at the source
            # (Session.sync_node / the native bulk path).
            rows = np.fromiter(session._dirty_rows, np.int64,
                               count=len(session._dirty_rows))
            rows.sort()
            return rows
        # Cross-cycle adoption: one vectorized diff is exact whatever
        # happened in between (binds the scheduler committed, watch
        # deltas, statement mutations of the previous session).
        h_idle, h_rel, h_room = self._host
        diff = (h_idle != session.node_idle).any(axis=1)
        diff |= (h_rel != session.node_releasing).any(axis=1)
        diff |= h_room != session.node_room
        return np.nonzero(diff)[0]

    def arrays(self, session) -> tuple:
        import jax.numpy as jnp

        idle, rel, room = (session.node_idle, session.node_releasing,
                           session.node_room)
        n = idle.shape[0]
        if self._host is not None and self._host[0].shape != idle.shape:
            self.invalidate()  # node bucket grew: shapes no longer match
        if self._dev is None:
            dev = self._upload(session, idle, rel, room)
            self._owner = session
            session._dirty_rows.clear()
            return dev
        rows = self._changed_rows(session)
        self._owner = session
        session._dirty_rows.clear()
        if rows.size == 0:
            return self._dev
        if rows.size > n * SCATTER_MAX_FRACTION:
            METRICS.inc("arena_state_full_upload_total")
            return self._upload(session, idle, rel, room)
        # Pad the row axis to a pow2 bucket so the scatter kernel compiles
        # a handful of shapes, not one per K; padding repeats the first
        # real row with its own value (an idempotent write).
        k = int(rows.size)
        k_pad = _next_pow2(k)
        rows_pad = np.full(k_pad, rows[0], np.int64)
        rows_pad[:k] = rows
        # Slice values in the RESIDENT dtype: the host mirrors are f64
        # (exact diffing) but the device arrays follow the backend's
        # default width — converting here is one fused host pass, where
        # an f64 np array handed to jnp.asarray under 32-bit mode pays a
        # separate conversion copy per scatter.
        dt = np.dtype(self._dev[0].dtype)
        idle_v = np.ascontiguousarray(idle[rows_pad], dt)
        rel_v = np.ascontiguousarray(rel[rows_pad], dt)
        room_v = np.ascontiguousarray(room[rows_pad], dt)
        dev = self._dev
        from ..ops.arena import apply_deltas_kernel
        with TRACER.span("arena_scatter", kind="arena_scatter",
                         rows=k, padded=k_pad):
            self._dev = session.dispatch_kernel(
                lambda: apply_deltas_kernel(
                    dev[0], dev[1], dev[2], jnp.asarray(rows_pad),
                    jnp.asarray(idle_v), jnp.asarray(rel_v),
                    jnp.asarray(room_v)),
                label="arena_scatter",
                validate=lambda r: (getattr(r[0], "shape", None)
                                    == dev[0].shape))
        METRICS.inc("arena_scatter_rows", k)
        h_idle, h_rel, h_room = self._host
        h_idle[rows] = idle[rows]
        h_rel[rows] = rel[rows]
        h_room[rows] = room[rows]
        return self._dev


def _delta_or_full(cluster, prev, reason, dirty_nodes, queue_usage,
                   pad_nodes_to, reuse_tasks) -> tuple:
    """The pack itself, once an arena has settled its ``reason``: patch
    ``prev`` where there is none, pack from scratch where there is one or
    where the patch cannot be applied.  Returns ``(tensors, changed rows
    or None after a full pack, reason)``."""
    if reason is None:
        try:
            snap, rows = pack_incremental(
                cluster, prev, dirty_nodes, queue_usage=queue_usage,
                pad_nodes_to=pad_nodes_to, reuse_tasks=reuse_tasks)
            return snap, rows, None
        except Exception as exc:
            # A delta that cannot be applied must degrade to a rebuild,
            # never crash the cycle; the property suite keeps this branch
            # honest (it asserts delta packs DO happen, so a silent
            # always-fallback would fail).
            LOG.warning("arena: incremental pack failed (%r); "
                        "falling back to full rebuild", exc)
            reason = "delta-error"
    snap = pack(cluster, queue_usage=queue_usage, pad_nodes_to=pad_nodes_to)
    METRICS.inc("arena_full_rebuild_total")
    return snap, None, reason


def _verdict(cluster, rows, reason, generation: int, t0: float) -> dict:
    """The pack's verdict as ``run_once`` copies it onto the ``snapshot``
    span and ``/debug/cycles`` shows it; sets ``snapshot_delta_ratio``."""
    n = max(1, len(cluster.node_order))
    ratio = 1.0 if rows is None else len(rows) / n
    METRICS.set_gauge("snapshot_delta_ratio", ratio)
    return {
        "full_rebuild": rows is None,
        "reason": reason or "",
        "changed_rows": (n if rows is None else int(len(rows))),
        "total_rows": n,
        "delta_ratio": round(ratio, 6),
        "generation": generation,
        "pack_s": round(time.perf_counter() - t0, 6),
    }


class HostArena:
    """The host half of the arena, for a scheduler whose cache brings no
    ``ClusterArena``: the previous session's packed arrays, native node
    table and name-to-row index are carried to the next session over the
    same ``ClusterInfo`` object and patched on the rows that moved.

    Nobody tells this arena what changed: it reads it off the objects.
    ``NodeInfo.version`` is re-stamped wherever a node's accounting, pod
    set or row binding changes, so the rows to patch are those whose stamp
    differs from the one kept at the last pack; ``vocabulary_signature``
    stands in for the watch stream's vocabulary events.  Whatever cannot
    be proven packs from scratch (``_reason_and_rows``).  Reading marks
    nothing, so a bystander's ``pack(cluster)`` between two cycles changes
    nothing here.

    Host only: the session keeps its own per-session device arrays
    (``Session._device_arrays``), so no scatter program is dispatched and
    nothing compiles that a from-scratch session would not compile.
    Owned by one ``Scheduler`` and touched on its cycle thread alone."""

    def __init__(self):
        # kairace: single-writer=main
        self.generation = 0
        self.last_pack: dict = {}
        # kairace: single-writer=main
        self._prev: SnapshotTensors | None = None
        self._prev_pad: int | None = None
        self._cluster = None          # weakref to the baseline's cluster
        self._vocab: tuple | None = None
        # Per row, the node's stamp when the baseline was settled; None
        # between ``pack`` and ``settle`` (a session that died in between
        # leaves no baseline to patch).
        # kairace: single-writer=main
        self._versions: np.ndarray | None = None
        # What the last pack patched (None after a full pack).
        self.patched_rows: np.ndarray | None = None
        # What the sessions' plugins keep from one session to the next
        # (``Session.products``): a full pack starts it empty.
        self.products: dict = {}
        # The pods that carry an inter-pod term, as the last pack's walk
        # over every pod found them (``Session.term_carriers``).
        self.term_carriers: list = []
        self._table = None            # the sessions' NativeNodeTable
        self._node_index: dict | None = None

    def _reason_and_rows(self, cluster, pad_nodes_to, vocab) -> tuple:
        """``(reason, None)``: why this cluster cannot be patched from the
        baseline; or ``(None, rows)``: the rows whose nodes moved."""
        prev = self._prev
        if prev is None:
            return "no-previous-pack", None
        if self._cluster is None or self._cluster() is not cluster:
            return "other-cluster", None
        if self._versions is None:
            return "unsettled-baseline", None
        if pad_nodes_to != self._prev_pad:
            return "node-bucket-growth", None
        order, nodes = cluster.node_order, cluster.nodes
        if len(nodes) != len(order) or order != prev.node_names:
            return "topology-change", None
        if vocab != self._vocab:
            return "vocab-change", None
        n = len(order)
        now = self._stamps(cluster)
        if now is None or now.shape != self._versions.shape:
            return "topology-change", None
        rows = np.nonzero(now != self._versions)[0]
        if rows.size > n * DELTA_MAX_FRACTION:
            return "mostly-dirty", None
        moved = [nodes[order[i]] for i in rows]
        if any(nd.idx != i for nd, i in zip(moved, rows)):
            return "topology-change", None
        if moved and not np.array_equal(
                np.stack([nd.allocatable for nd in moved]),
                prev.node_allocatable[rows]):
            # A re-stamped node whose hardware differs is a new Node.
            return "node-change", None
        return None, rows

    @staticmethod
    def _stamps(cluster) -> np.ndarray | None:
        """Each node's stamp at the row its own idx names, or None where
        some idx names no row.  Stamps are never reused, so a row that no
        node or the wrong node claims cannot read as it did; the rows
        that moved are then held to ``node_order`` one by one."""
        nodes = cluster.nodes
        n = len(nodes)
        idx = np.fromiter(map(_IDX, nodes.values()), np.int64, count=n)
        if n and (idx.min() < 0 or idx.max() >= n):
            return None
        now = np.zeros(n, np.int64)
        now[idx] = np.fromiter(map(_VERSION, nodes.values()), np.int64,
                               count=n)
        return now

    def pack(self, cluster, queue_usage=None,
             pad_nodes_to: int | None = None
             ) -> tuple[SnapshotTensors, dict]:
        """Pack ``cluster`` for one Session: the baseline patched where
        ``_reason_and_rows`` allows it, ``api.snapshot.pack`` otherwise;
        bit-identical to the latter either way.  Its three steps are
        parts of the caller's ``snapshot`` span (``snapshot:survey``,
        ``snapshot:stamps``, ``snapshot:pack``); the verdict rides on that
        span as attributes (``Session.pack_stats``) and what of it says
        which pack this was on ``snapshot:pack`` too
        (docs/OBSERVABILITY.md)."""
        t0 = time.perf_counter()
        with TRACER.span("snapshot:survey", kind="snapshot_part"):
            vocab, self.term_carriers = vocabulary_signature(cluster)
        with TRACER.span("snapshot:stamps", kind="snapshot_part"):
            reason, rows = self._reason_and_rows(cluster, pad_nodes_to,
                                                 vocab)
        dirty = () if rows is None else [cluster.node_order[i] for i in rows]
        # Task, job and queue arrays are rebuilt: podgroups, pod statuses
        # and queues are plain fields that no stamp covers.
        with TRACER.span("snapshot:pack", kind="snapshot_part") as pack_sp:
            snap, rows, reason = _delta_or_full(
                cluster, self._prev, reason, dirty, queue_usage,
                pad_nodes_to, reuse_tasks=False)
        if rows is None:
            self.generation += 1
            self.products = {}
        self._prev = snap
        self._prev_pad = pad_nodes_to
        self._cluster = weakref.ref(cluster)
        self._vocab = vocab
        self.patched_rows = rows
        self._versions = None
        self.last_pack = _verdict(cluster, rows, reason, self.generation, t0)
        pack_sp.set(**{k: self.last_pack[k]
                       for k in ("full_rebuild", "reason", "changed_rows")})
        return snap, self.last_pack

    def carried(self, snap: SnapshotTensors) -> tuple:
        """``(table, dirty rows, node index)`` of the session before, for
        the session being built on ``snap``; ``(None, None, None)`` after
        a full pack.  The table's ``used`` and ``releasing`` rows are the
        memory the ``NodeInfo`` objects write to, so they are current;
        ``room`` and the binding of the dirty rows are the session's to
        patch."""
        if self.patched_rows is None:
            return None, None, None
        table = self._table
        if table is not None and (table.n_nodes, table.n_res) \
                != snap.node_allocatable.shape:
            table = None
        return table, self.patched_rows, self._node_index

    def settle(self, session) -> None:
        """The session is built: keep its table and index, and the stamps
        its nodes carry now that it has re-bound (and so re-stamped) the
        rows it had to."""
        self._versions = self._stamps(session.cluster)
        self._table = session._native
        self._node_index = session._node_index


class ClusterArena:
    """Cross-cycle pack + device residency cache, one per ClusterCache.

    Producer side (``ClusterCache.snapshot`` on the scheduler thread):
    ``note_nodes``/``note_tasks``/``note_vocab``/``note_full`` accumulate
    the dirty set derived from the watch-updated store since the last
    pack; ``stamp`` marks the ClusterInfo as this arena's latest view.

    Consumer side (``Session.__init__`` / ``Session._device_arrays``, same
    thread): ``pack`` turns the accumulated delta into a SnapshotTensors
    (incremental when safe, full rebuild otherwise), ``device_arrays``
    serves the resident device tensors."""

    def __init__(self):
        # Single-writer structure (DESIGN §9): the scheduler thread owns
        # every arena mutation — watch hooks mark dirt through the
        # cache's queued changes, never here.  The annotations are
        # machine-checked by kairace KRC003 (docs/STATIC_ANALYSIS.md).
        # kairace: single-writer=main
        self.generation = 0
        # kairace: single-writer=main
        self._prev: SnapshotTensors | None = None
        # kairace: single-writer=main
        self._prev_pad: int | None = None
        # kairace: single-writer=main
        self._prev_usage: dict | None = None
        self._prev_node_order: list | None = None
        # Accumulated dirty state since the last pack.
        # kairace: single-writer=main
        self._dirty_nodes: set[str] = set()
        # kairace: single-writer=main
        self._tasks_dirty = True
        # kairace: single-writer=main
        self._vocab_dirty = False
        self._full_reason: str | None = "first-snapshot"
        # Stamp: only the owning cache's LATEST snapshot may take the
        # delta path (an older/foreign ClusterInfo packs from scratch).
        # kairace: single-writer=main
        self._stamp = 0
        self._latest_stamp: int | None = None
        # Device residency.
        self.state = DeviceStateCache()
        self._static_dev: tuple | None = None
        self._static_gen = -1
        self.guard_watch = GuardWatch()
        self.last_pack: dict = {}
        # As ``HostArena``'s: the rows the last pack patched (None after
        # a full pack), and what plugins keep between sessions.
        self.patched_rows: np.ndarray | None = None
        self.products: dict = {}

    # -- producer side (ClusterCache.snapshot) -----------------------------
    def note_nodes(self, names) -> None:
        self._dirty_nodes.update(names)

    def note_tasks(self) -> None:
        self._tasks_dirty = True

    def note_vocab(self) -> None:
        """A selector/toleration-bearing pod changed: the label codec (and
        the task-array widths derived from it) may shift — delta packs
        must not trust the previous vocabulary."""
        self._vocab_dirty = True
        self._tasks_dirty = True

    def note_full(self, reason: str) -> None:
        if self._full_reason is None:
            self._full_reason = reason

    def stamp(self, cluster) -> None:
        self._stamp += 1
        self._latest_stamp = self._stamp
        cluster.arena_stamp = self._stamp

    def invalidate(self, reason: str) -> None:
        """Wholesale invalidation (watch resync, explicit operator
        action): the next pack rebuilds from scratch and the device side
        re-uploads."""
        self.note_full(reason)
        self.drop_device(reason)

    def drop_device(self, reason: str) -> None:
        if self._static_dev is not None or self.state.resident:
            METRICS.inc("arena_device_invalidation_total")
            LOG.v(1).info("arena: device caches dropped (%s)", reason)
        self._static_dev = None
        self._static_gen = -1
        self.state.invalidate()

    # -- pack --------------------------------------------------------------
    def _full_rebuild_reason(self, cluster, pad_nodes_to,
                             queue_usage) -> str | None:
        if self._full_reason is not None:
            return self._full_reason
        if self._prev is None:
            return "no-previous-pack"
        if getattr(cluster, "arena_stamp", None) != self._latest_stamp:
            return "unstamped-cluster"
        if pad_nodes_to != self._prev_pad:
            return "node-bucket-growth"
        if self._vocab_dirty:
            return "vocab-change"
        if cluster.node_order != self._prev.node_names:
            return "topology-change"
        return None

    @staticmethod
    def _usage_equal(a, b) -> bool:
        if a is None and b is None:
            return True
        if a is None or b is None or set(a) != set(b):
            return False
        return all(np.array_equal(a[k], b[k]) for k in a)

    def pack(self, cluster, queue_usage=None,
             pad_nodes_to: int | None = None
             ) -> tuple[SnapshotTensors, dict]:
        """Pack ``cluster`` for one Session, reusing the previous cycle's
        arrays where the accumulated delta proves them unchanged.  Always
        bit-identical to ``api.snapshot.pack`` on the same cluster."""
        with TRACER.span("snapshot_delta", kind="snapshot_delta") as sp:
            t0 = time.perf_counter()
            reason = self._full_rebuild_reason(cluster, pad_nodes_to,
                                               queue_usage)
            reuse_tasks = (reason is None and not self._tasks_dirty
                           and self._usage_equal(queue_usage,
                                                 self._prev_usage))
            snap, rows, reason = _delta_or_full(
                cluster, self._prev, reason, self._dirty_nodes,
                queue_usage, pad_nodes_to, reuse_tasks)
            if rows is None:
                self.generation += 1
                self.products = {}
            self.patched_rows = rows
            self._prev = snap
            self._prev_pad = pad_nodes_to
            self._prev_usage = queue_usage
            stamp = getattr(cluster, "arena_stamp", None)
            if stamp is not None and stamp == self._latest_stamp:
                # The baseline now matches the latest snapshot: the dirty
                # accumulation restarts from here.
                self._dirty_nodes = set()
                self._tasks_dirty = False
                self._vocab_dirty = False
                self._full_reason = None
            else:
                # A stale/foreign cluster became the baseline: the dirty
                # set no longer describes "changes since the baseline",
                # so the next pack must rebuild regardless.
                self._full_reason = "stale-baseline"
            stats = _verdict(cluster, rows, reason, self.generation, t0)
            self.last_pack = stats
            sp.set(**stats)
        return snap, stats

    # -- device residency --------------------------------------------------
    def device_static(self, snap: SnapshotTensors, session) -> tuple:
        """(allocatable, labels, taints) device arrays, uploaded once per
        arena generation and reused across Sessions (the static tensors
        are shared by reference across delta packs, so a generation match
        proves the device copies current)."""
        import jax.numpy as jnp

        s = self._static_dev
        if s is not None and self._static_gen == self.generation \
                and s[0].shape == snap.node_allocatable.shape:
            return s

        def thunk():
            return (jnp.asarray(snap.node_allocatable),
                    jnp.asarray(snap.node_labels),
                    jnp.asarray(snap.node_taints))

        self._static_dev = session.dispatch_kernel(
            thunk, label="arena_static_upload")
        self._static_gen = self.generation
        return self._static_dev

    def device_arrays(self, snap: SnapshotTensors, session) -> tuple:
        """The kernel-input tuple (alloc, idle, rel, labels, taints, room)
        served from the resident caches; called on the cycle thread, every
        device touch routed through ``session.dispatch_kernel``."""
        from ..utils.deviceguard import device_guard
        guard = device_guard()
        if self.guard_watch.transitioned(guard):
            # Breaker flipped or a CPU fallback ran: device buffers may
            # sit on the dead/wrong side of the fallback boundary.
            self.drop_device("device-guard transition "
                             f"({guard.breaker.state})")
        alloc, labels, taints = self.device_static(snap, session)
        idle, rel, room = self.state.arrays(session)
        # The arena's own guarded uploads may themselves have fallen
        # back; absorbing them here keeps a degraded steady state from
        # re-invalidating (and re-uploading) on every call.
        self.guard_watch.resync(guard)
        return (alloc, idle, rel, labels, taints, room)

    # -- observability -----------------------------------------------------
    def stats(self) -> dict:
        """Pack/residency stats for GET /debug/cycles."""
        return {
            "generation": self.generation,
            "last_pack": dict(self.last_pack),
            "device": {
                "static_resident": self._static_dev is not None,
                "state_resident": self.state.resident,
            },
            "full_rebuild_total": METRICS.counters.get(
                "arena_full_rebuild_total", 0),
            "scatter_rows_total": METRICS.counters.get(
                "arena_scatter_rows", 0),
        }
