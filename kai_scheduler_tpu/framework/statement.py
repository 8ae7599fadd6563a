"""Transactional statement: the in-session operation log.

Mirrors pkg/scheduler/framework/statement.go: every mutation an action makes
(Allocate/Pipeline/Evict) goes through here so preemption scenarios can
checkpoint (:44), roll back (:48), convert allocations to pipelines (:483),
and finally commit side effects (:536 — bind requests and evictions).

The statement is also the single writer of the session's dense node-state
mirrors: each op updates both the host object graph (NodeInfo/PodGroupInfo)
and the packed numpy arrays the device kernels consume, keeping the two
views exactly in sync.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING

from ..api.cluster_info import BindRequest
from ..api.pod_info import PodInfo
from ..api.pod_status import PodStatus

if TYPE_CHECKING:
    from .session import Session


@dataclass
class _Op:
    kind: str                      # allocate | pipeline | evict
    task: PodInfo
    node_name: str = ""
    prev_status: PodStatus = PodStatus.PENDING
    prev_node: str = ""
    prev_gpu_group: str = ""
    gpu_group: str = ""
    # Fast-path ops: accounting went through the native table directly
    # (one batched call), so undo must route there too.
    native_req: object = None      # np.ndarray when native-applied
    node_idx: int = -1


class Statement:
    def __init__(self, session: "Session"):
        self.session = session
        # Op recording and commit both run on the scheduler thread; the
        # commit executor only ever executes the already-frozen closures
        # (DESIGN §10) — it never touches the op list.
        # kairace: single-writer=main
        self.ops: list[_Op] = []
        # kairace: single-writer=main
        self.committed = False
        # Deferred-sync mode for bulk application: node-state mirror
        # pushes collapse to one sync per touched node instead of one per
        # task (the dominant host cost at 100k-node scale).
        self._defer: "set | None" = None

    def _sync(self, node) -> None:
        if self._defer is not None:
            self._defer.add(node.name)
        else:
            self.session.sync_node(node)

    def apply_bulk(self, placements) -> None:
        """Apply [(task, node_name, pipelined)] with one mirror sync per
        touched node.  Semantically identical to per-task allocate()/
        pipeline() — the op log and handlers still fire per task, so
        checkpoint/rollback and queue accounting are unchanged.

        Plain tasks (no fractional GPU, no MIG, no storage claims) take
        the NATIVE batch path: per-task Python does only the object-graph
        bookkeeping (op log, job status, handlers, pod_infos) while the
        resource accounting for the whole batch lands in ONE
        statestore.cpp call, with NodeInfo.used/releasing views updated
        for free (framework/session.py row binding)."""
        # Callers pass generators; materialize once so the native attempt
        # and the generic fallback iterate the same complete list (a
        # partially-consumed generator would silently drop placements and
        # break gang atomicity).
        placements = list(placements)
        if self._apply_bulk_native(placements):
            return
        self._defer = set()
        try:
            for task, node_name, pipelined in placements:
                if pipelined:
                    self.pipeline(task, node_name)
                else:
                    self.allocate(task, node_name)
        finally:
            touched, self._defer = self._defer, None
            for name in touched:
                self.session.sync_node(self.session.cluster.nodes[name])

    def _apply_bulk_native(self, placements) -> bool:
        """Try the batched native path; False -> caller uses the generic
        per-task path (non-plain task, no native table, unbound views)."""
        import numpy as np
        ssn = self.session
        table = getattr(ssn, "_native", None)
        if table is None or not placements:
            return False
        nodes = ssn.cluster.nodes
        rows = []
        for task, node_name, pipelined in placements:
            node = nodes[node_name]
            if (task.is_fractional or task.res_req.mig_resources
                    or task.needs_storage_scheduling() or node.idx < 0
                    or node.idx >= table.n_nodes
                    or node.used.base is None):  # view not bound
                return False
            rows.append((task, node, pipelined))
        n = len(rows)
        idx = np.empty(n, np.int64)
        reqs = np.empty((n, table.n_res), np.float64)
        statuses = np.empty(n, np.int32)
        ops = []
        for i, (task, node, pipelined) in enumerate(rows):
            status = (PodStatus.PIPELINED if pipelined
                      else PodStatus.ALLOCATED)
            req = task.res_req.to_vec(node.gpu_memory_per_device,
                                      mig_as_gpu=False)
            op = _Op("pipeline" if pipelined else "allocate", task,
                     node.name, prev_status=task.status,
                     prev_node=task.node_name,
                     prev_gpu_group=task.gpu_group,
                     native_req=req, node_idx=node.idx)
            task.node_name = node.name
            task.gpu_group = ""
            job = ssn.cluster.podgroups.get(task.job_id)
            if job is not None:
                job.update_task_status(task, status)
            else:
                task.status = status
            node.pod_infos[task.uid] = task
            node.touch()
            ssn.fire_allocate_handlers(task)
            ops.append(op)
            idx[i] = node.idx
            reqs[i] = req
            statuses[i] = 2 if pipelined else 0
        table.add_tasks(idx, reqs, statuses)
        ssn.cluster.invalidate_aggregates()
        ssn.mutation_count += 1
        ssn._dirty_rows.update(int(i) for i in idx)
        self.ops.extend(ops)
        return True

    # -- mutations ---------------------------------------------------------
    def allocate(self, task: PodInfo, node_name: str,
                 gpu_group: str = "") -> None:
        """Assign the task to a node on idle resources (statement.go:297)."""
        self._place(task, node_name, PodStatus.ALLOCATED, gpu_group,
                    "allocate")

    def pipeline(self, task: PodInfo, node_name: str,
                 gpu_group: str = "") -> None:
        """Assign the task onto releasing resources (statement.go:197)."""
        self._place(task, node_name, PodStatus.PIPELINED, gpu_group,
                    "pipeline")

    def _place(self, task: PodInfo, node_name: str, status: PodStatus,
               gpu_group: str, kind: str) -> None:
        node = self.session.cluster.nodes[node_name]
        job = self.session.cluster.podgroups.get(task.job_id)
        op = _Op(kind, task, node_name, prev_status=task.status,
                 prev_node=task.node_name, prev_gpu_group=task.gpu_group,
                 gpu_group=gpu_group)
        task.node_name = node_name
        task.gpu_group = gpu_group
        if job is not None:
            job.update_task_status(task, status)
        else:
            task.status = status
        self.session.cluster.invalidate_aggregates()
        node.add_task(task)
        self._sync(node)
        self.session.fire_allocate_handlers(task)
        self.ops.append(op)

    def evict(self, task: PodInfo) -> None:
        """Mark the task as releasing its resources (statement.go:63)."""
        node = self.session.cluster.nodes.get(task.node_name)
        job = self.session.cluster.podgroups.get(task.job_id)
        op = _Op("evict", task, task.node_name, prev_status=task.status,
                 prev_node=task.node_name, prev_gpu_group=task.gpu_group)
        if node is not None:
            node.remove_task(task)
        if job is not None:
            job.update_task_status(task, PodStatus.RELEASING)
        else:
            task.status = PodStatus.RELEASING
        self.session.cluster.invalidate_aggregates()
        if node is not None:
            node.add_task(task)
            self._sync(node)
        self.session.fire_deallocate_handlers(task, op.prev_status)
        self.ops.append(op)

    # -- undo --------------------------------------------------------------
    def checkpoint(self) -> int:
        return len(self.ops)

    def rollback(self, checkpoint: int = 0) -> None:
        while len(self.ops) > checkpoint:
            self._undo(self.ops.pop())

    _STATUS_CODE = {PodStatus.ALLOCATED: 0, PodStatus.RELEASING: 1,
                    PodStatus.PIPELINED: 2}

    def _undo(self, op: _Op) -> None:
        task = op.task
        node = self.session.cluster.nodes.get(op.node_name)
        job = self.session.cluster.podgroups.get(task.job_id)
        self.session.cluster.invalidate_aggregates()
        if op.native_req is not None and op.kind in ("allocate",
                                                     "pipeline"):
            # Native-applied op: reverse through the table (views keep
            # the NodeInfo graph consistent).
            if node is not None:
                node.pod_infos.pop(task.uid, None)
                node.touch()
                self.session._native.remove_task(
                    op.node_idx, op.native_req,
                    self._STATUS_CODE.get(task.status, 0))
            self.session.fire_deallocate_handlers(task, task.status)
            if job is not None:
                job.update_task_status(task, op.prev_status)
            else:
                task.status = op.prev_status
            task.node_name = op.prev_node
            task.gpu_group = op.prev_gpu_group
            self.session.mutation_count += 1
            self.session._dirty_rows.add(op.node_idx)
            return
        if op.kind in ("allocate", "pipeline"):
            if node is not None:
                node.remove_task(task)
            self.session.fire_deallocate_handlers(task, task.status)
            if job is not None:
                job.update_task_status(task, op.prev_status)
            else:
                task.status = op.prev_status
            task.node_name = op.prev_node
            task.gpu_group = op.prev_gpu_group
            if node is not None:
                self.session.sync_node(node)
        elif op.kind == "evict":
            if node is not None:
                node.remove_task(task)
            if job is not None:
                job.update_task_status(task, op.prev_status)
            else:
                task.status = op.prev_status
            task.node_name = op.prev_node
            task.gpu_group = op.prev_gpu_group
            if node is not None:
                node.add_task(task)
                self.session.sync_node(node)
            self.session.fire_allocate_handlers(task)

    # -- pipelining conversion (statement.go:483) --------------------------
    def convert_all_allocated_to_pipelined(self, job_id: str) -> None:
        """Once any gang member pipelines, the whole gang must wait for the
        releasing resources: demote this statement's Allocated ops."""
        for op in self.ops:
            if (op.kind == "allocate" and op.task.job_id == job_id
                    and op.task.status == PodStatus.ALLOCATED):
                node = self.session.cluster.nodes[op.task.node_name]
                job = self.session.cluster.podgroups.get(job_id)
                if op.native_req is not None:
                    self.session._native.remove_task(
                        op.node_idx, op.native_req, 0)
                    if job is not None:
                        job.update_task_status(op.task,
                                               PodStatus.PIPELINED)
                    else:
                        op.task.status = PodStatus.PIPELINED
                    self.session._native.add_task(
                        op.node_idx, op.native_req, 2)
                    node.touch()
                    self.session.mutation_count += 1
                    self.session._dirty_rows.add(op.node_idx)
                    op.kind = "pipeline"
                    continue
                node.remove_task(op.task)
                if job is not None:
                    job.update_task_status(op.task, PodStatus.PIPELINED)
                else:
                    op.task.status = PodStatus.PIPELINED
                node.add_task(op.task)
                self.session.sync_node(node)
                op.kind = "pipeline"

    # -- commit (statement.go:536) -----------------------------------------
    def commit(self) -> list[BindRequest]:
        """Apply durable side effects: BindRequests for allocations,
        evictions via the cache/evictor.  Pipelined tasks stay in-memory —
        they bind in a later cycle once resources actually free.

        When the cache carries a commit journal (utils/commitlog.py), the
        commit follows WAL discipline: every durable side effect's intent
        is journaled and fsync'd as ONE batch before the first API write
        (a gang's intents are all-or-nothing durable), then each
        completed write appends a buffered ``done`` marker.  A crash
        anywhere in between leaves a journal the restart reconcile pass
        (``ClusterCache.startup_reconcile``) resolves against live API
        state — no phantom reservations, no half-trusted history.

        OVERLAPPED mode (DESIGN §10): when the session carries a commit
        executor (``Session.commit_executor``, armed by the pipelined
        operator cycle) and the cache supports the speculative view, the
        decision is registered speculatively on THIS thread — the next
        snapshot already sees it — and the whole durable write batch
        (journal fsync + API writes) is enqueued to the commit-executor
        thread, overlapping the next cycle's host prep and device work.
        Write order, journal discipline, and fencing are preserved: the
        executor is single-threaded FIFO and every write still carries
        the leadership epoch read at write time."""
        from ..utils.lifecycle import LIFECYCLE

        cache = self.session.cache
        log = getattr(cache, "commitlog", None)
        epoch_provider = getattr(cache, "epoch_provider", None)
        epoch = epoch_provider() if epoch_provider is not None else None
        trace_id = getattr(self.session, "trace_id", None)

        binds, by_op, intents, intent_ops = self._build_commit_batch(
            log, epoch, trace_id)

        # Lifecycle 'scheduled' stamps happen at DECISION time on the
        # cycle thread, for allocate and pipeline ops alike (stamped
        # before any bind write so the phase order stays monotone:
        # scheduled <= bind_requested, whichever thread writes).
        for op in self.ops:
            if op.kind in ("allocate", "pipeline"):
                LIFECYCLE.note(op.task.uid, "scheduled",
                               podgroup=op.task.job_id,
                               node=op.node_name, trace_id=trace_id)
            if op.kind == "pipeline":
                # Pipelined assignments persist in the cache across
                # cycles (Cache.TaskPipelined, cache/interface.go:36-50)
                # so the next snapshot rebuilds them.  In-memory: always
                # on the decision thread.
                task_pipelined = getattr(cache, "task_pipelined", None)
                if task_pipelined is not None:
                    task_pipelined(op.task, op.node_name, op.gpu_group)

        executor = getattr(self.session, "commit_executor", None)
        if executor is not None and hasattr(cache, "speculate"):
            self._commit_overlapped(executor, cache, log, binds, by_op,
                                    intents, intent_ops, epoch)
        else:
            self._commit_serial(cache, log, binds, by_op, intents,
                                intent_ops, epoch)
        self.committed = True
        self.session.cluster.bind_requests.extend(binds)
        return binds

    def _build_commit_batch(self, log, epoch, trace_id):
        """Pre-pass: build every BindRequest (running the plugin
        mutators, dynamicresources.go:252) and collect the intent
        records in op order, so the whole gang's intents hit the journal
        in one fsync'd batch before any API write.  ``intent_ops`` maps
        each intent to its op index — done markers stay correct however
        the writes are batched downstream."""
        from ..utils import commitlog as cl

        binds: list[BindRequest] = []
        by_op: dict[int, BindRequest] = {}
        intents: list[dict] = []
        intent_ops: list[int] = []
        for i, op in enumerate(self.ops):
            if op.kind == "allocate":
                br = BindRequest(
                    pod_uid=op.task.uid, pod_name=op.task.name,
                    namespace=op.task.namespace, node_name=op.node_name,
                    gpu_groups=(op.gpu_group.split(",") if op.gpu_group
                                else []),
                    trace_id=trace_id)
                for mutator in getattr(self.session,
                                       "bind_request_mutators", []):
                    mutator(op.task, br)
                binds.append(br)
                by_op[i] = br
                if log is not None:
                    intents.append(cl.bind_intent(
                        op.task.uid, op.task.name, op.task.namespace,
                        op.node_name, br.gpu_groups, epoch))
                    intent_ops.append(i)
            elif op.kind == "evict" and log is not None:
                intents.append(cl.evict_intent(
                    op.task.uid, op.task.name, op.task.namespace, epoch))
                intent_ops.append(i)
        return binds, by_op, intents, intent_ops

    def _journal_batch(self, log, intents, intent_ops, epoch) -> dict:
        """Append + fsync the intent batch; returns op index -> txid.
        Raises the chaos ``SimulatedCrash`` AFTER the fsync — intents
        durable, nothing committed — on whichever thread runs the batch
        (the restart reconcile pass must cope either way)."""
        from ..utils import commitlog as cl
        from ..utils.deviceguard import control_fault
        from ..utils.tracing import TRACER

        if log is None or not intents:
            return {}
        # The journal append is the commit's one fsync: a span of its
        # own so a slow disk is distinguishable from slow API writes.
        with TRACER.span("journal", kind="commit",
                         intents=len(intents), epoch=epoch):
            txids = log.append_intents(intents)
        txid_of = dict(zip(intent_ops, txids))
        if control_fault("crash-after-journal") is not None:
            # Chaos: die at the worst instant — intents durable, nothing
            # committed.  The restart reconcile pass must make this
            # indistinguishable from "never decided".
            raise cl.SimulatedCrash(
                "crash-after-journal: intents journaled, API commit "
                "not started")
        return txid_of

    def _apply_writes(self, cache, log, by_op, txid_of, ops, intents,
                      landed=None) -> None:
        """The ONE durable-write loop both commit paths share: apply
        every side effect in op order — evictions batch through
        ``cache.evict_many`` and binds through ``cache.bind_many`` (one
        flush per gang batch each) when the cache supports them; a bind
        wave flushes the pending evict batch first and an evict flushes
        the pending bind wave, so writes land in op order ACROSS kinds
        (a crash between them must never leave a bind durable against
        capacity whose victim was not evicted).  ``landed`` (overlapped
        mode) collects the uid of every write that reached the store —
        the fenced-rollback path rolls back exactly the rest.  Per-item
        bulk outcomes: a failed item fails that item only — the rest of
        the wave lands, its journal entries mark done — and the first
        failure (Fenced first) re-raises after the wave settles, exactly
        like ``evict_many``."""
        from ..controllers.kubeapi import Fenced

        evict_batch: list[tuple[int, object]] = []
        bind_batch: list[tuple[int, object]] = []
        evict_many = getattr(cache, "evict_many", None)
        bind_many = getattr(cache, "bind_many", None)

        def note_landed(uid) -> None:
            if landed is not None:
                landed.add(uid)

        def flush_evicts() -> None:
            if not evict_batch:
                return
            evict_many([task for _i, task in evict_batch])
            for i, task in evict_batch:
                note_landed(task.uid)
                if i in txid_of:
                    log.mark_done(txid_of[i])
            evict_batch.clear()

        def flush_binds() -> None:
            if not bind_batch:
                return
            outcomes = bind_many([(op.task, op.node_name, by_op[i])
                                  for i, op in bind_batch])
            failures: list = []
            for (i, op), out in zip(bind_batch, outcomes):
                if out.get("ok"):
                    note_landed(op.task.uid)
                    if i in txid_of:
                        log.mark_done(txid_of[i])
                else:
                    failures.append(out.get("error"))
            bind_batch.clear()
            for exc in failures:
                if isinstance(exc, Fenced):
                    raise exc
            if failures:
                raise failures[0]

        for i, op in enumerate(ops):
            if op.kind == "allocate":
                flush_evicts()
                if bind_many is not None:
                    bind_batch.append((i, op))
                else:
                    cache.bind(op.task, op.node_name, by_op[i])
                    note_landed(op.task.uid)
                    if i in txid_of:
                        log.mark_done(txid_of[i])
            elif op.kind == "evict":
                flush_binds()
                if evict_many is not None:
                    evict_batch.append((i, op.task))
                else:
                    cache.evict(op.task)
                    note_landed(op.task.uid)
                    if i in txid_of:
                        log.mark_done(txid_of[i])
        flush_binds()
        flush_evicts()
        if log is not None and intents:
            log.flush_buffered()

    def _commit_serial(self, cache, log, binds, by_op, intents,
                       intent_ops, epoch) -> None:
        """The synchronous write path (no executor): journal, then the
        shared write loop."""
        txid_of = self._journal_batch(log, intents, intent_ops, epoch)
        self._apply_writes(cache, log, by_op, txid_of, self.ops, intents)

    def _commit_overlapped(self, executor, cache, log, binds, by_op,
                           intents, intent_ops, epoch) -> None:
        """Register the decision speculatively and hand the durable
        writes to the commit executor.  On a fencing rejection mid-batch
        the UN-LANDED decisions' speculative view rolls back and the
        executor poisons (the operator then drains the pipeline to the
        serial path); landed writes stand, exactly like a serial
        mid-commit depose."""
        import time as _time

        from ..utils.tracing import TRACER

        trace_id = getattr(self.session, "trace_id", None)
        spec_entries = []
        for i, op in enumerate(self.ops):
            if op.kind == "allocate":
                spec_entries.append((op.task.uid, "bind", op.node_name))
            elif op.kind == "evict":
                spec_entries.append((op.task.uid, "evict", ""))
        handle = cache.speculate(spec_entries)
        ops = list(self.ops)

        def run_batch() -> None:
            t_batch = _time.perf_counter()
            # Ambient wire context: the batch's bulk bind/status waves
            # run on the executor thread after the cycle trace was
            # finalized — arm the trace id so every wave's request
            # still stamps X-Kai-Trace and its client span attaches to
            # the owning cycle (the wire observatory's commit leg).
            TRACER.set_wire_context(trace_id)
            try:
                self._run_overlapped_batch(executor, cache, log, by_op,
                                           intents, intent_ops, epoch,
                                           handle, ops)
            finally:
                TRACER.clear_wire_context()
                # The commit stage finishes after its cycle's trace was
                # finalized: attach the span post-hoc so /debug/trace
                # still shows where cycle N's commit budget went.
                TRACER.attach_async_span(
                    trace_id, "stage:commit", "commit_async",
                    _time.perf_counter() - t_batch,
                    ops=len(ops), binds=len(binds))

        executor.submit(
            run_batch, label="commit-batch",
            # Dropped by poisoning (an earlier batch hit the fence or a
            # crash): these decisions will never be durable — roll back
            # their speculative view at fault time.
            on_skip=lambda: cache.rollback_speculation(
                handle, "commit skipped: pipeline poisoned"))

    def _run_overlapped_batch(self, executor, cache, log, by_op, intents,
                              intent_ops, epoch, handle, ops) -> None:
        from ..controllers.kubeapi import Fenced
        from ..utils import commitlog as cl
        from ..utils.metrics import METRICS

        txid_of = {}
        try:
            txid_of = self._journal_batch(log, intents, intent_ops,
                                          epoch)
        except cl.SimulatedCrash:
            # Crash semantics: this scheduler is dead — nothing else it
            # queued may commit.  The speculation stays (a real crash
            # takes the whole process); the test/restart path reconciles
            # from the journal.
            executor.poison("crash-after-journal")
            raise
        landed: set = set()
        try:
            self._apply_writes(cache, log, by_op, txid_of, ops, intents,
                               landed=landed)
        except Fenced as exc:
            # Deposed mid-overlap: the store rejected the write.
            # Decisions whose writes never landed roll back their
            # speculative view — the rightful leader re-schedules those
            # pods; landed writes stand (they carried a then-valid
            # epoch).
            remaining = {uid: seq for uid, seq in handle.items()
                         if uid not in landed}
            cache.rollback_speculation(remaining, f"fenced: {exc}")
            METRICS.inc("pipeline_fenced_commits_total")
            executor.poison(f"fenced commit: {exc}")

    def discard(self) -> None:
        """Roll everything back (an action abandoning its statement)."""
        self.rollback(0)
