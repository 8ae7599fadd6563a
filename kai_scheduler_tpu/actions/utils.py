"""Action utilities: comparator heaps and per-queue job ordering.

Mirrors pkg/scheduler/scheduler_util/priority_queue.go (binary heap with
capacity) and pkg/scheduler/actions/utils/job_order_by_queue.go: a heap of
queues ordered by the DRF queue comparator, each holding a heap of its jobs
ordered by the composed job-order functions; popping yields the globally
next job, and queues re-enter the heap with updated shares after each
allocation.
"""

from __future__ import annotations

import heapq
import itertools
from typing import Callable, Iterable

from ..api.podgroup_info import PodGroupInfo

INFINITE = -1


class PriorityQueue:
    """Heap over a less(a, b) comparator with optional max size.

    ``key``: optional item -> sort-key function; when given, each push
    computes the key ONCE and heap maintenance compares tuples instead of
    invoking the comparator per comparison — pairwise DRF comparators cost
    tens of microseconds each, which dominated steady-state cycles with
    thousands of pending jobs (the burst scale scenario).
    ``largest_key_first`` pops by descending key (the victim order of a
    leaf) with no wrapper a key; ``less`` comes reversed from the caller.
    ``items`` are there from the start, as if pushed one by one in their
    order, but ordered in bulk: in key mode one key an item and one C
    sort of the key tuples (a sorted list is a heap), in comparator mode
    one ``heapify``, or one sort where ``max_size`` keeps the best only.
    """

    def __init__(self, less: Callable, max_size: int = INFINITE,
                 key: Callable | None = None,
                 largest_key_first: bool = False, items: Iterable = ()):
        self.less = less
        self.key = key
        self.max_size = max_size
        self._keyed = (self._DescendingEntry if largest_key_first
                       else self._KeyedEntry)
        items = list(items)
        bounded = max_size != INFINITE
        if key is not None:
            keys = [key(item) for item in items]
            # Stable in either direction: equal keys stay in the order
            # given, which is the entries' ``seq``.
            ranked = sorted(range(len(items)), key=keys.__getitem__,
                            reverse=largest_key_first)
            if bounded:
                del ranked[max_size:]
            self._items = [self._keyed(items[i], keys[i], i) for i in ranked]
        else:
            entries = [self._Entry(item, less, seq)
                       for seq, item in enumerate(items)]
            if bounded:
                entries = sorted(entries)[:max_size]
            else:
                heapq.heapify(entries)
            self._items = entries
        self._counter = itertools.count(len(items))

    class _Entry:
        __slots__ = ("item", "less", "seq")

        def __init__(self, item, less, seq):
            self.item, self.less, self.seq = item, less, seq

        def __lt__(self, other):
            if self.less(self.item, other.item):
                return True
            if self.less(other.item, self.item):
                return False
            return self.seq < other.seq

    class _KeyedEntry:
        __slots__ = ("item", "k", "seq")

        def __init__(self, item, k, seq):
            self.item, self.k, self.seq = item, k, seq

        def __lt__(self, other):
            if self.k != other.k:
                return self.k < other.k
            return self.seq < other.seq

    class _DescendingEntry(_KeyedEntry):
        __slots__ = ()

        def __lt__(self, other):
            if self.k != other.k:
                return other.k < self.k
            return self.seq < other.seq

    def push(self, item) -> None:
        if self.key is not None:
            entry = self._keyed(item, self.key(item), next(self._counter))
        else:
            entry = self._Entry(item, self.less, next(self._counter))
        if self.max_size != INFINITE and len(self._items) >= self.max_size:
            # Keep the best max_size items: replace the worst if the new
            # item beats it (priority_queue.go bounded behavior).
            worst = max(self._items)
            if entry < worst:
                self._items.remove(worst)
                heapq.heapify(self._items)
                heapq.heappush(self._items, entry)
            return
        heapq.heappush(self._items, entry)

    def pop(self):
        return heapq.heappop(self._items).item

    def peek(self):
        return self._items[0].item

    def empty(self) -> bool:
        return not self._items

    def __len__(self) -> int:
        return len(self._items)


class _QueueNode:
    """One queue in the ordering tree (job_order_by_queue.go queueNode).

    Leaves hold a job heap; inner nodes hold a child-node heap.  Nodes
    carry a ``token`` for lazy heap deletion: re-pushing a node bumps the
    token, so stale heap entries (older token, or detached node) are
    skipped on pop — the cheap stand-in for the reference's
    needsReorder + heap Fix."""

    __slots__ = ("qid", "parent", "jobs", "children", "is_leaf",
                 "token", "attached")

    def __init__(self, qid: str, is_leaf: bool):
        self.qid = qid
        self.parent: "_QueueNode | None" = None
        self.jobs: PriorityQueue | None = None
        self.children: list = []   # heap of (_NodeEntry)
        self.is_leaf = is_leaf
        self.token = 0
        self.attached = False

    def live(self) -> bool:
        if self.is_leaf:
            return self.jobs is not None and not self.jobs.empty()
        return any(e.node.attached and e.token == e.node.token
                   for e in self.children)


class _Rev:
    """Reverses the sort order of a queue's key tuple (victim-mode key
    form: pairwise-comparator reversal would abandon the O(1)-comparison
    key fast path that keeps 1000s-of-jobs ordering cheap)."""

    __slots__ = ("k",)

    def __init__(self, k):
        self.k = k

    def __lt__(self, other):
        return other.k < self.k

    def __eq__(self, other):
        return self.k == other.k


class _NodeEntry:
    __slots__ = ("node", "token", "k", "less", "seq")

    def __init__(self, node, k, less, seq):
        self.node, self.token = node, node.token
        self.k, self.less, self.seq = k, less, seq

    def __lt__(self, other):
        if self.k is not None or other.k is not None:
            if self.k != other.k:
                return self.k < other.k
            return self.seq < other.seq
        if self.less(self.node, other.node):
            return True
        if self.less(other.node, self.node):
            return False
        return self.seq < other.seq


class JobsOrderByQueues:
    """The allocate/reclaim job iterator over the n-level queue hierarchy
    (job_order_by_queue.go).

    Queues form a tree mirroring parentQueue links; at every level sibling
    nodes are ordered by ssn.compare_queues with each subtree's *best
    descendant job* as context (buildNodeOrderFn/getBestJobFromNode), so a
    department's standing — not just a leaf's — decides who allocates
    next.  Jobs within a leaf are ordered by ssn.compare_jobs.  After a
    job is processed the caller re-queues its leaf; ancestors re-enter
    their heaps with fresh keys (the needsReorder analog).
    """

    def __init__(self, ssn, jobs: Iterable[PodGroupInfo],
                 max_jobs_per_queue: int = INFINITE,
                 victims_by_queue: dict | None = None,
                 victim_mode: bool = False):
        self.ssn = ssn
        self.victims_by_queue = victims_by_queue or {}
        self.victim_mode = victim_mode
        self._max_jobs = max_jobs_per_queue
        self._counter = itertools.count()
        # Key mode: when every registered comparator has a matching
        # precomputed-key form, heap maintenance compares cached tuples
        # (one key computation per push) instead of running the pairwise
        # DRF comparators per heap comparison.  An unpaired registration
        # (order fn without key fn) disables it, preserving exact
        # comparator semantics.  Victim mode reverses the keys (the
        # reference's VictimQueue "!order" with the fast path kept — a
        # 3200-victim survey must not pay pairwise DRF comparisons): a
        # leaf's jobs pop largest key first, a queue's key goes in a _Rev.
        self._job_key = None
        if (getattr(ssn, "job_keys_complete", False)
                and len(ssn.job_key_fns) == len(ssn.job_order_fns)):
            self._job_key = ssn.job_sort_key
        self._queue_key = None
        if (ssn.queue_key_fn is not None
                and len(ssn.queue_order_fns) == 1
                and (victim_mode or not self.victims_by_queue)):
            if victim_mode:
                self._queue_key = lambda qid, job: _Rev(
                    ssn.queue_key_fn(qid, job))
            else:
                self._queue_key = ssn.queue_key_fn
        self._nodes: dict[str, _QueueNode] = {}
        self._roots: list = []      # heap of _NodeEntry
        # Bulk build: order each leaf's jobs in one call, then attach
        # each node ONCE (bottom-up by construction order: leaves insert
        # before the parents they create), instead of a heap push a job
        # and re-keying ancestors per job.
        by_leaf: dict[str, list] = {}
        for job in jobs:
            by_leaf.setdefault(job.queue_id, []).append(job)
        for qid, leaf_jobs in by_leaf.items():
            self._leaf(qid, leaf_jobs)
        for node in list(self._nodes.values()):
            if node.live():
                self._attach(node)

    # -- tree construction -------------------------------------------------
    def _leaf(self, qid: str, jobs: Iterable = ()) -> _QueueNode:
        """The leaf node of ``qid``, made on first use with ``jobs`` as
        the jobs it starts with."""
        node = self._nodes.get(qid)
        if node is None:
            node = _QueueNode(qid, is_leaf=True)
            if self.victim_mode:
                # createLeafNode: victims pop in REVERSE job order (the
                # weakest claim — newest / lowest priority — first).
                job_less = lambda a, b: self.ssn.compare_jobs(a, b) > 0
            else:
                job_less = lambda a, b: self.ssn.compare_jobs(a, b) < 0
            node.jobs = PriorityQueue(
                job_less, self._max_jobs, key=self._job_key,
                largest_key_first=self.victim_mode, items=jobs)
            self._nodes[qid] = node
            self._link_parent(node)
        return node

    def _link_parent(self, node: _QueueNode) -> None:
        queue = self.ssn.cluster.queues.get(node.qid)
        parent_id = queue.parent if queue is not None else None
        if parent_id and parent_id in self.ssn.cluster.queues:
            parent = self._nodes.get(parent_id)
            if parent is None:
                parent = _QueueNode(parent_id, is_leaf=False)
                self._nodes[parent_id] = parent
                self._link_parent(parent)
            node.parent = parent

    # -- node ordering (buildNodeOrderFn) ----------------------------------
    def _best_job(self, node: _QueueNode):
        """Best descendant job of the subtree (getBestJobFromNode)."""
        while not node.is_leaf:
            child = self._peek_node(node.children)
            if child is None:
                return None, None
            node = child
        jobs = node.jobs
        job = jobs.peek() if jobs is not None and not jobs.empty() else None
        return job, node.qid

    def _node_less(self, l: _QueueNode, r: _QueueNode) -> bool:
        l_job, l_qid = self._best_job(l)
        r_job, r_qid = self._best_job(r)
        if self.victim_mode:
            # getVictimsForQueue: the comparison context is the popped
            # victims plus the next candidate, with no pending job; the
            # queue order is REVERSED (buildNodeOrderFn reverseOrder) so
            # the least deserving queue yields victims first.
            l_victims = list(self.victims_by_queue.get(l_qid) or ())
            r_victims = list(self.victims_by_queue.get(r_qid) or ())
            if l_job is not None:
                l_victims.append(l_job)
            if r_job is not None:
                r_victims.append(r_job)
            return self.ssn.compare_queues(
                l.qid, r.qid, None, None, l_victims, r_victims) > 0
        return self.ssn.compare_queues(
            l.qid, r.qid, l_job, r_job,
            self.victims_by_queue.get(l_qid),
            self.victims_by_queue.get(r_qid)) < 0

    def _entry(self, node: _QueueNode) -> _NodeEntry:
        key = None
        if self._queue_key is not None:
            best, _ = self._best_job(node)
            key = self._queue_key(node.qid, best)
        return _NodeEntry(node, key, self._node_less,
                          next(self._counter))

    def _attach(self, node: _QueueNode) -> None:
        """(Re-)insert the node into its parent's heap with a fresh key;
        any older heap entry goes stale via the token bump."""
        node.token += 1
        node.attached = True
        heap = self._roots if node.parent is None else node.parent.children
        heapq.heappush(heap, self._entry(node))

    def _detach(self, node: _QueueNode) -> None:
        node.attached = False
        node.token += 1

    def _peek_node(self, heap: list) -> "_QueueNode | None":
        while heap:
            entry = heap[0]
            if entry.node.attached and entry.token == entry.node.token \
                    and entry.node.live():
                return entry.node
            heapq.heappop(heap)   # stale or empty: lazy delete
        return None

    # -- public API --------------------------------------------------------
    def empty(self) -> bool:
        return self._peek_node(self._roots) is None

    def pop_next_job(self) -> PodGroupInfo | None:
        """Pop the best job of the best root-to-leaf path; the leaf
        leaves the tree until push_job/requeue_queue re-inserts it, and
        its ancestors re-enter their heaps with fresh ordering keys."""
        node = self._peek_node(self._roots)
        if node is None:
            return None
        while not node.is_leaf:
            child = self._peek_node(node.children)
            if child is None:
                self._detach(node)
                return self.pop_next_job()
            node = child
        job = node.jobs.pop()
        if self.victim_mode:
            # Popped victims join the comparator context
            # (poppedJobsByQueue, getVictimsForQueue).
            self.victims_by_queue.setdefault(node.qid, []).append(job)
        self._detach(node)
        self._refresh_ancestors(node)
        return job

    def _refresh_ancestors(self, node: _QueueNode) -> None:
        """Re-key every ancestor (markAncestorsForReorder analog): its
        best-descendant context changed, so its heap position must too."""
        anc = node.parent
        while anc is not None:
            if anc.live():
                self._attach(anc)
            else:
                self._detach(anc)
            anc = anc.parent

    def push_job(self, job: PodGroupInfo) -> None:
        """Enqueue a job (initial build, or elastic next chunk) and
        attach its leaf's ancestor chain."""
        node = self._leaf(job.queue_id)
        node.jobs.push(job)
        self._attach(node)
        self._refresh_ancestors(node)

    def requeue_queue(self, qid: str) -> None:
        node = self._nodes.get(qid)
        if node is None:
            return
        if node.is_leaf and node.jobs is not None \
                and not node.jobs.empty():
            self._attach(node)
        self._refresh_ancestors(node)

    def release(self) -> None:
        """Drop the tree of an order that is done with.  Nodes, heap
        entries and the comparators they hold refer to one another and to
        this object, so an order abandoned with jobs still on its heaps
        (a victim stream is read a fiftieth of the way) would keep every
        entry and key until the cyclic collector's next full pass; taken
        apart, they go with their last reference."""
        for node in self._nodes.values():
            node.parent = node.jobs = None
            node.children = []
        self._nodes = {}
        self._roots = []
