"""Plain reference of what one cycle of cross-queue reclaim may do.

Written from the guarantees the configuration states (KAI-Scheduler's
reclaim action: a job of a queue under its fair share takes resources from
preemptible jobs of OTHER queues that stand over theirs; a gang is whole or
not at all, the reclaimer's and every victim's; nothing is taken that the
reclaimer does not need), in numpy and plain Python.  It imports nothing of
``kai_scheduler_tpu`` and takes nothing the program has made: its inputs
are the client's ledger, its book of the jobs it submitted, and the
configuration's queue tree.  Every answer is exact, so every limit is 0.

Grown from ``benchmark/tests/data/tiny/reference/eviction.py``, which the
fixture benchmark keeps: the first four functions are that file's.
"""

from __future__ import annotations

import math

import numpy as np

EPS = 1e-9


def nodes_over_capacity(capacity, used, pods, max_pods) -> int:
    """Nodes past their cpu, memory, GPUs or pod room."""
    over = np.any(used > capacity + EPS, axis=1) | (pods > max_pods)
    return int(over.sum())


def victim_faults(victims, reclaimer_queue) -> dict:
    """``victims``: [(queue, preemptible)] of every pod a cycle evicted.
    A victim has to be preemptible and of another queue than the
    reclaimer's; with no reclaimer pending nothing may be evicted."""
    return {
        "victims_not_preemptible": sum(1 for _q, p in victims if not p),
        "victims_from_own_queue": sum(
            1 for q, _p in victims if q == reclaimer_queue),
        "evictions_without_reclaimer":
            len(victims) if reclaimer_queue is None else 0}


def gangs_left_below_minimum(running, evicted, minimum) -> int:
    """Jobs that a cycle's evictions left with some pods running but
    fewer than their gang's minimum: below it a victim goes whole.
    ``running``, ``evicted``, ``minimum``: job -> count of pods."""
    left = 0
    for job, gone in evicted.items():
        rest = running[job] - gone
        left += 0 < rest < minimum[job]
    return left


def gang_faults(bound: int, size: int) -> dict:
    """A gang binds all its pods in one cycle or none."""
    return {"gangs_partly_bound": int(0 < bound < size)}


def pods_that_fit(capacity, used, pods, max_pods, gang_req) -> np.ndarray:
    """[T] bool: which of the gang's pods find room on what is idle, pods
    of one request together, each node taking as many as it holds."""
    free = capacity - used
    room = (max_pods - pods).astype(np.int64)
    placed = np.zeros(len(gang_req), bool)
    for req in np.unique(gang_req, axis=0):
        rows = np.flatnonzero(np.all(gang_req == req, axis=1))
        asks = req > 0
        holds = np.floor(np.min((free[:, asks] + EPS) / req[asks], axis=1)) \
            if asks.any() else np.full(len(free), len(rows))
        holds = np.maximum(np.minimum(holds, room), 0).astype(np.int64)
        take = np.minimum(holds, np.maximum(
            0, len(rows) - np.concatenate(([0], np.cumsum(holds)[:-1]))))
        placed[rows[:int(take.sum())]] = True
        free = free - take[:, None] * req
        room = room - take
    return placed


def fewest_evictions(capacity, used, pods, max_pods, gang_req,
                     victim_req) -> int:
    """The fewest pods whose release lets the gang fit: what the gang asks
    beyond what is idle for it (``used`` and ``pods`` are the ledger the
    gang met), over the most one victim releases, resource by resource.
    Exact where victims and pods ask the same of one resource, as one-GPU
    victims and one-GPU pods do; elsewhere a lower bound, which is the
    side a limit of 0 may err on."""
    placed = pods_that_fit(capacity, used, pods, max_pods, gang_req)
    short = gang_req[~placed].sum(axis=0)
    most = victim_req.max(axis=0)
    need = [math.ceil(short[r] / most[r] - EPS)
            for r in range(len(short)) if short[r] > EPS and most[r] > 0]
    return max(need, default=0)


def deserved_share(total, departments: int, leaves_per_department: int,
                   leaf: bool) -> np.ndarray:
    """What the configuration's queue tree gives a queue of the fleet's
    ``total`` [3]: equal shares among departments, and among a
    department's leaves."""
    share = 1.0 / departments
    if leaf:
        share /= leaves_per_department
    return np.asarray(total, float) * share


def victim_queue_below_quota(deserved, used_before, lost, reclaimer_queue,
                             reclaimer_asks) -> int:
    """Faults of a cycle's evictions against the quotas.  ``lost``: queue
    -> [V,3], the requests of the pods the cycle evicted from it.  One
    fault for every queue that lost a pod while it stood at or under its
    deserved share of every resource (with all but its largest loss taken
    it has to be over its share of one: the last pod may cross the line,
    no pod may be taken from below it); and one where anything was taken
    for a reclaimer whose queue, with all that its gangs of the cycle ask
    (``reclaimer_asks`` [3]), stands over its own deserved share."""
    faults = 0
    for queue, took in lost.items():
        took = np.asarray(took, float)
        before_last = used_before[queue] - took.sum(axis=0) \
            + took.max(axis=0)
        faults += bool(np.all(before_last <= deserved[queue] + EPS))
    if lost and reclaimer_queue is not None:
        stands = used_before[reclaimer_queue] + reclaimer_asks
        faults += bool(np.any(stands > deserved[reclaimer_queue] + EPS))
    return faults
