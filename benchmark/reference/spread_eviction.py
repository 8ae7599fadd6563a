"""Plain reference of cross-queue reclaim on a shard that SPREADS: what one
cycle may evict, and where the gang it bound has to land.

Written from the guarantees the configuration states, in numpy (float64)
and plain Python.  It imports nothing of ``kai_scheduler_tpu`` and takes
nothing the program has made: its inputs are the client's ledger, its book
of the jobs it submitted, and the configuration's queue tree.

Who may be evicted and how many is ``reference/eviction.py``'s (KAI-
Scheduler's reclaim action: a job of a queue under its fair share takes
resources from preemptible jobs of OTHER queues that stand over theirs; a
gang is whole or not at all, the reclaimer's and every victim's; nothing
is taken that the reclaimer does not need): the functions down to
``victim_queue_below_quota`` are that file's, ``fewest_evictions`` with
one repair (below).  Where the gang lands is this file's: a strategy is
where pods land.

Score of a feasible node for one pod (``scores.go`` magnitudes, the
``nodeplacement`` plugin's ``spread.go:16-37``):
  spread    free / capacity of the pod's dominant resource (GPUs for a pod
            that asks for any, else CPU) on nodes that have the resource:
            at most 1
  type      10 where the node's kind (GPU or CPU-only) matches the pod's
  available 100 where the pod fits on idle resources now
The first node in name order wins a tie.  GPUs, milli-cores and bytes are
whole numbers, exact in f32 and f64 alike, and on a fleet of one node
shape two nodes' free shares are equal or differ by at least one unit over
the capacity (1/8 for GPUs here): every answer is exact, every limit 0.
"""

from __future__ import annotations

import math

import numpy as np

CPU, MEM, GPU = 0, 1, 2
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


def pods_that_fit(capacity, used, pods, max_pods, gang_req):
    """([T] bool, [N,3]): which of the gang's pods find room on what is
    idle, pods of one request together, each node taking as many as it
    holds; and what stays idle once they sit."""
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
    return placed, free


def fewest_evictions(capacity, used, pods, max_pods, gang_req,
                     victim_req) -> int:
    """The fewest pods whose release lets the gang fit: what the pods
    that found no idle room ask, beyond what stays idle in the fleet
    (``used`` and ``pods`` are the ledger the gang met), over the most one
    victim releases, resource by resource.  ``reference/eviction.py``
    leaves the idle rest out, which is right where every pod asks what a
    victim releases; a master that asks twice a victim's cpu beside idle
    cpu on every victim's node would read one eviction more than the gang
    needs, and a limit of 0 would pass a cycle that took one too many.
    Exact where one resource binds and victims and pods ask the same of
    it, as one-GPU victims and one-GPU pods do on a fleet with no idle
    GPU; elsewhere a lower bound, the side a limit of 0 may err on."""
    placed, free = pods_that_fit(capacity, used, pods, max_pods, gang_req)
    short = gang_req[~placed].sum(axis=0) - free.sum(axis=0)
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


# -- where the gang lands ------------------------------------------------------
def spread_scores(capacity_t, idle_t, room, req):
    """(feasible [N] bool, score [N]) of one pod against the fleet's
    state; ``capacity_t`` and ``idle_t`` are [3,N] (resource-major)."""
    fits = ((req[CPU] <= idle_t[CPU] + EPS) & (req[MEM] <= idle_t[MEM] + EPS)
            & (req[GPU] <= idle_t[GPU] + EPS))
    feasible = (room >= 1.0) & fits
    gpu_pod = req[GPU] > 0
    res = GPU if gpu_pod else CPU
    cap = capacity_t[res]
    has_res = cap > 0
    score = np.where(has_res, idle_t[res] / np.where(has_res, cap, 1.0), 0.0)
    score = score + np.where((capacity_t[GPU] > 0) == gpu_pod, 10.0, 0.0)
    score = score + np.where(fits, 100.0, 0.0)
    return feasible, score


def place_gang(capacity, used, pods, max_pods, reqs):
    """Upstream's spread order for pods ``reqs`` [T,3], in order, each
    against the state the pods before it left: [T] node indices, or None
    where a pod fits nowhere (the gang then binds nothing)."""
    capacity_t = np.ascontiguousarray(capacity.T)
    idle_t = np.ascontiguousarray((capacity - used).T)
    room = (max_pods - pods).astype(np.float64)
    out = np.empty(len(reqs), np.int64)
    for t, req in enumerate(reqs):
        feasible, score = spread_scores(capacity_t, idle_t, room, req)
        if not feasible.any():
            return None
        best = int(np.argmax(np.where(feasible, score, -np.inf)))
        out[t] = best
        idle_t[:, best] -= req
        room[best] -= 1.0
    return out


def placements_not_reference(capacity, used, pods, max_pods, reqs,
                             nodes) -> int:
    """Pods of a bound gang (``nodes`` [T], in the order of ``reqs``)
    that are not on the node ``place_gang`` gives them from the ledger
    before the bind; every pod where the reference would bind none."""
    want = place_gang(capacity, used, pods, max_pods, reqs)
    if want is None:
        return len(reqs)
    return int((want != np.asarray(nodes)).sum())
