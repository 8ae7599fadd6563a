"""Plain reference of what one commit of cross-queue reclaim may do for a
gang that must lie inside ONE topology domain.

Written from the guarantees the configuration states (KAI-Scheduler's
reclaim action under the topology plugin's ``SubsetNodesFn``: a job of a
queue under its fair share takes resources from preemptible jobs of other
queues that stand over theirs; the victims are tried in upstream's order,
lowest priority and then newest, a step at a time, and the first prefix
whose release seats the whole gang inside one domain of its required level
is the one evicted; what of that prefix the gang does not need is placed
again by the same scenario), in numpy and plain Python, float64.  It
imports nothing of ``kai_scheduler_tpu`` and takes nothing the program has
made: its inputs are the client's ledger, its book of the jobs it
submitted, the nodes' domains as the client labelled them, and the
configuration's queue tree.  Every answer is a whole number, so every
limit is 0.

``eviction.py``'s kin with one more axis, written apart: a state is a fleet
AND its split into domains, and "the gang fits" is asked of one domain at
a time, by segment sums first and pod by pod where the sums allow it.
"""

from __future__ import annotations

import numpy as np

EPS = 1e-9
GPU = 2


def nodes_over_capacity(capacity, used, pods, max_pods) -> int:
    """Nodes past their cpu, memory, GPUs or pod room."""
    over = np.any(used > capacity + EPS, axis=1) | (pods > max_pods)
    return int(over.sum())


def victim_order(priority, created) -> np.ndarray:
    """Upstream's victim order inside one queue: the lowest priority
    first, among equals the newest.  Indices into the two arrays."""
    return np.lexsort((-np.asarray(created, float),
                       np.asarray(priority, float)))


def victim_faults(victims, reclaimer_queue) -> dict:
    """``victims``: [(queue, preemptible)] of every pod a commit evicted.
    A victim has to be preemptible and of another queue than the
    reclaimer's; with no reclaimer nothing may be evicted."""
    return {
        "victims_not_preemptible": sum(1 for _q, p in victims if not p),
        "victims_from_own_queue": sum(
            1 for q, _p in victims if q == reclaimer_queue),
        "evictions_without_reclaimer":
            len(victims) if reclaimer_queue is None else 0}


def gangs_left_below_minimum(running, gone, minimum) -> int:
    """Jobs left with some pods but fewer than their gang's minimum.
    ``running``, ``gone``, ``minimum``: job -> count of pods."""
    return sum(0 < running[job] - lost < minimum[job]
               for job, lost in gone.items())


def domains_apart(nodes, seg) -> int:
    """How many domains beyond one the nodes lie in (0: all in one); a
    node of no domain (``seg`` -1) is a domain apart from every other."""
    doms = np.asarray(seg)[np.asarray(nodes, np.int64)]
    return len(set(doms[doms >= 0].tolist())) + int((doms < 0).sum()) - 1 \
        if len(doms) else 0


def seats_inside(free, room, gang_req) -> bool:
    """Whether the gang's pods [T,3] can all be placed on the nodes whose
    free resources are ``free`` [n,3] and pod room ``room`` [n]: pod by
    pod in the gang's order, each on the fullest node that holds it (the
    least free GPUs, then the first), as bin-pack places them."""
    free = np.array(free, float)
    room = np.array(room, np.int64)
    for req in gang_req:
        fits = np.all(free + EPS >= req, axis=1) & (room >= 1)
        if not fits.any():
            return False
        best = int(np.flatnonzero(fits)[np.argmin(free[fits, GPU])])
        free[best] -= req
        room[best] -= 1
    return True


def first_seating_prefix(capacity, used, pods, max_pods, seg, steps,
                         gang_req, allowed=None):
    """``(steps taken, domain)`` of the smallest prefix of ``steps`` whose
    release seats the gang inside one domain, or ``(None, None)``.

    ``steps``: [(nodes [m], reqs [m,3])] in upstream's order, what each
    step releases and where.  ``seg`` [N]: the domain of every node at the
    gang's required level, -1 for none.  The prefix grows a step at a
    time; per state a domain is tried only where its free sums cover the
    gang's whole request (segment sums, kept as running totals), and then
    pod by pod (``seats_inside``).  ``allowed``: the domains the gang may
    use, or None for all.  Zero steps where a domain seats it as it is."""
    seg = np.asarray(seg, np.int64)
    free = np.asarray(capacity, float) - np.asarray(used, float)
    room = int(max_pods) - np.asarray(pods, np.int64)
    member = seg >= 0
    d = int(seg.max()) + 1 if member.any() else 0
    sums = np.zeros((d, free.shape[1]))
    np.add.at(sums, seg[member], free[member])
    total = gang_req.sum(axis=0)
    nodes_of = {}

    def seated(dom: int) -> bool:
        if allowed is not None and dom not in allowed:
            return False
        if np.any(total > sums[dom] + EPS):
            return False
        if dom not in nodes_of:
            nodes_of[dom] = np.flatnonzero(seg == dom)
        inside = nodes_of[dom]
        return seats_inside(free[inside], room[inside], gang_req)

    for dom in np.flatnonzero(np.all(sums + EPS >= total, axis=1)).tolist():
        if seated(dom):
            return 0, dom
    for k, (nodes, reqs) in enumerate(steps):
        nodes = np.asarray(nodes, np.int64)
        np.add.at(free, nodes, reqs)
        np.add.at(room, nodes, 1)
        inside = seg[nodes] >= 0
        np.add.at(sums, seg[nodes[inside]], np.asarray(reqs)[inside])
        for dom in sorted(set(seg[nodes[inside]].tolist())):
            if seated(dom):
                return k + 1, dom
    return None, None


def deserved_share(total, departments: int, leaves_per_department: int,
                   leaf: bool) -> np.ndarray:
    """What the configuration's queue tree gives a queue of the fleet's
    ``total`` [3]: equal shares among departments, and among a
    department's leaves."""
    share = 1.0 / departments
    if leaf:
        share /= leaves_per_department
    return np.asarray(total, float) * share


def quota_faults(deserved, limit, used_before, lost, reclaimer_queue,
                 reclaimer_asks, parent) -> int:
    """Faults of one commit against the quotas.  ``lost``: queue -> [V,3],
    what the commit took from it for good (evicted and not placed again).
    One fault for every queue that lost a pod while it stood at or under
    its deserved share of every resource (the last pod may cross the
    line, none may be taken from below it); one where anything was taken
    for a reclaimer whose queue, with what its gang asks, stands over its
    deserved share; and one for every queue, from the reclaimer's leaf to
    its root, that the gang takes past its limit."""
    faults = 0
    for queue, took in lost.items():
        took = np.asarray(took, float)
        before_last = used_before[queue] - took.sum(axis=0) \
            + took.max(axis=0)
        faults += bool(np.all(before_last <= deserved[queue] + EPS))
    if reclaimer_queue is None:
        return faults
    stands = used_before[reclaimer_queue] + reclaimer_asks
    if lost:
        faults += bool(np.any(stands > deserved[reclaimer_queue] + EPS))
    queue = reclaimer_queue
    while queue is not None:
        faults += bool(np.any(used_before[queue] + reclaimer_asks
                              > limit[queue] + EPS))
        queue = parent[queue]
    return faults
