"""Plain reference of what one cycle of in-queue preemption may do.

Written from the guarantees the configuration ``preempt-98k`` states
(KAI-Scheduler's preempt action, ``preempt.go:126-155``: a pending job
takes resources from running jobs of its OWN queue that are preemptible
and of strictly lower priority; a gang is whole or not at all, the
preemptor's and every victim's; the victims are taken in the order lowest
priority, then newest, a job's surplus before its core gang, and no more of
them than seat the preemptor; a non-preemptible job stays inside its
queue's deserved share), in numpy and plain Python, float64.  It imports
nothing of ``kai_scheduler_tpu`` and takes nothing the program has made:
its inputs are the client's ledger, its book of the jobs it submitted, the
writes it read back in their order, and the configuration's queue tree.
Every answer is a whole number of pods, jobs, nodes or queues, so every
limit is 0.

The first four functions are ``reference/eviction.py``'s, which the
reclaim cells keep.
"""

from __future__ import annotations

import math

import numpy as np

EPS = 1e-9


def nodes_over_capacity(capacity, used, pods, max_pods) -> int:
    """Nodes past their cpu, memory, GPUs or pod room."""
    over = np.any(used > capacity + EPS, axis=1) | (pods > max_pods)
    return int(over.sum())


def gangs_left_below_minimum(running, evicted, minimum) -> int:
    """Jobs that a cycle's evictions left with some pods running but
    fewer than their gang's minimum: below it a victim goes whole.
    ``running``, ``evicted``, ``minimum``: job -> count of pods."""
    left = 0
    for job, gone in evicted.items():
        rest = running[job] - gone
        left += 0 < rest < minimum[job]
    return left


def gang_faults(count: int, size: int) -> int:
    """A gang gets all its pods in one step (a bind, a commit's
    nominations) or none."""
    return int(0 < count < size)


def deserved_share(total, departments: int, leaves_per_department: int,
                   leaf: bool) -> np.ndarray:
    """What the configuration's queue tree gives a queue of the fleet's
    ``total`` [3]: equal shares among departments, and among a
    department's leaves."""
    share = 1.0 / departments
    if leaf:
        share /= leaves_per_department
    return np.asarray(total, float) * share


def may_be_taken(victim_queue: str, preemptible: bool, priority: float,
                 preemptor_queue: str, preemptor_priority: float) -> bool:
    """``preempt.go:126-155``: a victim is of the preemptor's own queue,
    preemptible, and of STRICTLY lower priority."""
    return (victim_queue == preemptor_queue and bool(preemptible)
            and priority < preemptor_priority)


def victim_faults(victims, preemptor) -> dict:
    """``victims``: [(queue, preemptible, priority)] of every pod one
    commit evicted; ``preemptor``: (queue, priority) of the job the commit
    seated, or None where it seated none (then only preemptibility can be
    judged, and the cycle's other counts say the rest)."""
    out = {"victims_not_preemptible": sum(1 for _q, p, _y in victims
                                          if not p),
           "victims_from_other_queue": 0, "victims_not_lower_priority": 0}
    if preemptor is not None:
        queue, priority = preemptor
        out["victims_from_other_queue"] = sum(
            1 for q, _p, _y in victims if q != queue)
        out["victims_not_lower_priority"] = sum(
            1 for _q, _p, y in victims if not y < priority)
    return out


def victim_order(jobs) -> list:
    """``jobs``: [(uid, priority, created)]; upstream's order of victims:
    lowest priority first, then newest.  No two jobs of the book share a
    creation time."""
    return [uid for uid, _p, _c in
            sorted(jobs, key=lambda j: (j[1], -j[2]))]


def victim_steps(pods: dict, minimum: int) -> list:
    """The steps in which a job is taken: its surplus over the gang's
    minimum first (the last pods by name), then its core gang; a job at or
    under its minimum goes in one step.  ``pods``: name -> node."""
    names = sorted(pods)
    if len(names) > minimum > 0:
        return [names[minimum:], names[:minimum]]
    return [names]


def seats(free, room, gang_req, nodes=None) -> bool:
    """Does every pod of the gang find a node: the largest requests first,
    each on the first node (of ``nodes``, or of the fleet) that holds it.
    Exact where the pods that differ are whole-node pods, as a replica's
    are: no two of them share a node, so no choice of node can hurt.
    ``free`` [N,3] and ``room`` [N] are not changed."""
    idx = np.arange(len(free)) if nodes is None \
        else np.asarray(nodes, np.int64)
    free, room = free[idx].copy(), room[idx].copy()
    order = sorted(range(len(gang_req)),
                   key=lambda t: tuple(-gang_req[t][::-1]))
    for t in order:
        fits = np.flatnonzero(
            np.all(free >= gang_req[t] - EPS, axis=1) & (room > 0))
        if not fits.size:
            return False
        free[fits[0]] -= gang_req[t]
        room[fits[0]] -= 1
    return True


def reference_victims(capacity, used, pods, max_pods, gang_req,
                      candidates):
    """The pods upstream's solver takes for one preemptor: walk the
    ``candidates`` ([(uid, minimum, {pod: node}, req [3])], already in
    ``victim_order``) a step at a time, releasing each step's pods, and
    stop at the first step after which the gang is seated.  Returns the set
    of pod names, empty where the gang is seated with nothing released,
    None where no prefix seats it (then nothing may be evicted for it).

    A pod can only come to fit on a node that had room for the smallest
    request before the walk or on which something was released, so those
    nodes alone are tried again at every step."""
    free = capacity - used
    room = (max_pods - pods).astype(np.int64)
    least = gang_req.min(axis=0)
    tried = set(np.flatnonzero(
        np.all(free >= least - EPS, axis=1) & (room > 0)).tolist())
    if seats(free, room, gang_req, sorted(tried)):
        return set()
    taken = set()
    for _uid, minimum, job_pods, req in candidates:
        for step in victim_steps(job_pods, minimum):
            for name in step:
                node = job_pods[name]
                free[node] += req
                room[node] += 1
                tried.add(node)
            taken.update(step)
            if seats(free, room, gang_req, sorted(tried)):
                return taken
    return None


def victims_by_node(nodes: int, victim_node, victim_req) -> tuple:
    """(count [N], most [N,3]) of the pods that may be taken
    (``victim_node`` [V], ``victim_req`` [V,3]): how many stand on each
    node, and the most one of them releases there, resource by resource."""
    victim_node = np.asarray(victim_node, np.int64)
    count = np.bincount(victim_node, minlength=nodes)
    most = np.zeros((nodes, 3))
    np.maximum.at(most, victim_node,
                  np.asarray(victim_req, float).reshape(-1, 3))
    return count, most


def fewest_evictions(capacity, used, pods, max_pods, gang_req, count,
                     most) -> float:
    """The fewest pods whose release seats the gang, whatever their order:
    the pods of the gang largest first, each on the node where the fewest
    of the victims there (``victims_by_node``) have to go for it to fit
    beside what is free, counted resource by resource against the most one
    victim of that node releases.  Exact where a node's victims ask alike,
    as the one-GPU pods of a training job do; elsewhere a lower bound,
    which is the side a limit of 0 may err on.  ``math.inf`` where the
    gang cannot be seated.  ``count`` is not changed."""
    free = capacity - used
    room = (max_pods - pods).astype(np.int64)
    count = np.array(count, np.int64)
    total = 0
    order = sorted(range(len(gang_req)),
                   key=lambda t: tuple(-gang_req[t][::-1]))
    for t in order:
        short = np.maximum(gang_req[t] - free, 0.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            need = np.where(short > EPS, np.ceil(short / most - EPS), 0.0)
        need = np.nan_to_num(need, nan=np.inf, posinf=np.inf).max(axis=1)
        need = np.where((need <= count) & (room + need > 0), need, np.inf)
        node = int(np.argmin(need))
        if not np.isfinite(need[node]):
            return math.inf
        k = int(need[node])
        total += k
        free[node] = free[node] + k * most[node] - gang_req[t]
        room[node] += k - 1
        count[node] -= k
    return total


def queues_over(holds: dict, bound: dict) -> int:
    """Queues that hold more of any resource than their ``bound`` (a
    limit, or for non-preemptible work the deserved share).  ``holds``,
    ``bound``: queue -> [3]."""
    return sum(bool(np.any(np.asarray(held) > bound[q] + EPS))
               for q, held in holds.items())
