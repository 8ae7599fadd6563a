"""Plain reference of what one cycle of consolidation may do.

Written from the guarantee the configuration states (KAI-Scheduler's
consolidation action, ``consolidation.go`` ``allPodsReallocated``: to seat a
pending job that the fleet's idle resources would hold and no node can, the
scheduler may move running preemptible pods, and a solution counts only if
every pod it displaces is placed again: the running set never shrinks), in
numpy and plain Python.  It imports nothing of ``kai_scheduler_tpu`` and
takes nothing the program has made: its inputs are the client's ledger and
its book of the jobs it submitted.  Every answer is a whole number, so
every limit is 0.
"""

from __future__ import annotations

import numpy as np

EPS = 1e-9


def nodes_over_capacity(capacity, used, pods, max_pods) -> int:
    """Nodes past their cpu, memory, GPUs or pod room."""
    over = np.any(used > capacity + EPS, axis=1) | (pods > max_pods)
    return int(over.sum())


def gang_faults(bound: int, size: int) -> dict:
    """A gang binds all its pods in one cycle or none."""
    return {"gangs_partly_bound": int(0 < bound < size)}


def place_on_idle(capacity, used, pods, max_pods, gang_req) -> tuple:
    """([T] bool, [N,3]): which of the gang's pods find room on what is
    idle, pods of one request together, the largest request first, each
    node taking as many as it holds; and what is idle once they have."""
    free = capacity - used
    room = (max_pods - pods).astype(np.int64)
    placed = np.zeros(len(gang_req), bool)
    kinds = np.unique(gang_req, axis=0)
    for req in kinds[np.argsort(-kinds.sum(axis=1), kind="stable")]:
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


def pods_that_fit(capacity, used, pods, max_pods, gang_req) -> np.ndarray:
    """[T] bool: which of the gang's pods find room on what is idle."""
    return place_on_idle(capacity, used, pods, max_pods, gang_req)[0]


def move_faults(moved, placed) -> dict:
    """``moved``: [(pod, preemptible)] of every pod a cycle evicted;
    ``placed``: the pods for which the same cycle's commit pipelined a
    place.  Only a preemptible pod may be moved, and none without a place
    to land."""
    return {
        "moved_not_preemptible": sum(1 for _pod, p in moved if not p),
        "moved_without_place": sum(1 for pod, _p in moved
                                   if pod not in placed)}


def jobs_moved_in_part(running, moved) -> int:
    """Jobs of which a cycle moved some running pods and left others:
    a gang moves whole.  ``running``, ``moved``: job -> count of pods."""
    return sum(1 for job, gone in moved.items() if 0 < gone < running[job])


def moves_without_consolidator(moved: int, waiting_req, idle_total) -> int:
    """Pods moved in a cycle that had no business moving any: no gang was
    waiting (``waiting_req`` None), or the one that was asks more than the
    fleet has idle in all (``waiting_req`` [3] against ``idle_total`` [3]:
    moving pods frees nothing, so no move can seat it)."""
    if not moved:
        return 0
    if waiting_req is None:
        return moved
    return moved if np.any(np.asarray(waiting_req)
                           > np.asarray(idle_total) + EPS) else 0


def replacements_not_bound(replaced_by, bound_next) -> int:
    """Moved pods whose replacement was not bound by the end of the next
    cycle: the running set shrank.  ``replaced_by``: moved pod -> the pod
    its controller made in its place; ``bound_next``: the pods the next
    cycle bound."""
    return sum(1 for new in replaced_by.values() if new not in bound_next)


def fewest_moves(capacity, used, pods, max_pods, gang_req, movable_sum,
                 movable_largest) -> int:
    """The fewest pods whose leaving lets the gang fit, from the ledger
    the gang met (``used`` [N,3], ``pods`` [N]).  The gang's pods that
    find no idle room each lack a node; a node lacks, of every resource,
    what the smallest such pod asks beyond what is free there, and gives
    it up only if its movable pods hold that much together
    (``movable_sum`` [N,3]); it then costs at least that over the most
    one of its movable pods holds (``movable_largest`` [N,3]), resource
    by resource.  The answer is the sum of the cheapest such nodes, one
    for every pod that lacks one; 0 where there are too few, since no
    move can seat the gang then.  Exact where a node's movable pods are
    alike, as one-GPU fragments under whole-node pods are; elsewhere a
    lower bound."""
    placed, free = place_on_idle(capacity, used, pods, max_pods, gang_req)
    lacking = int((~placed).sum())
    if not lacking:
        return 0
    ask = gang_req[~placed].min(axis=0)
    short = np.maximum(ask[None, :] - free, 0.0)
    able = np.all(short <= movable_sum + EPS, axis=1)
    with np.errstate(divide="ignore", invalid="ignore"):
        per = np.where(short > EPS, short / movable_largest, 0.0)
    cost = np.ceil(np.max(per, axis=1) - EPS)
    cost = np.sort(cost[able & np.isfinite(cost)])
    if len(cost) < lacking:
        return 0
    return int(cost[:lacking].sum())
