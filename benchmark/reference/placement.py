"""Plain sequential reference of one gang's placement.

Written from the semantics the configuration states (KAI-Scheduler's
allocate action with its default plugins: bin-packing node order,
resource-type and availability scores, gang all-or-nothing, topology
domains most-packed-first), in numpy and float64, one pod at a time.  It
imports nothing of ``kai_scheduler_tpu`` and takes nothing the program has
made: its inputs are the client's ledger and the job as submitted.

Score of a feasible node for one pod (``scores.go`` magnitudes):
  bin-pack  9 * (1 - (free - min_free) / (max_free - min_free)) over the
            feasible nodes that have the pod's dominant resource (GPUs for
            a pod that asks for any, else CPU); 9 for all when they tie
  type      10 where the node's kind (GPU or CPU-only) matches the pod's
  available 100 where the pod fits on idle resources now
The first node in name order wins a tie.
"""

from __future__ import annotations

import numpy as np

CPU, MEM, GPU = 0, 1, 2
EPS = 1e-9


def node_scores(capacity_t, idle_t, room, req, extra=None):
    """(feasible [N] bool, score [N]) of one pod against the fleet's state.

    ``capacity_t`` and ``idle_t`` are [3,N] (resource-major).
    """
    fits = ((req[CPU] <= idle_t[CPU] + EPS) & (req[MEM] <= idle_t[MEM] + EPS)
            & (req[GPU] <= idle_t[GPU] + EPS))
    feasible = (room >= 1.0) & fits
    gpu_pod = req[GPU] > 0
    res = GPU if gpu_pod else CPU
    free = idle_t[res]
    has_res = capacity_t[res] > 0
    valid = feasible & has_res
    score = np.where(fits, 100.0, 0.0)
    if valid.any():
        lo, hi = free[valid].min(), free[valid].max()
        if hi - lo <= 0:
            score = score + np.where(has_res, 9.0, 0.0)
        else:
            score = score + np.where(
                has_res, 9.0 * (1.0 - (free - lo) / (hi - lo)), 0.0)
    score = score + np.where((capacity_t[GPU] > 0) == gpu_pod, 10.0, 0.0)
    if extra is not None:
        score = score + extra
    return feasible, score


def place_gang(capacity, used, pods, max_pods, reqs, subset=None,
               extra=None):
    """Greedy placement of pods ``reqs`` [T,3], in order, each against the
    state the pods before it left.

    Returns [T] node indices, or None where a pod fits nowhere (the gang
    then binds nothing).  ``subset`` [N] bool limits the candidate nodes;
    ``extra`` [N] is added to every pod's score (the topology tier).
    """
    if subset is not None:
        # Nodes outside the subset are never candidates: leave them out.
        inside = np.nonzero(subset)[0]
        placed = place_gang(
            capacity[inside], used[inside], pods[inside], max_pods, reqs,
            extra=None if extra is None else extra[inside])
        return None if placed is None else inside[placed]
    capacity_t = np.ascontiguousarray(capacity.T)
    idle_t = np.ascontiguousarray((capacity - used).T)
    room = (max_pods - pods).astype(np.float64)
    out = np.empty(len(reqs), np.int64)
    for t, req in enumerate(reqs):
        feasible, score = node_scores(capacity_t, idle_t, room, req, extra)
        if not feasible.any():
            return None
        best = int(np.argmax(np.where(feasible, score, -np.inf)))
        out[t] = best
        idle_t[:, best] -= req
        room[best] -= 1.0
    return out


def place_gang_stale(capacity, used, pods, max_pods, reqs, subset=None):
    """The control: every pod scored against the state BEFORE the gang, as
    one batched [T,N] scoring would, so that no pod sees what the pods
    before it took.  Equal pods then pile onto one node, past its
    capacity.  This is the shortcut that tempts: it drops the guarantee
    that no node is filled past its capacity or its pod room."""
    capacity_t = np.ascontiguousarray(capacity.T)
    idle_t = np.ascontiguousarray((capacity - used).T)
    room = (max_pods - pods).astype(np.float64)
    out = np.empty(len(reqs), np.int64)
    memo = {}
    for t, req in enumerate(reqs):
        key = req.tobytes()
        if key not in memo:
            feasible, score = node_scores(capacity_t, idle_t, room, req)
            if subset is not None:
                feasible = feasible & subset
            if not feasible.any():
                return None
            memo[key] = int(np.argmax(np.where(feasible, score, -np.inf)))
        out[t] = memo[key]
    return out


def topology_candidates(capacity, used, pods, max_pods, reqs, levels,
                        level_order, required, preferred):
    """Node subsets to try, in order, for a gang with a topology
    constraint: every domain, from the preferred level up to the required
    one, whose free resources hold the gang's total request and whose
    nodes hold as many largest-pods as the gang has pods; fullest first
    (largest share of its free resources requested), then by name.

    ``levels``: level -> [N] domain id; ``level_order``: levels from the
    widest to the narrowest, as the Topology lists them.
    """
    free = capacity - used
    room = max_pods - pods
    total = reqs.sum(axis=0)
    largest = reqs.max(axis=0)
    gang = len(reqs)
    with np.errstate(divide="ignore", invalid="ignore"):
        per_res = np.where(largest[None, :] > 0,
                           np.floor(free / np.where(largest > 0, largest,
                                                    1.0)[None, :]), np.inf)
    stack = np.clip(np.minimum(per_res.min(axis=1), room), 0, gang)

    walk, collecting = [], False
    for level in list(reversed(level_order)) + [None]:   # None = the root
        if level is not None and level in (preferred, required):
            collecting = True
        if collecting:
            walk.append(level)
        if level is not None and level == required:
            break
    out = []
    for rank, level in enumerate(walk):
        seg = (np.zeros(len(capacity), np.int64) if level is None
               else levels[level])
        for dom in range(int(seg.max()) + 1):
            inside = seg == dom
            if stack[inside].sum() < gang:
                continue
            dom_free = free[inside].sum(axis=0)
            if np.any(total > dom_free + EPS):
                continue
            ratio = max((total[r] / dom_free[r] if dom_free[r] > 0 else 1e9)
                        for r in range(3) if total[r] > 0)
            name = "root" if level is None else f"{level}{dom:05d}"
            out.append((rank, -ratio, name, inside))
    out.sort(key=lambda c: (c[0], c[1], c[2]))
    return out


def preferred_boost(candidates, n):
    """[N] topology-tier score for nodes of preferred-level domains that
    can hold the gang: 10000 / (rank + 1), best-ranked domain first."""
    boost = np.zeros(n)
    rank = 0
    for level_rank, _ratio, _name, inside in candidates:
        if level_rank == 0:
            boost = np.maximum(boost, inside * (10000.0 / (rank + 1)))
            rank += 1
    return boost


def queue_admits(queue, req_total, parent, limit, queue_used):
    """A job is admitted while no queue from its leaf to the root would
    pass its limit on a resource the job asks for."""
    q = queue
    while q is not None:
        over = (req_total > EPS) & (limit[q] < queue_used[q] + req_total - EPS)
        if over.any():
            return False
        q = parent[q]
    return True


def schedule_gang(capacity, used, pods, max_pods, reqs, topology, levels,
                  level_order):
    """Node index per pod for one gang, or None where it cannot bind."""
    if not topology:
        return place_gang(capacity, used, pods, max_pods, reqs)
    cands = topology_candidates(
        capacity, used, pods, max_pods, reqs, levels, level_order,
        topology.get("required"), topology.get("preferred"))
    boost = (preferred_boost(cands, len(capacity))
             if topology.get("preferred") else None)
    for _rank, _ratio, _name, inside in cands:
        placed = place_gang(capacity, used, pods, max_pods, reqs,
                            subset=inside, extra=boost)
        if placed is not None:
            return placed
    return None
