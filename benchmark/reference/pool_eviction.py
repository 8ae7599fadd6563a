"""Plain reference of cross-queue reclaim on a fleet of node POOLS: which
nodes a pod may use, what one cycle may evict, and where the gang it bound
has to land.

Written from the guarantees the configuration states, in numpy (float64)
and plain Python.  It imports nothing of ``kai_scheduler_tpu`` and takes
nothing the program has made: its inputs are the client's ledger (with the
labels and taints it gave every node), its book of the jobs it submitted
with the constraints it put on their pods, and the configuration's queue
tree.

Which nodes a pod may use is kube-scheduler's, which KAI-Scheduler embeds
(``pkg/scheduler/k8s_internal/predicates``): ``nodeSelector`` (every pair
on the node), ``nodeAffinity.required`` (OR across nodeSelectorTerms, AND
across a term's matchExpressions; ``In``, ``NotIn``, ``Exists``,
``DoesNotExist``) and ``TaintToleration`` (every taint of the node
tolerated; taints and tolerations by key, as the configuration states
them), node by node from plain dicts and sets.

Who may be evicted and how many is ``reference/eviction.py``'s, read with
that row: only what is idle, and only what victims release, ON NODES THE
GANG MAY USE lets it fit.  Where the gang lands is ``reference/
placement.py``'s bin-pack order among the admitted nodes.  Both are this
configuration's own copies; those files stay as they are.

GPUs, milli-cores and bytes are whole numbers, exact in f32 and f64 alike,
and on a fleet of one node shape two nodes' free GPUs are equal or differ
by at least one: every answer is exact, every limit 0.
"""

from __future__ import annotations

import math

import numpy as np

CPU, MEM, GPU = 0, 1, 2
EPS = 1e-9


# -- which nodes a pod may use ---------------------------------------------
def expression_matches(expr: dict, labels: dict) -> bool:
    """One nodeSelectorRequirement against a node's labels."""
    key, op = expr["key"], expr["operator"]
    values = expr.get("values") or []
    if op == "In":
        return key in labels and labels[key] in values
    if op == "NotIn":
        return key not in labels or labels[key] not in values
    if op == "Exists":
        return key in labels
    if op == "DoesNotExist":
        return key not in labels
    raise ValueError(f"operator {op!r} is not one the configuration uses")


def affinity_matches(terms: list, labels: dict) -> bool:
    """``requiredDuringSchedulingIgnoredDuringExecution``: no terms admit
    every node; otherwise one term has to match, all its expressions
    together; a term with no expression matches nothing."""
    if not terms:
        return True
    return any(
        term.get("expressions") and all(
            expression_matches(e, labels) for e in term["expressions"])
        for term in terms)


def admitted(node_labels: list, node_taints: list, selector: dict,
             terms: list, tolerations) -> np.ndarray:
    """[N] bool: the nodes a pod with this ``nodeSelector``, these
    required node-affinity ``terms`` and these ``tolerations`` may use,
    node by node (``node_labels``: a dict a node, ``node_taints``: a set of
    taint keys a node)."""
    tolerations = set(tolerations)
    out = np.zeros(len(node_labels), bool)
    for i, (labels, taints) in enumerate(zip(node_labels, node_taints)):
        out[i] = (all(labels.get(k) == v for k, v in selector.items())
                  and affinity_matches(terms, labels)
                  and set(taints) <= tolerations)
    return out


def pods_outside(nodes, rows) -> int:
    """Pods on a node they may not use: ``nodes`` [T] node indices,
    ``rows`` [T,N] or [N] bool, what ``admitted`` gives each pod."""
    nodes = np.asarray(nodes, np.int64)
    rows = np.asarray(rows, bool)
    ok = rows[nodes] if rows.ndim == 1 else rows[np.arange(len(nodes)),
                                                  nodes]
    return int((~ok).sum())


# -- who may be evicted ------------------------------------------------------
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


def pods_that_fit(capacity, used, pods, max_pods, gang_req, admits):
    """([T] bool, [3]): which of the gang's pods find room on what is idle
    on the nodes ``admits`` [N] leaves them, pods of one request together,
    each node taking as many as it holds; and what stays idle THERE once
    they sit."""
    free = (capacity - used)[admits]
    room = (max_pods - pods)[admits].astype(np.int64)
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
    return placed, free.sum(axis=0)


def fewest_evictions(capacity, used, pods, max_pods, gang_req, victim_req,
                     admits) -> int:
    """The fewest pods whose release ON NODES THE GANG MAY USE lets it
    fit: what the pods that found no idle room there ask, beyond what
    stays idle there (``used`` and ``pods`` are the ledger the gang met;
    what is idle on a node the gang may not use helps it nothing), over
    the most one victim releases, resource by resource.  Exact where one
    resource binds and victims and pods ask the same of it, as one-GPU
    victims and one-GPU pods do on pools with no idle GPU; elsewhere a
    lower bound, the side a limit of 0 may err on."""
    placed, idle_rest = pods_that_fit(capacity, used, pods, max_pods,
                                      gang_req, admits)
    short = gang_req[~placed].sum(axis=0) - idle_rest
    most = victim_req.max(axis=0)
    need = [math.ceil(short[r] / most[r] - EPS)
            for r in range(len(short)) if short[r] > EPS and most[r] > 0]
    return max(need, default=0)


def evictions_on_excluded_nodes(victim_nodes, admits) -> int:
    """Pods evicted from a node no pod of the reclaimer may use
    (``admits`` [N]: the union of its pods' rows): their release frees
    nothing it can take."""
    return int((~np.asarray(admits, bool)[np.asarray(victim_nodes,
                                                     np.int64)]).sum())


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
def binpack_scores(capacity_t, idle_t, room, req, admits):
    """(feasible [N] bool, score [N]) of one pod against the fleet's
    state, ``capacity_t`` and ``idle_t`` [3,N] (resource-major), among the
    nodes ``admits`` [N] leaves it (``scores.go`` magnitudes):
      bin-pack  9 * (1 - (free - min_free) / (max_free - min_free)) over
                the feasible nodes that have the pod's dominant resource
                (GPUs for a pod that asks for any, else CPU); 9 for all
                when they tie
      type      10 where the node's kind (GPU or CPU-only) matches the
                pod's
      available 100 where the pod fits on idle resources now
    """
    fits = ((req[CPU] <= idle_t[CPU] + EPS) & (req[MEM] <= idle_t[MEM] + EPS)
            & (req[GPU] <= idle_t[GPU] + EPS))
    feasible = (room >= 1.0) & fits & admits
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
    return feasible, score


def place_gang(capacity, used, pods, max_pods, reqs, rows):
    """Upstream's bin-pack order for pods ``reqs`` [T,3], in order, each
    against the state the pods before it left and among the nodes its row
    of ``rows`` ([T,N], or [N] for all) admits; the first node by name
    among equals.  [T] node indices, or None where a pod fits nowhere
    (the gang then binds nothing)."""
    rows = np.asarray(rows, bool)
    capacity_t = np.ascontiguousarray(capacity.T)
    idle_t = np.ascontiguousarray((capacity - used).T)
    room = (max_pods - pods).astype(np.float64)
    out = np.empty(len(reqs), np.int64)
    for t, req in enumerate(reqs):
        feasible, score = binpack_scores(
            capacity_t, idle_t, room, req, rows if rows.ndim == 1
            else rows[t])
        if not feasible.any():
            return None
        best = int(np.argmax(np.where(feasible, score, -np.inf)))
        out[t] = best
        idle_t[:, best] -= req
        room[best] -= 1.0
    return out


def placements_not_reference(capacity, used, pods, max_pods, reqs, nodes,
                             rows) -> int:
    """Pods of a bound gang (``nodes`` [T], in the order of ``reqs``)
    that are not on the node ``place_gang`` gives them from the ledger
    before the bind; every pod where the reference would bind none."""
    want = place_gang(capacity, used, pods, max_pods, reqs, rows)
    if want is None:
        return len(reqs)
    return int((want != np.asarray(nodes)).sum())
