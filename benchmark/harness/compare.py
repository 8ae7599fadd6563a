"""The comparison that decides ``correct``.

It reads what the timed cycles themselves bound, at the timed sizes, and
holds it to the guarantees the configuration states and to the plain
reference (``benchmark/reference/placement.py``):

- every gang of the window bound all its pods or none (and, since the mix
  is chosen so that every gang fits, all);
- after every cycle no node is past its capacity or its pod room, by the
  client's own ledger;
- no queue, from leaf to root, is past its limit, and the reference would
  have admitted the gang;
- a gang with a required topology level lies inside one domain of it, and
  one with a preferred level inside one domain of that while the reference
  finds one that holds it;
- every pod of every gang of the window is where the reference puts it:
  the reference places the whole gang, one pod at a time, from the state
  the client's ledger had before the cycle, and takes nothing from the
  program.

Each number has the limit 0: the reference is exact (PERF.md section 2).
"""

from __future__ import annotations

import numpy as np

from ..reference import placement as ref

LIMITS = {
    "gangs_not_bound": 0, "gangs_partly_bound": 0, "foreign_binds": 0,
    "nodes_over_capacity": 0, "queues_over_limit": 0,
    "gangs_refused_by_reference": 0, "pods_outside_domain": 0,
    "placements_not_reference": 0,
}


def level_order(config: dict, topology: dict | None) -> list:
    if not topology:
        return []
    return list(config["topologies"][topology["name"]]["levels"])


def compare(records, ledger, config) -> dict:
    """{name: value} for every number in ``LIMITS``, plus counts."""
    out = {k: 0 for k in LIMITS}
    out["gangs"] = len(records)
    out["placements_checked"] = 0
    capacity = ledger.capacity
    for rec in records:
        gang = rec.gang
        t = len(gang.names)
        bound = gang.bound
        out["foreign_binds"] += rec.foreign_binds
        if not bound:
            out["gangs_not_bound"] += 1
            continue
        if len(bound) != t:
            out["gangs_partly_bound"] += 1
            continue
        nodes = np.array([bound[n] for n in gang.names])

        # The ledger after this cycle, and the guarantees on it.
        used = rec.used_before.copy()
        np.add.at(used, nodes, gang.req)
        pods = rec.pods_before.copy()
        np.add.at(pods, nodes, 1)
        over = np.any(used > capacity + ref.EPS, axis=1) \
            | (pods > ledger.max_pods)
        out["nodes_over_capacity"] += int(over.sum())
        total = gang.req.sum(axis=0)
        q = gang.queue
        while q is not None:
            after = rec.queue_used_before[q] + total
            if np.any(after > ledger.queue_limit[q] + ref.EPS):
                out["queues_over_limit"] += 1
            q = ledger.queue_parent[q]
        if not ref.queue_admits(gang.queue, total, ledger.queue_parent,
                                ledger.queue_limit, rec.queue_used_before):
            out["gangs_refused_by_reference"] += 1
            continue

        # Topology: one domain of the required level; one of the preferred
        # level while the reference finds one that holds the gang.
        topo = gang.topology or {}
        levels = level_order(config, gang.topology)
        held = []
        if topo.get("required"):
            held.append(topo["required"])
        if topo.get("preferred"):
            cands = ref.topology_candidates(
                capacity, rec.used_before, rec.pods_before, ledger.max_pods,
                gang.req, ledger.levels, levels, topo.get("required"),
                topo["preferred"])
            if cands and cands[0][0] == 0:
                held.append(topo["preferred"])
        for level in held:
            doms = ledger.levels[level][nodes]
            out["pods_outside_domain"] += int((doms != doms[0]).sum())

        # The whole gang, placed by the reference from the state before.
        want = ref.schedule_gang(
            capacity, rec.used_before, rec.pods_before, ledger.max_pods,
            gang.req, gang.topology, ledger.levels, levels)
        if want is None:
            out["gangs_refused_by_reference"] += 1
            continue
        out["placements_checked"] += t
        out["placements_not_reference"] += int((want != nodes).sum())
    return out


def verdict(numbers: dict) -> tuple[bool, dict]:
    """(correct, {name: [value, limit]}) in the order of ``LIMITS``."""
    compared = {k: [numbers[k], LIMITS[k]] for k in LIMITS}
    ok = all(v <= lim for v, lim in compared.values())
    return ok, compared
