"""The controls of the spread cell: a plain reclaimer with one guarantee
dropped, put in the program's place.

Each drives the generator's own loop and comparison
(``spread_reclaim_gangs``, ``reference/spread_eviction.py``) with a plain
reclaimer where ``Scheduler.run_once`` would be: it binds a waiting gang
onto what is idle, pod by pod, and for the gang that finds the fleet full
evicts whole jobs from the client's book, as many as the gang needs.
``correct`` has to come out false, by the one count the control drops.

  binpack    places the gang by bin-pack (the fullest feasible node first)
             on a shard that spreads.  Drops: every pod on the node
             upstream's spread order gives it (``placements_not_reference``).
  stale      scores every pod of the gang against the state BEFORE the gang
             (one score row for all, as a batched scoring would), and keeps
             to capacity: the node that led at first fills up before the
             next is touched.  Drops: pod by pod against the state the pods
             before it left (``placements_not_reference``).
  one_more   evicts one victim job more than the gang needs.  Drops: no
             more is taken than the reclaimer needs
             (``evictions_beyond_need``).
  sound      drops nothing: comes out correct, which shows that the
             controls fail by what they drop and not by the plain reclaimer.

    JAX_PLATFORMS=cpu python3 benchmark/tests/control_spread.py \\
        --workload spread98k-pytorchjob-256 --seeds 1,2,3

runs them at the cell's own width (no device is used: the fleet is built
and the reclaimer is numpy); exit 0 = ``sound`` correct and the three
others not, each by its own count alone.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KINDS = ("binpack", "stale", "one_more")
MOVES = {"binpack": "placements_not_reference",
         "stale": "placements_not_reference",
         "one_more": "evictions_beyond_need", "sound": None}


def cut_cell(cell, nodes: int, share: float, departments: int, leaves: int,
             whole: int, gang: int, victims: int):
    """The cell with its fleet, gang and solver caps cut for the CPU: the
    gang keeps its one master, the workers make up ``gang``."""
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["nodes"]["count"] = nodes
    cell.config["queues"].update(departments=departments,
                                 leaves_per_department=leaves)
    cell.config["occupancy"]["preemptible_nodes_share"] = share
    cell.config["occupancy"]["whole_node"]["gang_pods"] = whole
    cell.config["scheduler"].update(max_victims_considered=victims,
                                    scenario_prescreen_max=victims)
    master, worker = cell.traffic["gang"]["roles"]
    worker["count"] = gang - int(master["count"])
    return cell


def place(ref, kind: str, capacity, used, pods, max_pods, reqs):
    """[T] nodes for the gang's pods ``reqs`` on the ledger's state, or
    None: the reference's own order, or the control's."""
    if kind not in ("binpack", "stale"):
        return ref.place_gang(capacity, used, pods, max_pods, reqs)
    capacity_t = np.ascontiguousarray(capacity.T)
    idle_t = np.ascontiguousarray((capacity - used).T)
    room = (max_pods - pods).astype(np.float64)
    out = np.empty(len(reqs), np.int64)
    stale = None
    for t, req in enumerate(reqs):
        feasible, score = ref.spread_scores(capacity_t, idle_t, room, req)
        if not feasible.any():
            return None
        if kind == "stale":
            stale = score if stale is None else stale
            score = stale
        else:
            # Bin-pack: the least free share of the GPUs first.
            score = score - 2.0 * idle_t[ref.GPU] / capacity_t[ref.GPU]
        out[t] = best = int(np.argmax(np.where(feasible, score, -np.inf)))
        idle_t[:, best] -= req
        room[best] -= 1.0
    return out


def run_control(workload: str, seed: int, kind: str, cycles: int = 4,
                root: str = ROOT, cut: dict | None = None) -> dict:
    from benchmark.harness import cluster as gen
    from benchmark.harness import spec

    cell = spec.Cell(spec.load_benchmark(root), workload, root)
    if cut:
        cut_cell(cell, **cut)
    # The client alone: ``build`` first tries the program, which a control
    # stands in for.
    client = cell.generator.Client(cell, seed)
    ledger, cache, ref = client.ledger, client.sched.cache, cell.reference

    def control_cycle():
        """Stands where run_once stands."""
        used, pods = ledger.used.copy(), ledger.pods.copy()
        for gang, _pg in client.pending:
            state = (ledger.capacity, used, pods, ledger.max_pods, gang.req)
            nodes = place(ref, kind, *state)
            if nodes is not None:
                cache.bound.extend(
                    (name, gen.node_name(node))
                    for name, node in zip(gang.names, nodes.tolist()))
                np.add.at(used, nodes, gang.req)
                np.add.at(pods, nodes, 1)
                continue
            placed, _left = ref.pods_that_fit(*state)
            need = int((~placed).sum())
            victims = []
            for uid in sorted(client.jobs):
                job = client.jobs[uid]
                if not job.preemptible or job.queue == gang.queue:
                    continue
                if len(victims) >= need:
                    if kind == "one_more":
                        victims.extend(job.pods)
                    break
                victims.extend(job.pods)
            cache.evicted.extend(victims)

    client.sched.run_once = control_cycle
    for _ in range(cycles):
        client.cycle()
    verdict = cell.generator.compare(client.records, ledger, cell)
    return {"workload": workload, "seed": seed, "control": kind,
            "correct": verdict["correct"], "compared": verdict["compared"]}


def as_said(out: dict) -> bool:
    """Did the control come out as the docstring says: its own count above
    its limit and no other count moved (``sound``: none)?"""
    moved = {k for k, (v, lim) in out["compared"].items() if v > lim}
    want = MOVES[out["control"]]
    return moved == ({want} if want else set())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--kinds", default=",".join(KINDS + ("sound",)))
    args = ap.parse_args(argv)
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in args.kinds.split(","):
            out = run_control(args.workload, seed, kind)
            print(json.dumps(out), flush=True)
            bad += not as_said(out)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
