"""The controls of the reclaim cell: a plain reclaimer with one guarantee
dropped, put in the program's place.

Each drives the generator's own loop and comparison (``reclaim_gangs``,
``reference/eviction.py``) with a reclaimer where ``Scheduler.run_once``
would be: it binds a waiting gang onto what is idle, and for the gang that
finds the fleet full evicts whole jobs from the client's book, as many as
the gang needs.  ``correct`` has to come out false.

  one_more   evicts one victim job more than the gang needs.  Drops: no
             more is taken than the reclaimer needs
             (``evictions_beyond_need``).
  own_queue  takes its first victim from the reclaimer's own queue (one of
             the occupier's jobs is re-booked there first).  Drops: a
             victim is of another queue (``victims_from_own_queue``).
  evict_all  evicts every preemptible pod it finds.  Drops: the victims'
             queue keeps its deserved share (``victim_queue_below_quota``,
             where the occupier has no other pods), and the need.
  sound      drops nothing: comes out correct, which shows that the
             controls fail by what they drop and not by the plain
             reclaimer.

    JAX_PLATFORMS=cpu python3 benchmark/tests/control_reclaim.py \\
        --workload ns98k-reclaim-wide --seeds 1,2,3

runs them at the cell's own width (no device is used: the fleet is built
and the reclaimer is numpy).
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

KINDS = ("one_more", "own_queue", "evict_all")


def cut_cell(cell, nodes: int, share: float, departments: int, leaves: int,
             whole: int, gang: int, victims: int):
    """The cell with its fleet, gang and solver caps cut for the CPU."""
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["nodes"]["count"] = nodes
    cell.config["queues"].update(departments=departments,
                                 leaves_per_department=leaves)
    cell.config["occupancy"]["preemptible_nodes_share"] = share
    cell.config["occupancy"]["whole_node"]["gang_pods"] = whole
    cell.config["scheduler"].update(max_victims_considered=victims,
                                    scenario_prescreen_max=victims)
    cell.traffic["gang"]["roles"][0]["count"] = gang
    return cell


def first_fit(free: np.ndarray, room: np.ndarray, req: np.ndarray) -> list:
    """Nodes for pods of one request ``req`` [3], as many as ``free``
    [N,3] and the pod ``room`` [N] hold, nodes in order."""
    asks = req > 0
    holds = np.floor(np.min((free[:, asks] + 1e-9) / req[asks], axis=1))
    holds = np.maximum(np.minimum(holds, room), 0).astype(int)
    return np.repeat(np.arange(len(free)), holds).tolist()


def run_control(workload: str, seed: int, kind: str, cycles: int = 4,
                root: str = ROOT, cut: dict | None = None) -> dict:
    from benchmark.harness import cluster as gen
    from benchmark.harness import spec

    cell = spec.Cell(spec.load_benchmark(root), workload, root)
    if cut:
        cut_cell(cell, **cut)
    client = cell.generator.build(cell, seed)
    ledger, cache = client.ledger, client.sched.cache
    if kind == "own_queue":
        # One of the occupier's jobs is the reclaimer's queue's instead.
        job = client.jobs[min(u for u, j in client.jobs.items()
                              if j.preemptible)]
        nodes = np.array(list(job.pods.values()))
        reqs = np.tile(job.req, (len(nodes), 1))
        ledger.charge(job.queue, nodes, reqs, -1.0)
        job.queue = client.reclaimer
        ledger.charge(job.queue, nodes, reqs)

    def control_cycle():
        """Stands where run_once stands."""
        free = ledger.capacity - ledger.used
        room = ledger.max_pods - ledger.pods
        for gang, _pg in client.pending:
            nodes = first_fit(free, room, gang.req[0])
            if len(nodes) >= len(gang.names):
                nodes = nodes[:len(gang.names)]
                cache.bound.extend(
                    (name, gen.node_name(node))
                    for name, node in zip(gang.names, nodes))
                np.subtract.at(free, nodes, gang.req)
                np.subtract.at(room, nodes, 1)
                continue
            need = len(gang.names) - len(nodes)
            victims = []
            for uid in sorted(client.jobs):
                job = client.jobs[uid]
                if not job.preemptible:
                    continue
                if kind != "own_queue" and job.queue == gang.queue:
                    continue
                if len(victims) >= need and kind != "evict_all":
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--kinds", default="one_more,own_queue")
    args = ap.parse_args(argv)
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in args.kinds.split(","):
            out = run_control(args.workload, seed, kind)
            print(json.dumps(out), flush=True)
            bad += bool(out["correct"]) != (kind == "sound")
    return 1 if bad else 0   # every control has to fail the comparison


if __name__ == "__main__":
    sys.exit(main())
