"""The controls of the consolidation cell: a plain consolidator with one
guarantee dropped, put in the program's place.

Each drives the generator's own loop and comparison
(``consolidation_gangs``, ``reference/relocation.py``) with a consolidator
where ``Scheduler.run_once`` would be: it binds a waiting gang onto whole
nodes that are idle and the replacements of moved pods onto the fullest
nodes that hold them, and for the gang that finds no whole node moves the
jobs of as many nodes as it lacks, those with the fewest pods, every moved
pod with a place on the idle GPUs elsewhere.  ``correct`` has to come out
false.

  one_more         moves the jobs of one node more than the gang lacks.
                   Drops: no more is moved than the gang needs
                   (``moves_beyond_need``).
  lose_one         gives one moved pod no place and never binds what its
                   controller makes in its stead.  Drops: the running set
                   never shrinks (``moved_without_place``,
                   ``moved_not_rebound``).
  split            moves one pod of a job and leaves the other.  Drops: a
                   gang moves whole (``victim_gangs_split``).
  not_preemptible  moves a job that is not preemptible (one fragment job
                   is re-booked so first).  Drops: only preemptible pods
                   move (``moved_not_preemptible``).
  sound            drops nothing: comes out correct, which shows that the
                   controls fail by what they drop and not by the plain
                   consolidator.

    JAX_PLATFORMS=cpu python3 benchmark/tests/control_relocation.py \\
        --workload defrag98k-pytorchjob-1k --seeds 1,2,3

runs them at the cell's own width (no device is used: the fleet is built
and the consolidator is numpy).
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

KINDS = ("one_more", "lose_one", "split", "not_preemptible")


def cut_cell(cell, nodes: int, share: float, departments: int, leaves: int,
             fragment_queues: int, whole: int, workers: int, victims: int):
    """The cell with its fleet, gang and solver caps cut for the CPU."""
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["nodes"]["count"] = nodes
    cell.config["queues"].update(departments=departments,
                                 leaves_per_department=leaves)
    occ = cell.config["occupancy"]
    occ["fragmented_nodes_share"] = share
    occ["whole_node"]["gang_pods"] = whole
    frag = occ["fragment"]
    frag["queues"] = fragment_queues
    cell.config["scheduler"].update(max_victims_considered=victims,
                                    scenario_prescreen_max=victims)
    cell.traffic["gang"]["roles"][1]["count"] = workers
    return cell


def newest_first(ssn, job, _tasks):
    """``collect_consolidation_victims`` as the program had it before
    PR 37: the newest job first, blind to what its leaving empties.  Put
    in the program's place, it is the program that the generator's trial
    (``try_fewest_moves``) has to stop."""
    victims = [pg for pg in ssn.cluster.podgroups.values()
               if pg.uid != job.uid and pg.queue_id in ssn.cluster.queues
               and pg.is_preemptible() and pg.num_active_allocated() > 0]
    victims.sort(key=lambda pg: (pg.priority, -pg.creation_ts))
    return victims


def run_control(workload: str, seed: int, kind: str, cycles: int = 5,
                root: str = ROOT, cut: dict | None = None) -> dict:
    from benchmark.harness import cluster as gen
    from benchmark.harness import spec

    cell = spec.Cell(spec.load_benchmark(root), workload, root)
    if cut:
        cut_cell(cell, **cut)
    client = cell.generator.build(cell, seed)
    ledger, cache = client.ledger, client.sched.cache
    lost: set = set()                # replacements never to be bound
    hard = None
    if kind == "not_preemptible":
        # The newest fragment job is not preemptible after all.
        hard = max((j for j in client.jobs.values() if j.preemptible),
                   key=lambda j: (j.created, j.uid))
        hard.preemptible = False

    def fits(free, room, req):
        return np.all(free >= req - 1e-9, axis=1) & (room > 0)

    def control_cycle():
        """Stands where run_once stands.  The fault falls in the second
        cycle, the first whose moves meet a fleet that has been moved
        in before."""
        fault = kind if len(client.records) == 1 else "sound"
        free = ledger.capacity - ledger.used
        room = ledger.max_pods - ledger.pods
        waiting = None
        for gang, _pg in client.pending:
            at = []
            for req in gang.req:
                nodes = np.flatnonzero(fits(free, room, req))
                nodes = nodes[~np.isin(nodes, at)]
                if not nodes.size:
                    break
                at.append(int(nodes[0]))
            if len(at) == len(gang.names):
                cache.bound.extend((name, gen.node_name(node))
                                   for name, node in zip(gang.names, at))
                np.subtract.at(free, at, gang.req)
                np.subtract.at(room, at, 1)
            else:
                waiting = gang
        # Replacements, onto the fullest nodes that hold them.
        landed = set()
        for job in list(client.jobs.values()):
            for name in job.waiting:
                if name in lost:
                    continue
                nodes = np.flatnonzero(fits(free, room, job.req)
                                       & (free[:, 2] < ledger.capacity[:, 2]))
                node = int(nodes[np.argmin(free[nodes, 2])])
                cache.bound.append((name, gen.node_name(node)))
                landed.add(node)
                free[node] -= job.req
                room[node] -= 1
        if waiting is None:
            return
        # The nodes the gang lacks: those that its movable pods leaving
        # would empty, the fewest pods first, the newest jobs first.
        on_node: dict[int, list] = {}
        for uid, job in client.jobs.items():
            for node in set(job.pods.values()):
                on_node.setdefault(node, []).append(job)
        cand = [(sum(len(j.pods) for j in jobs),
                 -max(j.created for j in jobs), node)
                for node, jobs in on_node.items()
                if node not in landed and all((j.preemptible
                        or (j is hard and fault == "not_preemptible"))
                       and set(j.pods.values()) == {node} for j in jobs)]
        # The job that is not preemptible after all goes first.
        cand.sort(key=lambda c: (hard not in on_node[c[2]], c))
        need = len(waiting.names) + (fault == "one_more")
        chosen = [node for _pods, _age, node in cand[:need]]
        moved = [name for node in chosen for job in on_node[node]
                 for name in job.pods]
        if fault == "split":
            # One pod of one more job, and not its other.
            job = on_node[cand[need][2]][0]
            moved.append(next(iter(job.pods)))
        cache.evicted.extend(moved)
        places = [(name, gen.node_name(node))
                  for name, node in zip(waiting.names, chosen)]
        for name in moved:
            job = client.jobs[client.pod_job[name]]
            nodes = np.flatnonzero(fits(free, room, job.req)
                                   & ~np.isin(np.arange(ledger.n), chosen))
            node = int(nodes[np.argmin(free[nodes, 2])])
            places.append((name, gen.node_name(node)))
            free[node] -= job.req
            room[node] -= 1
        if fault == "lose_one":
            gone, _node = places.pop()
            job = client.jobs[client.pod_job[gone]]
            lost.add(f"{job.uid}-{job.made}")
        cache.pipelined.extend(places)

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
    ap.add_argument("--kinds", default="sound," + ",".join(KINDS))
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
