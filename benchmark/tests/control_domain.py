"""The controls of the rack-bound reclaim cell: a plain reclaimer with one
guarantee dropped, put in the program's place.

Each drives the generator's own loop and comparison
(``domain_reclaim_gangs``, ``reference/domain_eviction.py``) with a plain
reclaimer where ``Scheduler.run_once`` would be: it binds a waiting gang
inside a rack that holds it idle, and for each one that finds none walks
the victims in upstream's order (lowest priority, then newest) to the
first step that seats the gang inside ONE rack, places the gang there,
places the rest of the prefix again job by job on the first nodes that
hold them, nominates all those places and then evicts, a commit a gang,
through the client's cache as a statement would.  ``correct`` has to come
out false, by the counts the control drops and by no other.

  rack_blind   takes the smallest prefix that frees the gang's GPUs
               ANYWHERE and places the gang on the first nodes that hold
               its pods, whatever their rack.  Drops: every pod of a gang
               lies in one rack (``gangs_outside_one_rack``, at the
               nomination and again at the bind) and with it the victims
               are the rack-feasible prefix
               (``victims_outside_upstream_prefix``: the reference's is
               longer).
  oldest_first walks the victims OLDEST first.  Drops: the victims are
               upstream's (``victims_outside_upstream_prefix``).
  one_more     in its second cycle's first commit takes the next job in
               order beside the prefix, after the rest of the prefix has
               been placed again, and does not place it again.
               Drops: the prefix is upstream's
               (``victims_outside_upstream_prefix``, that job's 4 pods)
               and, where the gang's rack stood full, exactly the gang's
               pods stay evicted (``evictions_beyond_need``, the same 4;
               where the rack held idle GPUs, as at the cell's width
               after a cycle whose placed-again victims were deleted,
               the commit keeps fewer than the gang has pods even with
               four more, and this count does not move).
  keep_none    places no victim again.  Drops: what the prefix holds
               beyond the gang's need is placed again
               (``evictions_beyond_need``).
  sound        drops nothing: comes out correct, which shows that the
               controls fail by what they drop and not by the plain
               reclaimer.

    JAX_PLATFORMS=cpu python3 benchmark/tests/control_domain.py \\
        --workload tasreclaim98k-pytorchjob-8x256 --seeds 1,2,3

runs them at the cell's own width (no device is used: the fleet is built
and the reclaimer is numpy); exit 0 = ``sound`` correct and the four
others not, each by its own counts alone.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KINDS = ("rack_blind", "oldest_first", "one_more", "keep_none")
MOVES = {"rack_blind": {"gangs_outside_one_rack",
                        "victims_outside_upstream_prefix"},
         "oldest_first": {"victims_outside_upstream_prefix"},
         "one_more": {"victims_outside_upstream_prefix"},
         "keep_none": {"evictions_beyond_need"}, "sound": set()}
# Counts a control MAY move beside those it must.
MAY = {"one_more": {"evictions_beyond_need"}}
FAULT_CYCLE = 1          # the cycle in which one_more errs


def first_fit(free, room, reqs, inside=None):
    """[T] node of every pod, each on the first node that holds it (among
    ``inside``, where given); None where one finds none.  ``free`` and
    ``room`` are changed where the pods are placed."""
    nodes = np.full(len(reqs), -1)
    among = np.arange(len(free)) if inside is None else inside
    for t, req in enumerate(reqs):
        fits = among[np.all(free[among] >= req - 1e-9, axis=1)
                     & (room[among] > 0)]
        if not fits.size:
            for u in range(t):
                free[nodes[u]] += reqs[u]
                room[nodes[u]] += 1
            return None
        nodes[t] = fits[0]
        free[nodes[t]] -= req
        room[nodes[t]] -= 1
    return nodes


def run_control(workload: str, seed: int, kind: str, cycles: int = 4,
                root: str = ROOT, cut: dict | None = None) -> dict:
    from benchmark.harness import cluster as gen
    from benchmark.harness import spec

    cell = spec.Cell(spec.load_benchmark(root), workload, root)
    if cut:
        cell = cell.generator.cut_cell(cell, **cut)
    # The client alone: ``build`` first tries the program, which a control
    # stands in for.
    client = cell.generator.Client(cell, seed)
    ledger, cache, ref = client.ledger, client.sched.cache, cell.reference
    seg = ledger.levels[cell.traffic["gang"]["topology"]["required"]]
    cap = int(cell.config["scheduler"]["max_victims_considered"])
    everywhere = np.full(ledger.n, 0)

    def control_cycle():
        """Stands where run_once stands: the allocate action's binds,
        then a commit for every gang still waiting."""
        erring = len(client.records) == FAULT_CYCLE
        free = ledger.capacity - ledger.used
        room = (ledger.max_pods - ledger.pods).astype(np.int64)
        waiting = []
        for gang, _pg in client.pending:
            nodes = None
            if kind == "rack_blind":
                nodes = first_fit(free, room, gang.req)
            else:
                k, dom = ref.first_seating_prefix(
                    ledger.capacity, ledger.capacity - free,
                    ledger.max_pods - room, ledger.max_pods, seg, [],
                    gang.req)
                if k == 0:
                    nodes = first_fit(free, room, gang.req,
                                      np.flatnonzero(seg == dom))
            if nodes is None:
                waiting.append(gang)
                continue
            cache.bound.extend((name, gen.node_name(int(node)))
                               for name, node in zip(gang.names, nodes))
        # The victims in order, with where each pod stands NOW: a victim
        # placed again by one commit may be taken again by the next.
        jobs = [j for j in client.jobs.values() if j.preemptible]
        jobs.sort(key=lambda j: j.created if kind == "oldest_first"
                  else -j.created)
        jobs = jobs[:2 * cap]
        where = {p: n for j in jobs for p, n in j.pods.items()}
        gone = set()
        for gang in waiting:
            offered = []
            for job in jobs:
                if job.queue == gang.queue:
                    continue
                pods = sorted(p for p in job.pods if p not in gone)
                if pods:
                    offered.append((job, pods))
                if len(offered) == cap:
                    break
            steps = [(np.array([where[p] for p in pods]),
                      np.tile(job.req, (len(pods), 1)))
                     for job, pods in offered]
            used, pods_on = ledger.capacity - free, ledger.max_pods - room
            k, dom = ref.first_seating_prefix(
                ledger.capacity, used, pods_on, ledger.max_pods,
                everywhere if kind == "rack_blind" else seg, steps,
                gang.req)
            if not k:
                continue
            taken = offered[:k]
            for job, pods in taken:
                for pod in pods:
                    free[where[pod]] += job.req
                    room[where[pod]] += 1
            inside = None if kind == "rack_blind" \
                else np.flatnonzero(seg == dom)
            nodes = first_fit(free, room, gang.req, inside)
            # A commit: the gang's places and the places of what is
            # placed again, then the evictions.
            for name, node in zip(gang.names, nodes):
                cache.task_pipelined(types.SimpleNamespace(uid=name),
                                     gen.node_name(int(node)))
            for n, (job, pods) in enumerate(taken):
                again = None
                if kind != "keep_none":
                    again = first_fit(free, room,
                                      np.tile(job.req, (len(pods), 1)))
                if again is None:
                    gone.update(pods)
                    continue
                for pod, node in zip(pods, again):
                    where[pod] = int(node)
                    cache.task_pipelined(types.SimpleNamespace(uid=pod),
                                         gen.node_name(int(node)))
            if kind == "one_more" and erring:
                # The next job in order goes too, and stays gone.
                job, pods = offered[k]
                taken.append((job, pods))
                gone.update(pods)
                for pod in pods:
                    free[where[pod]] += job.req
                    room[where[pod]] += 1
            for job, pods in taken:
                for pod in pods:
                    cache.evict(types.SimpleNamespace(uid=pod))
            erring = False

    client.sched.run_once = control_cycle
    for _ in range(cycles):
        client.cycle()
    verdict = cell.generator.compare(client.records, ledger, cell)
    return {"workload": workload, "seed": seed, "control": kind,
            "correct": verdict["correct"], "compared": verdict["compared"]}


def as_said(out: dict) -> bool:
    """Did the control come out as the docstring says: its own counts
    above their limit and no other count moved (``sound``: none)?"""
    moved = {k for k, (v, lim) in out["compared"].items() if v > lim}
    must = MOVES[out["control"]]
    return must <= moved <= must | MAY.get(out["control"], set())


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
