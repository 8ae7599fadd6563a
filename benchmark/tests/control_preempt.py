"""The controls of the in-queue preemption cell: a plain preemptor with one
guarantee dropped, put in the program's place.

Each drives the generator's own loop and comparison (``preempt_replicas``,
``reference/inqueue_eviction.py``) with a plain preemptor where
``Scheduler.run_once`` would be: it binds a waiting replica group onto
idle nodes, its pods largest first, and for each one that finds no room
walks the victims it may take in upstream's order (lowest priority, then
newest; a job's surplus, then its core gang) to the first step that seats
the replica, nominates the replica's places and then evicts, a commit a
replica, through the client's cache as a statement would.  ``correct`` has
to come out false, by the counts the control drops and by no other.

  queue_blind     takes victims of any queue.  The control first hands the
                  newest node's two jobs to a sibling leaf queue
                  (``Client.plant_decoys``): they are still preemptible
                  and of lower priority, and the newest, so it takes them
                  first.  Drops: every victim is of the preemptor's own
                  queue (``victims_from_other_queue``, their 8 pods).
  priority_blind  takes victims newest first whatever their priority.  The
                  control first raises the newest node's two jobs to the
                  replicas' own priority.  Drops: every victim is of
                  strictly lower priority (``victims_not_lower_priority``,
                  8).
  one_more        in its second cycle's first commit takes the next job
                  in order beside the prefix that seats the replica.
                  Drops: no more is taken than seats the preemptor
                  (``evictions_beyond_need``, that job's 4 pods) and with
                  it what is taken is upstream's prefix
                  (``evictions_not_reference``, the same 4).
  partial_gang    in its second cycle binds three of the first waiting
                  replica's four pods.  Drops: a gang is whole or not at
                  all (``gangs_partly_bound``, 1).
  sound           drops nothing, on a fleet with both kinds of decoy
                  planted: comes out correct, which shows that the
                  controls fail by what they drop and not by the plain
                  preemptor, and that the decoys can be passed over.

    JAX_PLATFORMS=cpu python3 benchmark/tests/control_preempt.py \\
        --workload preempt98k-lws-32x4 --seeds 1,2,3

runs them at the cell's own width (no device is used: the fleet is built
and the preemptor is numpy); exit 0 = ``sound`` correct and the four
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

KINDS = ("queue_blind", "priority_blind", "one_more", "partial_gang")
MOVES = {"queue_blind": {"victims_from_other_queue"},
         "priority_blind": {"victims_not_lower_priority"},
         "one_more": {"evictions_beyond_need", "evictions_not_reference"},
         "partial_gang": {"gangs_partly_bound"}, "sound": set()}
DECOYS = {"queue_blind": ("queue",), "priority_blind": ("priority",),
          "sound": ("queue", "priority")}
FAULT_CYCLE = 1          # the cycle in which one_more and partial_gang err


def place(free, room, gang_req):
    """[T] node of every pod of the gang, the largest requests first, each
    on the first node that holds it; None where one finds none.  ``free``
    and ``room`` are changed where the gang is placed."""
    nodes = np.full(len(gang_req), -1)
    took = []
    for t in sorted(range(len(gang_req)),
                    key=lambda t: tuple(-gang_req[t][::-1])):
        fits = np.flatnonzero(
            np.all(free >= gang_req[t] - 1e-9, axis=1) & (room > 0))
        if not fits.size:
            for u in took:
                free[nodes[u]] += gang_req[u]
                room[nodes[u]] += 1
            return None
        nodes[t] = fits[0]
        free[nodes[t]] -= gang_req[t]
        room[nodes[t]] -= 1
        took.append(t)
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
    if kind in DECOYS:
        client.plant_decoys(DECOYS[kind])

    def may_take(job, gang) -> bool:
        return job.preemptible and bool(job.pods) \
            and (kind == "queue_blind" or job.queue == gang.queue) \
            and (kind == "priority_blind" or job.priority < gang.priority)

    def in_order(jobs) -> list:
        if kind == "priority_blind":
            return sorted(jobs, key=lambda j: -j.created)
        return sorted(jobs, key=lambda j: (j.priority, -j.created))

    def control_cycle():
        """Stands where run_once stands: the allocate action's binds,
        then a commit for every replica still waiting."""
        erring = len(client.records) == FAULT_CYCLE
        free = ledger.capacity - ledger.used
        room = (ledger.max_pods - ledger.pods).astype(np.int64)
        waiting = []
        for gang, _pg in client.pending:
            nodes = place(free, room, gang.req)
            if nodes is None:
                waiting.append(gang)
                continue
            names = gang.names
            if kind == "partial_gang" and erring:
                erring = False
                names = names[:-1]
                free[nodes[-1]] += gang.req[-1]
                room[nodes[-1]] += 1
            cache.bound.extend((name, gen.node_name(int(node)))
                               for name, node in zip(names, nodes))
        views = None     # [uid, minimum, {pod: node} left, req], in order
        erring = len(client.records) == FAULT_CYCLE
        for gang in waiting:
            if views is None:
                views = [[j.uid, j.min_available, dict(j.pods), j.req]
                         for j in in_order([j for j in client.jobs.values()
                                            if may_take(j, gang)])]
                view_of = {v[0]: v for v in views}
            wanted = ref.reference_victims(
                ledger.capacity, ledger.capacity - free,
                ledger.max_pods - room, ledger.max_pods, gang.req,
                (tuple(v) for v in views if v[2]))
            if not wanted:
                continue
            if kind == "one_more" and erring:
                erring = False
                wanted |= set(next(v[2] for v in views
                                   if v[2] and not set(v[2]) & wanted))
            for pod in wanted:
                view = view_of[client.pod_job[pod]]
                free[view[2][pod]] += view[3]
                room[view[2].pop(pod)] += 1
            nodes = place(free, room, gang.req)
            # A commit: the preemptor's places, then the evictions.
            for name, node in zip(gang.names, nodes):
                cache.task_pipelined(types.SimpleNamespace(uid=name),
                                     gen.node_name(int(node)))
            for pod in sorted(wanted):
                cache.evict(types.SimpleNamespace(uid=pod))

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
    return moved == MOVES[out["control"]]


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
