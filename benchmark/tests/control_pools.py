"""The controls of the pools cell: a plain reclaimer with one guarantee
dropped, put in the program's place.

Each drives the generator's own loop and comparison
(``pool_reclaim_gangs``, ``reference/pool_eviction.py``) with a plain
reclaimer where ``Scheduler.run_once`` would be: it binds a waiting gang
onto what is idle on the nodes it may use, pod by pod in bin-pack order,
and for the gang that finds those nodes full evicts whole jobs from the
client's book on them, as many as the gang needs.  ``correct`` has to come
out false, by the counts the control drops and by no other.

  mask_blind      places the gang with no regard to its node affinity or
                  to taints: it binds on the idle A100s while they last
                  (at the cell's width always: they hold two gangs, and it
                  never reclaims).  Drops: no pod on a node its constraints
                  exclude (``pods_outside_pool``, every pod of a gang it
                  bound there).
  victim_blind    takes victims in the book's order wherever they run,
                  until what they release on nodes the gang may use is
                  enough.  Drops: no pod is evicted from a node the
                  reclaimer cannot use (``evictions_on_excluded_nodes``),
                  and with it no more is taken than the reclaimer needs
                  (``evictions_beyond_need``): what this repo's solver did
                  before PR 44 wherever its prescreen would have run.
  selector_blind  nominates an idle A100 node for the first victim of
                  every cycle to run on again, though the victim is pinned
                  to a Hopper pool by its ``nodeSelector``.  Drops: a
                  victim is placed again inside its own pool alone
                  (``pods_outside_pool``, one a reclaim).
  sound           drops nothing: comes out correct, which shows that the
                  controls fail by what they drop and not by the plain
                  reclaimer.

    JAX_PLATFORMS=cpu python3 benchmark/tests/control_pools.py \\
        --workload pools98k-pytorchjob-256 --seeds 1,2,3

runs them at the cell's own width (no device is used: the fleet is built
and the reclaimer is numpy); exit 0 = ``sound`` correct and the three
others not, each by its own counts alone.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KINDS = ("mask_blind", "victim_blind", "selector_blind")
MOVES = {"mask_blind": {"pods_outside_pool"},
         "victim_blind": {"evictions_on_excluded_nodes",
                          "evictions_beyond_need"},
         "selector_blind": {"pods_outside_pool"}, "sound": set()}


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
    terms, tolerations = cell.generator.gang_constraints(cell.traffic)
    gang_row = ref.admitted(ledger.node_labels, ledger.node_taints, {},
                            terms, tolerations)
    anywhere = np.ones(ledger.n, bool)

    def control_cycle():
        """Stands where run_once stands."""
        used, pods = ledger.used.copy(), ledger.pods.copy()
        for gang, _pg in client.pending:
            state = (ledger.capacity, used, pods, ledger.max_pods, gang.req)
            nodes = ref.place_gang(
                *state, anywhere if kind == "mask_blind" else gang_row)
            if nodes is not None:
                cache.bound.extend(
                    (name, gen.node_name(node))
                    for name, node in zip(gang.names, nodes.tolist()))
                np.add.at(used, nodes, gang.req)
                np.add.at(pods, nodes, 1)
                continue
            placed, _left = ref.pods_that_fit(*state, gang_row)
            need = int((~placed).sum())
            victims, released = [], 0
            for uid in sorted(client.jobs):
                job = client.jobs[uid]
                if not job.preemptible or job.queue == gang.queue:
                    continue
                if released >= need:
                    break
                useful = bool(gang_row[next(iter(job.pods.values()))])
                if useful or kind == "victim_blind":
                    victims.extend(job.pods)
                    released += len(job.pods) * useful
            cache.evicted.extend(victims)
            if kind == "selector_blind" and victims:
                cache.pipelined.append(
                    (victims[0], gen.node_name(int(client.idle_nodes[0]))))

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
