"""The controls: the reference with one guarantee dropped, put in the
program's place.

Each drives the harness's own loop and comparison with a placer where
``Scheduler.run_once`` would be, at a cell's own size, and prints the
numbers compared: ``correct`` has to come out false.

  stale           ``place_gang_stale``: every pod of a gang scored against
                  the state before the gang (one batched scoring, no pod
                  seeing what the pods before it took).  Drops: no node is
                  filled past its capacity.  The shortcut that tempts.
  no_topology     the gang placed over the whole fleet.  Drops: the
                  preferred (or required) topology level.
  no_queue_limit  the gang placed without asking its queues.  Drops: no
                  queue passes its limit.  Shows only where a limit is
                  near (``benchmark/tests`` has such a fixture).

    python3 benchmark/tests/control.py --workload tas65k-pytorchjob-16k --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KINDS = ("stale", "no_topology", "no_queue_limit")


def run_control(workload: str, seed: int, kind: str = "stale",
                cycles: int = 2, root: str = ROOT) -> dict:
    from benchmark.harness import cluster as gen
    from benchmark.harness import spec

    cell = spec.Cell(spec.load_benchmark(root), workload, root)
    ref = cell.reference
    client = cell.generator.build(cell, seed)
    ledger = client.ledger

    def control_cycle():
        """Stands where run_once stands: places the pending gang by the
        control and hands the binds to the cache the harness reads."""
        gang = client.pending_gang
        state = (ledger.capacity, ledger.used, ledger.pods, ledger.max_pods,
                 gang.req)
        levels = cell.generator.level_order(cell.config, gang.topology)
        if kind == "no_topology":
            nodes = ref.place_gang(*state)
        elif kind == "no_queue_limit":
            nodes = ref.schedule_gang(*state, gang.topology, ledger.levels,
                                      levels)
        else:
            subset = None
            if gang.topology:
                subset = ref.topology_candidates(
                    *state, ledger.levels, levels,
                    gang.topology.get("required"),
                    gang.topology.get("preferred"))[0][3]
            nodes = ref.place_gang_stale(*state, subset=subset)
        client.sched.cache.bound.extend(
            (name, gen.node_name(int(i)))
            for name, i in zip(gang.names, nodes))

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
    ap.add_argument("--kinds", default="stale,no_topology")
    args = ap.parse_args(argv)
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in args.kinds.split(","):
            out = run_control(args.workload, seed, kind)
            print(json.dumps(out), flush=True)
            bad += bool(out["correct"])
    return 1 if bad else 0   # every control has to fail the comparison


if __name__ == "__main__":
    sys.exit(main())
