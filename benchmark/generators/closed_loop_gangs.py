"""The generator ``closed_loop_gangs``: one gang a cycle, in a closed loop.

The client holds one ``ClusterInfo`` and drives ``Scheduler.run_once`` over
it: before a cycle the gang that was bound ``lifetime_cycles`` cycles ago
completes and is removed, and the mix's next gang arrives as a pending
PodGroup; after the cycle the pods it bound are running.  A gang that does
not bind in its own cycle has failed: nothing stays pending.  The
scheduler is the configuration's (``loop.scheduler_config``).

The module gives what ``run.py`` and ``preflight.py`` ask of a generator:
``build`` (the client), ``prime``, ``compare``, ``kernel_shapes``,
``reckon`` and ``compile_for``.

The comparison reads what the timed cycles themselves bound, at the timed
sizes, and holds it to the guarantees the configuration states and to the
plain reference the configuration names (``reference/placement.py``):

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

import gc
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.harness import cluster as gen
from benchmark.harness import loop


@dataclass
class CycleRecord:
    index: int
    gang: gen.Gang
    used_before: np.ndarray          # [N,3] the ledger before the cycle
    pods_before: np.ndarray          # [N]
    queue_used_before: dict
    t_sched: float = 0.0             # perf_counter at run_once
    foreign_binds: int = 0           # binds of pods that are not the gang's
    counters: dict = field(default_factory=dict)   # program counter deltas
    spans: list = field(default_factory=list)   # flight-recorder spans
    trace_t0: float = 0.0            # perf_counter origin of the spans


class Client:
    """The closed loop over one fleet."""

    def __init__(self, cell, seed: int, counters: tuple = ()):
        """``counters``: names in the program's metrics registry whose
        per-cycle movement the per-layer readers ask for."""
        from kai_scheduler_tpu.scheduler import Scheduler
        self.config = config = cell.config
        self.traffic = traffic = cell.traffic
        settings = loop.scheduler_config(config, cell.config_path)
        self.cluster, self.ledger = gen.build_fleet(config, seed)
        self.sched = Scheduler(lambda: self.cluster, settings)
        self.rng = np.random.default_rng([int(seed), 2])
        self.leaves = gen.leaf_queues(self.ledger)
        self.node_index = {gen.node_name(i): i
                           for i in range(self.ledger.n)}
        self.live: list[tuple[gen.Gang, object]] = []   # (gang, podgroup)
        self.lifetime = int(traffic["lifetime_cycles"])
        self.next_index = 0
        self.records: list[CycleRecord] = []
        self.pending_gang: gen.Gang | None = None   # the cycle's arrival
        self.counters = tuple(counters)

    # -- what the client does between cycles -------------------------------
    def _complete(self, gang: gen.Gang, pg) -> None:
        cluster = self.cluster
        for task in pg.pods.values():
            node = cluster.nodes.get(task.node_name)
            if node is not None:
                node.remove_task(task)
        del cluster.podgroups[pg.uid]
        cluster.invalidate_aggregates()
        self._charge(gang, -1.0)

    def _charge(self, gang: gen.Gang, sign: float) -> None:
        """Enter (or with -1 take out) the gang's bound pods in the ledger."""
        if not gang.bound:
            return
        row = {n: i for i, n in enumerate(gang.names)}
        names = list(gang.bound)
        self.ledger.charge(gang.queue,
                           np.array([gang.bound[n] for n in names]),
                           gang.req[[row[n] for n in names]], sign)

    def _arrive(self) -> tuple:
        queue = self.leaves[int(self.rng.integers(len(self.leaves)))]
        pg, gang = gen.make_gang(self.traffic, self.next_index, queue)
        self.next_index += 1
        self.cluster.podgroups[pg.uid] = pg
        self.cluster.invalidate_aggregates()
        return gang, pg

    def _settle(self, rec: CycleRecord, pg) -> None:
        """Read what the cycle bound, as the binder would see it."""
        from kai_scheduler_tpu.api import PodStatus
        cache = self.sched.cache
        gang = rec.gang
        members = set(gang.names)
        for uid, node in cache.bound:
            if uid in members:
                gang.bound[uid] = self.node_index[node]
            else:
                rec.foreign_binds += 1
        cache.bound.clear()
        self.cluster.bind_requests.clear()
        self._charge(gang, 1.0)
        for task in pg.pods.values():
            if task.uid in gang.bound:
                pg.update_task_status(task, PodStatus.RUNNING)

    # -- one cycle ---------------------------------------------------------
    def cycle(self, annotate=None) -> CycleRecord:
        """Completions, one arrival, ``run_once``, and the binds read
        back.  ``annotate`` (the profiler's TraceAnnotation) names the
        phases on the trace's clock."""
        phase = loop.phases(annotate)
        with phase("bench:client_before"):
            while len(self.live) >= self.lifetime:
                self._complete(*self.live.pop(0))
            gang, pg = self._arrive()
        self.pending_gang = gang
        ledger = self.ledger
        rec = CycleRecord(
            index=len(self.records), gang=gang,
            used_before=ledger.used.copy(), pods_before=ledger.pods.copy(),
            queue_used_before={q: v.copy()
                               for q, v in ledger.queue_used.items()})
        self.cluster.now += 1.0
        loop.run_once(self.sched, rec, self.counters, phase)
        with phase("bench:client_after"):
            self._settle(rec, pg)
            self.live.append((gang, pg))
        self.records.append(rec)
        return rec

    def close(self) -> None:
        """Free the program's state before the comparison runs."""
        self.sched = None
        self.cluster = None
        self.live = []
        gc.collect()


def build(cell, seed: int, counters: tuple = ()) -> Client:
    return Client(cell, seed, counters)



def file_shape(cell) -> dict:
    """The exact kernel's shape as the cell's files give it: the fleet's
    nodes, the gang's pods padded to the kernel's task axis, and whether
    the gang has a topology level, which is what makes its score and mask
    operands one ``[2,N]`` row pair a job.  One label, taint, selector and
    toleration column each: the fleets here select on no label."""
    t = gen.gang_size(cell.traffic)
    return {"t": t, "t_pad": gen.padded(t),
            "nodes": int(cell.config["nodes"]["count"]), "resources": 3,
            "label_cols": 1, "taint_cols": 1, "selector_cols": 1,
            "toleration_cols": 1,
            "job_rows": bool(cell.traffic["gang"].get("topology"))}


def _lower(sds, shape: dict):
    """``allocate_jobs_kernel`` lowered in the variant a gang's cycle
    dispatches (``Session.propose_placements``, the exact path): no
    per-task ``[T,N]`` operand, and for a gang with a topology level the
    job's ``[2,N]`` score rows and mask rows.  ``sds(shape, dtype)`` makes
    an operand; float64 stands for the host's float."""
    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
    from kai_scheduler_tpu.ops.scoring import BINPACK
    n, r, t = shape["nodes"], shape["resources"], shape["t_pad"]
    f, i = np.float64, np.int32
    rows = shape["job_rows"]
    return allocate_jobs_kernel.lower(
        sds((n, r), f), sds((n, r), f), sds((n, r), f),
        sds((n, shape["label_cols"]), i), sds((n, shape["taint_cols"]), i),
        sds((n,), f),
        sds((t, r), f), sds((t,), i), sds((t, shape["selector_cols"]), i),
        sds((t, shape["toleration_cols"]), i), sds((2,), bool), None,
        task_node_mask=None, task_anti_domain=None, task_aff_domain=None,
        job_extra_scores=sds((2, n), f) if rows else None,
        job_node_mask=sds((2, n), bool) if rows else None,
        gpu_strategy=BINPACK, cpu_strategy=BINPACK,
        allow_pipeline=True, pipeline_only=False)


def prime(client: Client, watch: loop.CompileWatch) -> dict:
    """Compile the exact kernel at the cell's own shape, in the variant the
    cycle dispatches, before the first guarded dispatch: the device guard
    allows a dispatch 30 s, compile included, and past that re-runs it on
    the CPU.  The warm cycle then compiles no ``allocate_jobs_kernel``.

    The shapes come from a pack of the fleet with the mix's first gang
    pending, as the first cycle will pack it; the gang is taken out again.
    """
    from kai_scheduler_tpu.api.snapshot import pack

    pg, gang = gen.make_gang(client.traffic, 0, client.leaves[0])
    client.cluster.podgroups[pg.uid] = pg
    try:
        snap = pack(client.cluster)
    finally:
        del client.cluster.podgroups[pg.uid]
        client.cluster.invalidate_aggregates()

    shape = {"t": len(gang.names), "t_pad": gen.padded(len(gang.names)),
             "nodes": int(snap.node_idle.shape[0]),
             "resources": int(snap.node_idle.shape[1]),
             "label_cols": int(snap.node_labels.shape[1]),
             "taint_cols": int(snap.node_taints.shape[1]),
             "selector_cols": int(snap.task_selector.shape[1]),
             "toleration_cols": int(snap.task_tolerations.shape[1]),
             "job_rows": bool(gang.topology)}
    before = watch.snapshot()
    t0 = time.perf_counter()
    _lower(loop.device_operand, shape).compile()
    client.primed = shape
    return {"seconds": round(time.perf_counter() - t0, 3),
            "kernel": "allocate_jobs_kernel",
            "operands": "job rows [2,N]" if shape["job_rows"]
            else "no node-axis operand",
            "t_pad": shape["t_pad"], "nodes": shape["nodes"],
            "resources": shape["resources"],
            "label_cols": shape["label_cols"],
            "taint_cols": shape["taint_cols"],
            "cache_misses": watch.since(before)["misses"]}


def kernel_shapes(client: Client) -> dict:
    """For the roofline readers, by the reader's ``model``: the arguments
    of that model's byte count."""
    shape = client.primed
    return {"exact_scan_bytes": {
        "steps": shape["t"], "nodes": shape["nodes"],
        "resources": shape["resources"], "has_mask": shape["job_rows"],
        "label_cols": shape["label_cols"],
        "taint_cols": shape["taint_cols"]}}


def reckon(cell) -> dict:
    """What the cell's cycle holds on the device, from its files: the node
    tables, the gang's task rows and, for a gang with a topology level,
    its ``[2,N]`` f32 score rows and bool mask rows (32-bit, as on the
    chip)."""
    shape = file_shape(cell)
    n, r, t = shape["nodes"], shape["resources"], shape["t_pad"]
    tables = n * 4 * (3 * r + shape["label_cols"] + shape["taint_cols"] + 1)
    task_rows = t * 4 * (r + 1 + shape["selector_cols"]
                         + shape["toleration_cols"])
    job_rows = 2 * n * (4 + 1) if shape["job_rows"] else 0
    return {"bytes": float(tables + task_rows + job_rows),
            "what": f"allocate_jobs_kernel {shape['t_pad']} x {n} "
                    f"({shape['t']} pods), "
                    + ("[2,N] score and mask rows"
                       if shape["job_rows"] else "no node-axis operand")
                    + f": node tables {tables:,} + task rows "
                    f"{task_rows:,} + job rows {job_rows:,} bytes"}


def compile_for(cell, sds):
    """The cycle's kernel compiled at the cell's shape with the operands
    ``sds(shape, dtype)`` makes (``preflight.py``: on a described v5e)."""
    return _lower(sds, file_shape(cell)).compile()


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


def numbers(records, ledger, config, ref) -> dict:
    """{name: value} for every number in ``LIMITS``, plus counts, against
    the reference module ``ref``."""
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


def compare(records, ledger, cell) -> dict:
    """The verdict on the window's ``records``: ``correct``, each number
    ``compared`` as [value, limit] in the order of ``LIMITS``, the three
    counts of the result line, and what the ``run`` line says of the
    comparison."""
    nums = numbers(records, ledger, cell.config, cell.reference)
    compared = {k: [nums[k], LIMITS[k]] for k in LIMITS}
    return {
        "correct": all(v <= lim for v, lim in compared.values()),
        "compared": compared,
        "attempted": len(records),
        "bound_pods": sum(len(r.gang.bound) for r in records),
        "failed": sum(1 for r in records
                      if len(r.gang.bound) != len(r.gang.names)),
        "run": {"gangs": nums["gangs"],
                "gang_roles": [{"name": r["name"], "count": int(r["count"])}
                               for r in cell.traffic["gang"]["roles"]],
                "placements_checked": nums["placements_checked"]}}
