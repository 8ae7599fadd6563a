"""The generator ``reclaim_gangs``: a gang arrives every cycle in a queue
under its share while the fleet is full and a queue over its share holds
what the gang's queue left unused.

The fleet is full (``occupancy`` of the configuration): a share of the
nodes under the occupying queue's preemptible jobs (``job_pods`` one-GPU
pods with a gang minimum below that, so each has a surplus to shed first),
the others under one non-preemptible whole-node pod each, of the other
queues, each within its deserved share.  Every cycle the mix's gang
arrives in a leaf queue of another department than the occupier's, under
its share.  The reclaim action evicts its victims and pipelines the gang
onto what they release; the client reads the evictions back, removes the
victims as their kubelets would, and shows the gang pending again, as the
apiserver would.
The next cycle's allocate action binds it, while the same cycle reclaims
for the gang that arrives then: once warm, every cycle holds one reclaim
and one bind.  A bound gang runs ``lifetime_cycles`` cycles, completes, and
the occupying queue's new jobs fill what it leaves.  A gang may stay
pending ``pending_cycles_max`` cycles.

The comparison holds every cycle of the window to the guarantees the
configuration states, by the client's own ledger and its book of the jobs
it submitted, with the plain reference the configuration names
(``reference/eviction.py``); every limit is 0.

Grown from ``benchmark/tests/data/tiny/generators/reclaim_gangs.py`` (a
wave of two cycles at 64 nodes), which the fixture benchmark keeps.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.harness import cluster as gen
from benchmark.harness import loop
from kai_scheduler_tpu.api import (ClusterInfo, NodeInfo, PodGroupInfo,
                                   PodInfo, PodStatus)
from kai_scheduler_tpu.api.resources import ResourceRequirements

PRESCREEN_ARRAYS = 7     # [K,N,R] f32 arrays the prescreen kernel makes


@dataclass
class Job:
    """What the client knows of a job it submitted."""
    uid: str
    queue: str
    preemptible: bool
    min_available: int
    req: np.ndarray                  # [3] of every pod
    pods: dict                       # pod name -> node index, running
    rr: object = None                # the pods' ResourceRequirements


@dataclass
class Victim:
    """An evicted pod, with what the client's book says of its job."""
    pod: str
    job: str
    queue: str
    preemptible: bool
    min_available: int
    node: int
    req: np.ndarray                  # [3]


@dataclass
class CycleRecord:
    index: int
    pending: list                    # gangs pending in this cycle, oldest first
    arrived: gen.Gang | None         # the gang that arrived in this cycle
    used_before: np.ndarray          # [N,3] the ledger before the cycle
    pods_before: np.ndarray          # [N]
    queue_used_before: dict          # queue -> [3], leaf to root
    running_before: dict = field(default_factory=dict)   # job -> pods
    evicted: list = field(default_factory=list)   # Victim, read back
    unknown_evictions: int = 0       # evicted pods the client never had
    bound: dict = field(default_factory=dict)     # gang uid -> {pod: node}
    foreign_binds: int = 0
    used_after: np.ndarray | None = None
    pods_after: np.ndarray | None = None
    t_sched: float = 0.0
    counters: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    trace_t0: float = 0.0

    @property
    def reclaimer(self):
        """The gang the cycle's evictions are for: the youngest pending
        one that the cycle did not bind."""
        waiting = [g for g in self.pending if g.uid not in self.bound]
        return waiting[-1] if waiting else None


def _requirements(pod: dict) -> ResourceRequirements:
    return ResourceRequirements.from_spec(pod.get("cpu"), pod.get("memory"),
                                          pod.get("gpu", 0))


class Client:
    """One reclaim and one bind a cycle over one full fleet."""

    def __init__(self, cell, seed: int, counters: tuple = ()):
        from kai_scheduler_tpu.scheduler import Scheduler
        self.cell = cell
        self.config = config = cell.config
        self.traffic = traffic = cell.traffic
        settings = loop.scheduler_config(config, cell.config_path)
        rng = np.random.default_rng([int(seed), 1])
        self.ledger = ledger = gen.Ledger(config)
        shape = config["nodes"]
        alloc = gen.res_vec(shape)
        self.node_names = [gen.node_name(i) for i in range(ledger.n)]
        nodes = {name: NodeInfo(name, alloc, labels={},
                                max_pods=ledger.max_pods)
                 for name in self.node_names}
        queues = gen.build_queues(config, ledger)
        leaves = gen.leaf_queues(ledger)
        # Which queue holds the fleet and which one reclaims: from the
        # seed, in different departments.
        order = rng.permutation(len(leaves))
        self.occupier = leaves[int(order[0])]
        self.reclaimer = next(
            leaves[int(i)] for i in order[1:]
            if ledger.queue_parent[leaves[int(i)]]
            != ledger.queue_parent[self.occupier])
        self.cluster = ClusterInfo(nodes, {}, queues, topologies={},
                                   now=1000.0)
        occ = config["occupancy"]
        self.occ_req = gen.res_vec(occ["pod"])
        # One requirements object for every pod of a template, as pods of
        # one template have: the queue roll-up counts per object.
        self.occ_rr = _requirements(occ["pod"])
        self.jobs: dict[str, Job] = {}
        self.pod_job: dict[str, str] = {}
        self.next_job = 0
        self.node_order = rng.permutation(ledger.n)
        self.node_rank = np.empty(ledger.n, np.int64)
        self.node_rank[self.node_order] = np.arange(ledger.n)
        # The fleet's jobs are made with the cyclic collector off: some
        # hundred thousand objects that all stay alive, which it would
        # walk again and again while they are made.
        gc.disable()
        try:
            self._hold_whole_nodes([leaves[int(i)] for i in order])
            self._fill()
        finally:
            gc.enable()
        self.sched = Scheduler(lambda: self.cluster, settings)
        self.lifetime = int(traffic["lifetime_cycles"])
        self.node_index = {name: i for i, name in enumerate(self.node_names)}
        self.pending: list = []      # [(Gang, PodGroupInfo)], oldest first
        self.running: list = []      # [[Gang, PodGroupInfo, cycles run]]
        self.gangs: list[gen.Gang] = []
        self.records: list[CycleRecord] = []
        self.counters = tuple(counters)

    # -- the occupying queue ----------------------------------------------
    def _hold_whole_nodes(self, leaves: list) -> None:
        """The nodes that the configuration's ``occupancy`` leaves to
        ``whole_node`` pods: all but ``preemptible_nodes_share`` of the
        fleet, the last in the seed's order, each under one running
        non-preemptible pod of a gang of ``gang_pods``.  The gangs go to
        the leaf queues but the occupier's, in the order given and the
        reclaimer's last, each queue up to its deserved share of the
        nodes."""
        occ = self.config["occupancy"]
        ledger = self.ledger
        held = self.node_order[
            int(round(ledger.n * float(occ["preemptible_nodes_share"]))):]
        whole = occ["whole_node"]
        req, rr = gen.res_vec(whole["pod"]), _requirements(whole["pod"])
        size = int(whole["gang_pods"])
        start = 0
        others = [q for q in leaves
                  if q not in (self.occupier, self.reclaimer)]
        for queue in others + [self.reclaimer]:
            room = ledger.n // len(leaves)
            while start < len(held) and room > 0:
                nodes = held[start:start + min(size, room)]
                start += len(nodes)
                room -= len(nodes)
                uid = f"whole-{self.next_job:06d}"
                self.next_job += 1
                job = Job(uid, queue, bool(whole["preemptible"]),
                          len(nodes), req,
                          {f"{uid}-{k}": int(node)
                           for k, node in enumerate(nodes)}, rr)
                self._book(job)
                ledger.charge(queue, nodes, np.tile(req, (len(nodes), 1)))
        if start < len(held):
            raise SystemExit(
                f"{self.cell.config_path}: the other queues' deserved "
                f"shares hold {start} of the {len(held)} nodes that "
                f"\"occupancy\" leaves to whole-node pods")

    def _book(self, job: Job) -> None:
        """Enter a new job in the book and show it to the cluster."""
        self.jobs[job.uid] = job
        for name in job.pods:
            self.pod_job[name] = job.uid
        self._show(job)

    def _fill(self, left=None) -> None:
        """Running jobs of the occupying queue on every node that has a
        whole job's resources free, nodes in the seed's order; with
        ``left`` ([N,3], what a completed gang left) as many on each node
        as what it left there holds, so that what a cycle's evictions
        freed stays free for the gang they were for."""
        occ = self.config["occupancy"]
        pods, req = int(occ["job_pods"]), self.occ_req
        ledger = self.ledger
        need = pods * req

        def holds(free):
            return np.floor(np.min(
                (free[:, need > 0] + 1e-9) / need[need > 0], axis=1))
        fits = np.minimum(holds(ledger.capacity - ledger.used),
                          (ledger.max_pods - ledger.pods) // pods)
        if left is not None:
            fits = np.minimum(fits, holds(left))
        open_nodes = np.flatnonzero(fits >= 1)
        if not open_nodes.size:
            return
        open_nodes = open_nodes[np.argsort(self.node_rank[open_nodes])]
        charged = []
        for node in open_nodes.tolist():
            for _ in range(int(fits[node])):
                uid = f"occ-{self.next_job:06d}"
                self.next_job += 1
                self._book(Job(
                    uid, self.occupier, bool(occ["preemptible"]),
                    int(occ["min_available"]), req,
                    {f"{uid}-{k}": node for k in range(pods)}, self.occ_rr))
                charged.append(node)
        idx = np.repeat(np.array(charged), pods)
        ledger.charge(self.occupier, idx, np.tile(req, (len(idx), 1)))
        self.cluster.invalidate_aggregates()

    def _show(self, job: Job) -> None:
        """Put the job into the cluster as the book has it, in place of
        what the cluster had of it."""
        cluster = self.cluster
        old = cluster.podgroups.pop(job.uid, None)
        if old is not None:
            for task in old.pods.values():
                cluster.nodes[task.node_name].remove_task(task)
        if not job.pods:
            return
        pg = PodGroupInfo(job.uid, job.uid, queue_id=job.queue,
                          min_available=job.min_available,
                          preemptible=job.preemptible)
        for name, node in job.pods.items():
            task = PodInfo(uid=name, name=name, res_req=job.rr,
                           status=PodStatus.RUNNING,
                           node_name=self.node_names[node])
            pg.add_task(task)
            cluster.nodes[task.node_name].add_task(task)
        cluster.podgroups[job.uid] = pg

    def _remove(self, victims: list) -> None:
        """The victims' pods are gone: from the book, the ledger and the
        cluster."""
        touched = {}
        for v in victims:
            job = self.jobs[v.job]
            del job.pods[v.pod]
            del self.pod_job[v.pod]
            touched[job.uid] = job
            self.ledger.charge(job.queue, np.array([v.node]),
                               v.req[None, :], -1.0)
        for job in touched.values():
            self._show(job)
            if not job.pods:
                del self.jobs[job.uid]

    def _complete(self, gang: gen.Gang, pg) -> np.ndarray:
        """The gang is done: its pods leave the cluster and the ledger.
        Returns [N,3], what it leaves free on each node."""
        for task in pg.pods.values():
            node = self.cluster.nodes.get(task.node_name)
            if node is not None:
                node.remove_task(task)
        del self.cluster.podgroups[pg.uid]
        names = list(gang.bound)
        row = {n: i for i, n in enumerate(gang.names)}
        nodes = np.array([gang.bound[n] for n in names])
        req = gang.req[[row[n] for n in names]]
        self.ledger.charge(gang.queue, nodes, req, -1.0)
        left = np.zeros_like(self.ledger.used)
        np.add.at(left, nodes, req)
        self.cluster.invalidate_aggregates()
        return left

    # -- one cycle ---------------------------------------------------------
    def _before(self) -> gen.Gang:
        """Completions and refills, and the cycle's arrival."""
        done = [r for r in self.running if r[2] >= self.lifetime]
        for r in self.running:
            r[2] += 1
        if done:
            self.running = [r for r in self.running if r not in done]
            self._fill(sum(self._complete(gang, pg)
                           for gang, pg, _ran in done))
        pg, gang = gen.make_gang(self.traffic, len(self.gangs),
                                 self.reclaimer)
        pg.creation_ts = self.cluster.now
        self.gangs.append(gang)
        self.cluster.podgroups[pg.uid] = pg
        self.cluster.invalidate_aggregates()
        self.pending.append((gang, pg))
        return gang

    def _settle(self, rec: CycleRecord) -> None:
        """Read back what the cycle evicted and bound, as the binder and
        the kubelets would see it."""
        cache = self.sched.cache
        for pod in cache.evicted:
            job = self.jobs.get(self.pod_job.get(pod))
            if job is None:
                rec.unknown_evictions += 1
                continue
            rec.evicted.append(Victim(pod, job.uid, job.queue,
                                      job.preemptible, job.min_available,
                                      job.pods[pod], job.req))
        rec.running_before = {v.job: len(self.jobs[v.job].pods)
                              for v in rec.evicted}
        self._remove(rec.evicted)
        cache.evicted.clear()
        cache.pipelined.clear()
        member = {name: gang.uid for gang, _pg in self.pending
                  for name in gang.names}
        for uid, node in cache.bound:
            gang_uid = member.get(uid)
            if gang_uid is None:
                rec.foreign_binds += 1
            else:
                rec.bound.setdefault(gang_uid, {})[uid] = \
                    self.node_index[node]
        cache.bound.clear()
        self.cluster.bind_requests.clear()
        still = []
        for gang, pg in self.pending:
            bound = rec.bound.get(gang.uid)
            if bound:
                gang.bound.update(bound)
                names = list(bound)
                row = {n: i for i, n in enumerate(gang.names)}
                self.ledger.charge(
                    gang.queue, np.array([bound[n] for n in names]),
                    gang.req[[row[n] for n in names]])
                for task in pg.pods.values():
                    if task.uid in bound:
                        pg.update_task_status(task, PodStatus.RUNNING)
                self.running.append([gang, pg, 0])
            else:
                # Pipelined onto what the victims release: the pods are
                # still pending at the apiserver.
                for task in pg.pods.values():
                    if task.status == PodStatus.PIPELINED:
                        self.cluster.nodes[task.node_name].remove_task(task)
                        task.node_name = ""
                        pg.update_task_status(task, PodStatus.PENDING)
                still.append((gang, pg))
        self.pending = still
        self.cluster.invalidate_aggregates()
        rec.used_after = self.ledger.used.copy()
        rec.pods_after = self.ledger.pods.copy()

    def cycle(self, annotate=None) -> CycleRecord:
        phase = loop.phases(annotate)
        with phase("bench:client_before"):
            arrived = self._before()
        ledger = self.ledger
        rec = CycleRecord(
            index=len(self.records),
            pending=[gang for gang, _pg in self.pending], arrived=arrived,
            used_before=ledger.used.copy(), pods_before=ledger.pods.copy(),
            queue_used_before={q: v.copy()
                               for q, v in ledger.queue_used.items()})
        self.cluster.now += 1.0
        loop.run_once(self.sched, rec, self.counters, phase)
        with phase("bench:client_after"):
            self._settle(rec)
        self.records.append(rec)
        return rec

    def close(self) -> None:
        self.sched = None
        self.cluster = None
        self.pending = []
        self.running = []
        gc.collect()


def build(cell, seed: int, counters: tuple = ()) -> Client:
    return Client(cell, seed, counters)


# -- the kernels of the cycle -------------------------------------------------
def file_shape(cell) -> dict:
    """The prescreen's shape as the cell's files give it.  The solver
    considers ``max_victims_considered`` victims, each in two steps (its
    surplus, then its core gang); the first step is simulated and fails,
    and the prescreen scores the next ``scenario_prescreen_max``."""
    occ, settings = cell.config["occupancy"], cell.config["scheduler"]
    n = int(cell.config["nodes"]["count"])
    jobs = int(round(n * float(occ["preemptible_nodes_share"]))) \
        * int(cell.config["nodes"]["gpu"]) // int(occ["job_pods"])
    victims = min(jobs, int(settings["max_victims_considered"]))
    steps = min(2 * victims - int(settings["scenario_prescreen_after"]),
                int(settings["scenario_prescreen_max"]))
    surplus = int(occ["job_pods"]) - int(occ["min_available"])
    # Steps alternate core gang, surplus, core gang, ...
    rows = (steps + 1) // 2 * int(occ["min_available"]) \
        + steps // 2 * surplus
    # The solver confirms a scenario in one exact scan over the gang and
    # what it would re-place of the victims: in the first scenario one pod
    # of the job that shed its surplus (a job over its minimum grows a pod
    # at a time), in the last one the core gang of every job that went
    # whole, a one-GPU victim a pod of the gang.
    t = gen.gang_size(cell.traffic)
    whole = t // int(occ["job_pods"])
    confirms = [(t + 1, 2), (t + whole * int(occ["min_available"]),
                             whole + 1)]
    return {"prefixes": gen.padded(steps), "rows": gen.padded(rows),
            "nodes": n, "resources": 3, "t": t, "t_pad": gen.padded(t),
            "confirm_steps": sum(tasks for tasks, _jobs in confirms),
            # With the task rows' padding job.
            "confirms": [[gen.padded(tasks), gen.padded(jobs + 1)]
                         for tasks, jobs in confirms],
            "label_cols": 1, "taint_cols": 1, "selector_cols": 1,
            "toleration_cols": 1}


def _node_tables(sds, shape: dict) -> tuple:
    """The six node arrays every kernel of the cycle takes first."""
    n, r = shape["nodes"], shape["resources"]
    f, i = np.float64, np.int32
    return (sds((n, r), f), sds((n, r), f), sds((n, r), f),
            sds((n, shape["label_cols"]), i),
            sds((n, shape["taint_cols"]), i), sds((n,), f))


def _lower(sds, shape: dict):
    """``batch_prefix_feasibility`` lowered as ``_prefix_prescreen``
    dispatches it."""
    from kai_scheduler_tpu.ops.scenario_batch import \
        batch_prefix_feasibility
    from kai_scheduler_tpu.ops.scoring import BINPACK
    r, t, m = shape["resources"], shape["t_pad"], shape["rows"]
    f, i = np.float64, np.int32
    return batch_prefix_feasibility.lower(
        *_node_tables(sds, shape),
        sds((m,), i), sds((m,), i), sds((m, r), f),
        sds((t, r), f), sds((t,), i), sds((t, shape["selector_cols"]), i),
        sds((t, shape["toleration_cols"]), i),
        num_prefixes=shape["prefixes"], gpu_strategy=BINPACK,
        cpu_strategy=BINPACK)


def _lower_fill(sds, shape: dict):
    """The grouped fill lowered as the allocate action dispatches it for
    the gang, one group of identical pods (``allocate_grouped`` behind
    ``Session.propose_placements``): the attempt that finds the fleet
    full, and a cycle later the bind."""
    from kai_scheduler_tpu.ops.allocate_grouped import (
        _allocate_groups_packed, _resolve_fused_mode)
    from kai_scheduler_tpu.ops.scoring import BINPACK
    r = shape["resources"]
    f, i = np.float64, np.int32
    return _allocate_groups_packed.lower(
        *_node_tables(sds, shape),
        sds((1, r), f), sds((1, shape["selector_cols"]), i),
        sds((1, shape["toleration_cols"]), i), sds((1,), f), sds((1,), i),
        sds((1,), bool), max_group=shape["t_pad"], t_pad=shape["t_pad"],
        group_indep=sds((1,), bool), gpu_strategy=BINPACK,
        cpu_strategy=BINPACK, allow_pipeline=True, pipeline_only=False,
        single_group_jobs=True,
        fused_mode=_resolve_fused_mode(None, shape["nodes"]),
        releasing_empty=True, f32_keys=False)


def _lower_confirm(sds, shape: dict, t_pad: int, j_pad: int):
    """The exact scan lowered as the solver's confirm dispatches it
    (``_batched_confirm`` behind ``propose_placements_multi``): several
    jobs, pipeline only, no node-axis operand."""
    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
    from kai_scheduler_tpu.ops.scoring import BINPACK
    f, i = np.float64, np.int32
    return allocate_jobs_kernel.lower(
        *_node_tables(sds, shape),
        sds((t_pad, shape["resources"]), f), sds((t_pad,), i),
        sds((t_pad, shape["selector_cols"]), i),
        sds((t_pad, shape["toleration_cols"]), i), sds((j_pad,), bool), None,
        task_node_mask=None, task_anti_domain=None, task_aff_domain=None,
        job_extra_scores=None, job_node_mask=None,
        gpu_strategy=BINPACK, cpu_strategy=BINPACK,
        allow_pipeline=True, pipeline_only=True)


def prime(client: Client, watch: loop.CompileWatch) -> dict:
    """Compile the programs of the cycle, each at the shape the cycle
    dispatches it, before the first guarded dispatch (the device guard
    gives a dispatch 30 s, compile included): the prescreen kernel of the
    reclaim action, the grouped fill of the allocate action, and the exact
    scan of the solver's confirm in its two shapes."""
    shape = file_shape(client.cell)
    sds = loop.device_operand
    lowerings = {"batch_prefix_feasibility": lambda: _lower(sds, shape),
                 "_allocate_groups_packed": lambda: _lower_fill(sds, shape)}
    for t_pad, j_pad in shape["confirms"]:
        lowerings[f"allocate_jobs_kernel[{t_pad},{j_pad}]"] = \
            lambda t=t_pad, j=j_pad: _lower_confirm(sds, shape, t, j)
    before = watch.snapshot()
    t0 = time.perf_counter()
    seconds = {}
    for name, lower in lowerings.items():
        t = time.perf_counter()
        lower().compile()
        seconds[name] = round(time.perf_counter() - t, 3)
    client.primed = shape
    return {"seconds": round(time.perf_counter() - t0, 3),
            "kernel": "batch_prefix_feasibility", "kernels": seconds,
            **shape, "cache_misses": watch.since(before)["misses"]}


def prefix_feasibility_bytes(prefixes: int, nodes: int,
                             resources: int = 3) -> float:
    """Bytes one prescreen call must move at the least: it writes and
    reads again one f32 releasing pool ``[K,N,R]``, the K states of the
    fleet it scores.  NOT the exact scan's bytes a step times the gang's
    pods times K: the gang is one group of identical pods, and a prescreen
    that scores a prefix in one group step is no faster than the chip
    allows."""
    return 2.0 * prefixes * nodes * resources * 4


def exact_scan_bytes(steps: int, nodes: int, resources: int = 3,
                     label_cols: int = 0, taint_cols: int = 0) -> float:
    """Bytes the solver's confirms must move in a cycle, over ``steps``
    real pods (the reclaimer's and the victims it would re-place, both
    confirms together).  A step reads allocatable, idle and releasing
    [N,R] f32, pod room [N] and the label and taint tables; a confirm has
    no score row and no mask row, so ``benchmark/roofline.py``'s count of
    those is left out."""
    per_step = 3 * nodes * resources * 4 + nodes * 4 \
        + nodes * 4 * (label_cols + taint_cols)
    return float(steps) * per_step


def kernel_shapes(client: Client) -> dict:
    shape = client.primed
    return {
        "prefix_feasibility_bytes": {
            "prefixes": shape["prefixes"], "nodes": shape["nodes"],
            "resources": shape["resources"]},
        "exact_scan_bytes": {
            "steps": shape["confirm_steps"],
            "nodes": shape["nodes"], "resources": shape["resources"],
            "label_cols": shape["label_cols"],
            "taint_cols": shape["taint_cols"]}}


def reckon(cell) -> dict:
    """What the reclaim cycle holds on the device, from the files.  The
    client's buffers are the kernel's operands (node tables, release rows,
    task rows); the program's temporaries are ``[K,N,R]`` f32 arrays (the
    scattered releases, their running sum, the pools, and the vmapped
    scan's carries), each prefix another state of the fleet."""
    shape = file_shape(cell)
    k, n, r = shape["prefixes"], shape["nodes"], shape["resources"]
    operands = 4 * (n * (3 * r + shape["label_cols"] + shape["taint_cols"]
                         + 1)
                    + shape["rows"] * (2 + r)
                    + shape["t_pad"] * (r + 1 + shape["selector_cols"]
                                        + shape["toleration_cols"]))
    one = k * n * r * 4
    return {"bytes": float(operands),
            "program_bytes": float(PRESCREEN_ARRAYS * one),
            "what": f"batch_prefix_feasibility [K={k}, N={n}, R={r}] f32 "
                    f"= {one:,} bytes an array x {PRESCREEN_ARRAYS}, "
                    f"operands {operands:,} bytes"}


def compile_for(cell, sds):
    return _lower(sds, file_shape(cell)).compile()


# -- the comparison ---------------------------------------------------------
LIMITS = {
    "gangs_not_bound": 0, "gangs_partly_bound": 0, "foreign_binds": 0,
    "nodes_over_capacity": 0, "victims_not_preemptible": 0,
    "victims_from_own_queue": 0, "evictions_without_reclaimer": 0,
    "unknown_evictions": 0, "victim_gangs_below_minimum": 0,
    "evictions_beyond_need": 0, "victim_queue_below_quota": 0,
}


def deserved_shares(config: dict, ledger, ref) -> dict:
    """queue -> [3] deserved share, by the configuration's queue tree."""
    tree = config["queues"]
    total = ledger.capacity.sum(axis=0)
    return {q: ref.deserved_share(
        total, int(tree["departments"]), int(tree["leaves_per_department"]),
        leaf=parent is not None)
        for q, parent in ledger.queue_parent.items()}


def compare(records, ledger, cell) -> dict:
    """The verdict on the window's ``records``.  A gang is attempted where
    it arrived in the window with ``pending_cycles_max`` cycles left to
    bind in."""
    ref = cell.reference
    pending_max = int(cell.traffic["pending_cycles_max"])
    deserved = deserved_shares(cell.config, ledger, ref)
    out = {k: 0 for k in LIMITS}
    evictions, binds, prescreens = [], [], []
    bound_in = {}                    # gang uid -> index of its bind cycle
    for rec in records:
        reclaimer = rec.reclaimer
        queue = reclaimer.queue if reclaimer else None
        faults = ref.victim_faults(
            [(v.queue, v.preemptible) for v in rec.evicted], queue)
        for name, value in faults.items():
            out[name] += value
        gone = {}
        for v in rec.evicted:
            gone[v.job] = gone.get(v.job, 0) + 1
        out["victim_gangs_below_minimum"] += ref.gangs_left_below_minimum(
            rec.running_before, gone,
            {v.job: v.min_available for v in rec.evicted})
        out["unknown_evictions"] += rec.unknown_evictions
        out["foreign_binds"] += rec.foreign_binds
        out["nodes_over_capacity"] += ref.nodes_over_capacity(
            ledger.capacity, rec.used_after, rec.pods_after,
            ledger.max_pods)
        # What the cycle bound, entered in the ledger it started from:
        # the state the reclaimer's gang met.
        used, pods = rec.used_before.copy(), rec.pods_before.copy()
        for gang in rec.pending:
            bound = rec.bound.get(gang.uid, {})
            faults = ref.gang_faults(len(bound), len(gang.names))
            out["gangs_partly_bound"] += faults["gangs_partly_bound"]
            if bound:
                bound_in[gang.uid] = rec.index
                row = {n: i for i, n in enumerate(gang.names)}
                names = list(bound)
                nodes = np.array([bound[n] for n in names])
                np.add.at(used, nodes, gang.req[[row[n] for n in names]])
                np.add.at(pods, nodes, 1)
        if reclaimer is not None and rec.evicted:
            fewest = ref.fewest_evictions(
                ledger.capacity, used, pods, ledger.max_pods, reclaimer.req,
                np.array([v.req for v in rec.evicted]))
            out["evictions_beyond_need"] += max(
                0, len(rec.evicted) - fewest)
        lost = {}
        for v in rec.evicted:
            lost.setdefault(v.queue, []).append(v.req)
        asked = sum((g.req.sum(axis=0) for g in rec.pending
                     if g.queue == queue), np.zeros(3))
        out["victim_queue_below_quota"] += ref.victim_queue_below_quota(
            deserved, rec.queue_used_before, lost, queue, asked)
        evictions.append(len(rec.evicted))
        binds.append(sum(len(b) for b in rec.bound.values()))
        prescreens.append(sum(1 for span in rec.spans
                              if span[0] == "dispatch:scenario_prescreen"))
    last = records[-1].index
    due = [r for r in records
           if r.arrived is not None and r.index + pending_max - 1 <= last]
    late = [r for r in due
            if bound_in.get(r.arrived.uid, last + 1)
            > r.index + pending_max - 1]
    out["gangs_not_bound"] = len(late)
    compared = {k: [out[k], LIMITS[k]] for k in LIMITS}
    return {
        "correct": all(v <= lim for v, lim in compared.values()),
        "compared": compared, "attempted": len(due),
        "bound_pods": sum(binds), "failed": len(late),
        "run": {"gangs": len(due), "evictions": sum(evictions),
                "evictions_per_cycle": sorted(set(evictions)),
                "binds_per_cycle": sorted(set(binds)),
                "prescreens_per_cycle": sorted(set(prescreens)),
                "bind_cycles_after_arrival": sorted(
                    {bound_in[r.arrived.uid] - r.index for r in due
                     if r.arrived.uid in bound_in})}}
