"""The generator ``reclaim_gangs``: a gang arrives in a queue under its
share while a queue over its share holds the whole fleet.

A fixture of ``benchmark/tests``: it proves that the harness takes a
second generator as files.  The fleet is full of the occupying queue's
preemptible jobs (``occupancy`` of the configuration: jobs of ``job_pods``
one-GPU pods with a gang minimum below that, so each has a surplus to shed
first).  A wave is two cycles.  In the first the mix's gang arrives in
another queue: the reclaim action evicts victims and pipelines the gang
onto what they release; the client reads the evictions back, removes the
victims as their kubelets would, and shows the gang pending again, as the
apiserver would.  In the second the allocate action binds the gang.  It
runs ``lifetime_cycles``, completes, and the occupying queue's new jobs
fill what it leaves.  A gang may stay pending ``pending_cycles_max``
cycles.

The comparison holds every cycle of the window to the guarantees the
configuration states, by the client's own ledger and its book of the jobs
it submitted, with the plain reference the configuration names
(``reference/eviction.py``); every limit is 0.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.harness import cluster as gen
from benchmark.harness import loop

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


@dataclass
class Victim:
    """An evicted pod, with what the client's book says of its job."""
    pod: str
    job: str
    queue: str
    preemptible: bool
    min_available: int


@dataclass
class CycleRecord:
    index: int
    pending: gen.Gang | None         # the gang pending in this cycle
    arrived: bool                    # it arrived in this cycle
    used_before: np.ndarray          # [N,3] the ledger before the cycle
    pods_before: np.ndarray          # [N]
    running_before: dict = field(default_factory=dict)   # job -> pods
    evicted: list = field(default_factory=list)   # Victim, read back
    unknown_evictions: int = 0       # evicted pods the client never had
    bound: dict = field(default_factory=dict)     # gang pod -> node index
    foreign_binds: int = 0
    used_after: np.ndarray | None = None
    pods_after: np.ndarray | None = None
    t_sched: float = 0.0
    counters: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    trace_t0: float = 0.0


def _running_pod_group(job: Job, pod_spec: dict):
    """The job's PodGroup with its pods running where the book has them."""
    from kai_scheduler_tpu.api import PodGroupInfo, PodInfo, PodStatus
    from kai_scheduler_tpu.api.resources import ResourceRequirements
    pg = PodGroupInfo(job.uid, job.uid, queue_id=job.queue,
                      min_available=job.min_available,
                      preemptible=job.preemptible)
    rr = ResourceRequirements.from_spec(
        pod_spec.get("cpu"), pod_spec.get("memory"), pod_spec.get("gpu", 0))
    status = PodStatus.RUNNING
    for name, node in job.pods.items():
        pg.add_task(PodInfo(uid=name, name=name, res_req=rr, status=status,
                            node_name=gen.node_name(node)))
    return pg


class Client:
    """Waves of reclaim over one full fleet."""

    def __init__(self, cell, seed: int, counters: tuple = ()):
        from kai_scheduler_tpu.api import ClusterInfo, NodeInfo
        from kai_scheduler_tpu.scheduler import Scheduler
        self.cell = cell
        self.config = config = cell.config
        self.traffic = traffic = cell.traffic
        settings = loop.scheduler_config(config, cell.config_path)
        rng = np.random.default_rng([int(seed), 1])
        self.ledger = ledger = gen.Ledger(config)
        shape = config["nodes"]
        nodes = {gen.node_name(i): NodeInfo(
            gen.node_name(i), gen.res_vec(shape), labels={},
            max_pods=ledger.max_pods) for i in range(ledger.n)}
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
        self.jobs: dict[str, Job] = {}
        self.pod_job: dict[str, str] = {}
        self.next_job = 0
        self.node_order = rng.permutation(ledger.n)
        self._fill()
        self.sched = Scheduler(lambda: self.cluster, settings)
        self.lifetime = int(traffic["lifetime_cycles"])
        self.node_index = {gen.node_name(i): i for i in range(ledger.n)}
        self.gang = None             # (Gang, PodGroupInfo), pending
        self.running = None          # (Gang, PodGroupInfo, cycles run)
        self.gangs: list[gen.Gang] = []
        self.records: list[CycleRecord] = []
        self.counters = tuple(counters)

    # -- the occupying queue ----------------------------------------------
    def _fill(self) -> None:
        """Running jobs of the occupying queue on every node that has a
        whole job's GPUs free, nodes in the seed's order."""
        occ = self.config["occupancy"]
        pods, req = int(occ["job_pods"]), gen.res_vec(occ["pod"])
        ledger = self.ledger
        for node in self.node_order.tolist():
            free = ledger.capacity[node] - ledger.used[node]
            while np.all(free + 1e-9 >= pods * req):
                uid = f"occ-{self.next_job:05d}"
                self.next_job += 1
                job = Job(uid, self.occupier, bool(occ["preemptible"]),
                          int(occ["min_available"]), req,
                          {f"{uid}-{k}": node for k in range(pods)})
                self.jobs[uid] = job
                self.pod_job.update((p, uid) for p in job.pods)
                self._show(job)
                ledger.charge(job.queue, np.full(pods, node),
                              np.tile(req, (pods, 1)))
                free = ledger.capacity[node] - ledger.used[node]
        self.cluster.invalidate_aggregates()

    def _show(self, job: Job) -> None:
        """Put the job into the cluster as the book has it, in place of
        what the cluster had of it."""
        cluster = self.cluster
        old = cluster.podgroups.pop(job.uid, None)
        if old is not None:
            for task in old.pods.values():
                cluster.nodes[task.node_name].remove_task(task)
        if job.pods:
            pg = _running_pod_group(job, self.config["occupancy"]["pod"])
            for task in pg.pods.values():
                cluster.nodes[task.node_name].add_task(task)
            cluster.podgroups[job.uid] = pg

    def _remove(self, victims: list) -> None:
        """The victims' pods are gone: from the book, the ledger and the
        cluster."""
        touched = {}
        for v in victims:
            job = self.jobs[v.job]
            node = job.pods.pop(v.pod)
            del self.pod_job[v.pod]
            self.ledger.charge(job.queue, np.array([node]),
                               job.req[None, :], -1.0)
            touched[job.uid] = job
        for job in touched.values():
            self._show(job)
            if not job.pods:
                del self.jobs[job.uid]

    def _complete(self, gang: gen.Gang, pg) -> None:
        for task in pg.pods.values():
            node = self.cluster.nodes.get(task.node_name)
            if node is not None:
                node.remove_task(task)
        del self.cluster.podgroups[pg.uid]
        names = list(gang.bound)
        self.ledger.charge(gang.queue,
                           np.array([gang.bound[n] for n in names]),
                           gang.req[:len(names)], -1.0)
        self.cluster.invalidate_aggregates()

    # -- one cycle ---------------------------------------------------------
    def _before(self) -> bool:
        """Completions and refills, and a gang's arrival where none is
        pending or running.  True where one arrived."""
        if self.running is not None:
            gang, pg, ran = self.running
            if ran >= self.lifetime:
                self._complete(gang, pg)
                self.running = None
                self._fill()
            else:
                self.running = (gang, pg, ran + 1)
        if self.gang is None and self.running is None:
            pg, gang = gen.make_gang(self.traffic, len(self.gangs),
                                     self.reclaimer)
            self.gangs.append(gang)
            self.cluster.podgroups[pg.uid] = pg
            self.cluster.invalidate_aggregates()
            self.gang = (gang, pg)
            return True
        return False

    def _settle(self, rec: CycleRecord) -> None:
        """Read back what the cycle evicted and bound, as the binder and
        the kubelets would see it."""
        from kai_scheduler_tpu.api import PodStatus
        cache = self.sched.cache
        for pod in cache.evicted:
            job = self.jobs.get(self.pod_job.get(pod))
            if job is None:
                rec.unknown_evictions += 1
                continue
            rec.evicted.append(Victim(pod, job.uid, job.queue,
                                      job.preemptible, job.min_available))
        rec.running_before = {v.job: len(self.jobs[v.job].pods)
                              for v in rec.evicted}
        self._remove(rec.evicted)
        cache.evicted.clear()
        cache.pipelined.clear()
        members = set(rec.pending.names) if rec.pending else set()
        for uid, node in cache.bound:
            if uid in members:
                rec.bound[uid] = self.node_index[node]
            else:
                rec.foreign_binds += 1
        cache.bound.clear()
        self.cluster.bind_requests.clear()
        if self.gang is not None:
            gang, pg = self.gang
            if rec.bound:
                gang.bound.update(rec.bound)
                names = list(rec.bound)
                row = {n: i for i, n in enumerate(gang.names)}
                self.ledger.charge(
                    gang.queue, np.array([rec.bound[n] for n in names]),
                    gang.req[[row[n] for n in names]])
                for task in pg.pods.values():
                    if task.uid in rec.bound:
                        pg.update_task_status(task, PodStatus.RUNNING)
                self.running, self.gang = (gang, pg, 1), None
            else:
                # Pipelined onto what the victims release: the pods are
                # still pending at the apiserver.
                for task in pg.pods.values():
                    if task.status == PodStatus.PIPELINED:
                        self.cluster.nodes[task.node_name].remove_task(task)
                        task.node_name = ""
                        pg.update_task_status(task, PodStatus.PENDING)
        self.cluster.invalidate_aggregates()
        rec.used_after = self.ledger.used.copy()
        rec.pods_after = self.ledger.pods.copy()

    def cycle(self, annotate=None) -> CycleRecord:
        phase = loop.phases(annotate)
        with phase("bench:client_before"):
            arrived = self._before()
        rec = CycleRecord(
            index=len(self.records),
            pending=self.gang[0] if self.gang else None, arrived=arrived,
            used_before=self.ledger.used.copy(),
            pods_before=self.ledger.pods.copy())
        self.cluster.now += 1.0
        loop.run_once(self.sched, rec, self.counters, phase)
        with phase("bench:client_after"):
            self._settle(rec)
        self.records.append(rec)
        return rec

    def close(self) -> None:
        self.sched = None
        self.cluster = None
        self.gang = self.running = None
        gc.collect()


def build(cell, seed: int, counters: tuple = ()) -> Client:
    return Client(cell, seed, counters)


# -- the prescreen kernel ---------------------------------------------------
def file_shape(cell) -> dict:
    """The prescreen's shape as the cell's files give it.  The solver
    considers ``max_victims_considered`` victims, each in two steps (its
    surplus, then its core gang); the first step is simulated and fails,
    and the prescreen scores the next ``scenario_prescreen_max``."""
    occ, settings = cell.config["occupancy"], cell.config["scheduler"]
    n = int(cell.config["nodes"]["count"])
    jobs = n * int(cell.config["nodes"]["gpu"]) // int(occ["job_pods"])
    victims = min(jobs, int(settings["max_victims_considered"]))
    steps = min(2 * victims - int(settings["scenario_prescreen_after"]),
                int(settings["scenario_prescreen_max"]))
    surplus = int(occ["job_pods"]) - int(occ["min_available"])
    # Steps alternate core gang, surplus, core gang, ...
    rows = (steps + 1) // 2 * int(occ["min_available"]) \
        + steps // 2 * surplus
    return {"prefixes": gen.padded(steps), "rows": gen.padded(rows),
            "nodes": n, "resources": 3,
            "t_pad": gen.padded(gen.gang_size(cell.traffic)),
            "label_cols": 1, "taint_cols": 1, "selector_cols": 1,
            "toleration_cols": 1}


def _lower(sds, shape: dict):
    """``batch_prefix_feasibility`` lowered as ``_prefix_prescreen``
    dispatches it."""
    from kai_scheduler_tpu.ops.scenario_batch import \
        batch_prefix_feasibility
    from kai_scheduler_tpu.ops.scoring import BINPACK
    n, r, t, m = (shape["nodes"], shape["resources"], shape["t_pad"],
                  shape["rows"])
    f, i = np.float64, np.int32
    return batch_prefix_feasibility.lower(
        sds((n, r), f), sds((n, r), f), sds((n, r), f),
        sds((n, shape["label_cols"]), i), sds((n, shape["taint_cols"]), i),
        sds((n,), f),
        sds((m,), i), sds((m,), i), sds((m, r), f),
        sds((t, r), f), sds((t,), i), sds((t, shape["selector_cols"]), i),
        sds((t, shape["toleration_cols"]), i),
        num_prefixes=shape["prefixes"], gpu_strategy=BINPACK,
        cpu_strategy=BINPACK)


def prime(client: Client, watch: loop.CompileWatch) -> dict:
    """Compile the prescreen kernel at the shape the reclaim cycle
    dispatches it, before the first guarded dispatch."""
    shape = file_shape(client.cell)
    before = watch.snapshot()
    t0 = time.perf_counter()
    _lower(loop.device_operand, shape).compile()
    client.primed = shape
    return {"seconds": round(time.perf_counter() - t0, 3),
            "kernel": "batch_prefix_feasibility", **shape,
            "cache_misses": watch.since(before)["misses"]}


def prefix_feasibility_bytes(prefixes: int, nodes: int,
                             resources: int = 3) -> float:
    """Bytes one prescreen call must move at the least: it writes and
    reads again one f32 releasing pool ``[K,N,R]``."""
    return 2.0 * prefixes * nodes * resources * 4


def kernel_shapes(client: Client) -> dict:
    shape = client.primed
    return {"prefix_feasibility_bytes": {
        "prefixes": shape["prefixes"], "nodes": shape["nodes"],
        "resources": shape["resources"]}}


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
}


def compare(records, ledger, cell) -> dict:
    """The verdict on the window's ``records``.  A gang is attempted where
    it arrived in the window with ``pending_cycles_max`` cycles left to
    bind in."""
    ref = cell.reference
    pending_max = int(cell.traffic["pending_cycles_max"])
    out = {k: 0 for k in LIMITS}
    evictions = 0
    bound_in = {}                    # gang uid -> index of its bind cycle
    for rec in records:
        queue = rec.pending.queue if rec.pending else None
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
        evictions += len(rec.evicted)
        if rec.pending is not None:
            faults = ref.gang_faults(len(rec.bound), len(rec.pending.names))
            out["gangs_partly_bound"] += faults["gangs_partly_bound"]
            if rec.bound:
                bound_in[rec.pending.uid] = rec.index
    last = records[-1].index
    due = [r for r in records
           if r.arrived and r.index + pending_max - 1 <= last]
    late = [r for r in due
            if bound_in.get(r.pending.uid, last + 1)
            > r.index + pending_max - 1]
    out["gangs_not_bound"] = len(late)
    compared = {k: [out[k], LIMITS[k]] for k in LIMITS}
    return {
        "correct": all(v <= lim for v, lim in compared.values()),
        "compared": compared, "attempted": len(due),
        "bound_pods": sum(len(r.bound) for r in records),
        "failed": len(late),
        "run": {"gangs": len(due), "evictions": evictions,
                "bind_cycles_after_arrival": sorted(
                    {bound_in[r.pending.uid] - r.index for r in due
                     if r.pending.uid in bound_in})}}

