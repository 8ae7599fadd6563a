"""The generator ``preempt_replicas``: a LeaderWorkerSet scales up inside
its team's queue, a step of several replica groups a cycle, and every
replica group takes its nodes from the queue's OWN training jobs.

The fleet is ``reclaim_gangs``' (its client imported from the file beside
this one, nothing of it edited): full, a share of the nodes under one leaf
queue's preemptible jobs, the others under whole-node pods of other
queues.  What in-queue preemption changes belongs here:

- the arrivals come to the queue that HOLDS the preemptible jobs, at a
  higher priority and non-preemptible, so the reclaim action has no
  reclaimer (the queue stands over its fair share) and the preempt action
  has ``replicas_per_cycle`` preemptors a cycle, which it solves one after
  another, each on the statement the one before committed;
- a preemptor is a gang of two pod templates (a leader, its workers), every
  pod a whole node: a replica that can be given three of its four nodes is
  given none;
- every job has a priority and a creation time of its own, so that
  upstream's victim order (lowest priority, then newest) is a total order;
- the client gives the scheduler a cache that keeps the ORDER of what a
  cycle wrote (a commit nominates its preemptor's places and then evicts),
  so that the comparison can hold every commit, and not only the cycle, to
  the reference (``reference/inqueue_eviction.py``);
- ``compare`` holds every cycle of the window to fifteen counts, every
  limit 0 (whole numbers of pods, jobs, nodes and queues);
- ``try_inqueue_preemption``: the deployment on 256 nodes with four
  replicas a cycle, before the run's fleet is built, with two decoys among
  the newest jobs (a job of a sibling queue, a job of the replicas' own
  priority) that a preemptor blind to queues or priorities would take
  first.  A program that breaks a guarantee there stops with status 1.
"""

from __future__ import annotations

import copy
import os
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.harness import cluster as gen
from benchmark.harness import loop, spec
from kai_scheduler_tpu.api import PodGroupInfo, PodInfo, PodStatus
from kai_scheduler_tpu.framework.session import InMemoryCache

base = spec.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "reclaim_gangs.py"), "generator", "reclaim_gangs")

TICK = 1e-3              # one creation time apart, in the cluster's seconds
SPANS_A_SOLVE = 96       # room in the flight recorder for one preemptor
DECOY_JOBS = 2           # jobs a decoy takes over: one node's


class OrderedCache(InMemoryCache):
    """The scheduler's side effects as the apiserver would see them: the
    three lists of ``InMemoryCache``, and ``writes``, the nominations and
    evictions in the order they were written."""

    def __init__(self):
        super().__init__()
        self.writes = []     # ("nominate", pod, node) | ("evict", pod, None)

    def task_pipelined(self, task, node_name, gpu_group="") -> None:
        super().task_pipelined(task, node_name, gpu_group)
        self.writes.append(("nominate", task.uid, node_name))

    def evict(self, task) -> None:
        super().evict(task)
        self.writes.append(("evict", task.uid, None))


@dataclass
class Victim:
    """An evicted pod, with what the client's book says of its job."""
    pod: str
    job: str
    queue: str
    preemptible: bool
    priority: float
    created: float
    min_available: int
    node: int
    req: np.ndarray                  # [3]


@dataclass
class Commit:
    """What one commit wrote: places nominated, then pods evicted."""
    nominated: list = field(default_factory=list)    # (pod, node index)
    evicted: list = field(default_factory=list)      # Victim
    unknown_evictions: int = 0


@dataclass
class CycleRecord:
    index: int
    pending: list                    # replicas pending in this cycle
    arrived: list                    # the replicas that arrived in it
    used_before: np.ndarray          # [N,3] the ledger before the cycle
    pods_before: np.ndarray          # [N]
    refilled: list = field(default_factory=list)   # job uids made before it
    commits: list = field(default_factory=list)    # Commit, in write order
    running_before: dict = field(default_factory=dict)   # job -> pods
    bound: dict = field(default_factory=dict)     # replica uid -> {pod: node}
    foreign_binds: int = 0
    used_after: np.ndarray | None = None
    pods_after: np.ndarray | None = None
    queue_used_after: dict = field(default_factory=dict)     # queue -> [3]
    queue_fixed_after: dict = field(default_factory=dict)    # non-preemptible
    t_sched: float = 0.0
    counters: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    trace_t0: float = 0.0

    @property
    def evicted(self) -> list:
        return [v for c in self.commits for v in c.evicted]


class Client(base.Client):
    """``reclaim_gangs``' fleet with the arrivals in the occupier's own
    queue, ``replicas_per_cycle`` of them a cycle."""

    def __init__(self, cell, seed: int, counters: tuple = ()):
        self.made = 0                # jobs made so far: the creation clock
        super().__init__(cell, seed, counters)
        ledger = self.ledger
        # What the book holds of non-preemptible work, leaf to root.
        ledger.queue_fixed = {q: np.zeros(3) for q in ledger.queue_parent}
        for job in self.jobs.values():
            if not job.preemptible:
                self._fixed(job.queue, job.req * len(job.pods))
        # The comparison reads the book through the ledger: the live one
        # after the window, and every cycle's changes from its record.
        ledger.book = self.jobs
        self.sched.cache = OrderedCache()
        self.per_cycle = int(self.traffic["replicas_per_cycle"])

    def _hold_whole_nodes(self, leaves: list) -> None:
        """One team's queue where ``reclaim_gangs`` has an occupier and a
        reclaimer; its department last, so that the other departments'
        leaves take the whole-node pods, each up to its deserved share."""
        self.team = self.reclaimer = self.occupier
        parent = self.ledger.queue_parent
        super()._hold_whole_nodes(sorted(
            leaves, key=lambda q: parent[q] == parent[self.team]))

    # -- the book ---------------------------------------------------------
    def _fixed(self, queue: str, total: np.ndarray) -> None:
        """Non-preemptible work entered (or with a negative ``total``
        taken out), leaf to root."""
        fixed = self.ledger.queue_fixed
        while queue is not None:
            fixed[queue] = fixed[queue] + total
            queue = self.ledger.queue_parent[queue]

    def _book(self, job) -> None:
        """A new job gets the next creation time and its template's
        priority before the cluster sees it."""
        occ = self.config["occupancy"]
        job.created = self.made * TICK
        self.made += 1
        job.priority = float((occ if job.preemptible
                              else occ["whole_node"])["priority"])
        super()._book(job)

    def _show(self, job) -> None:
        super()._show(job)
        pg = self.cluster.podgroups.get(job.uid)
        if pg is not None:
            pg.priority, pg.creation_ts = int(job.priority), job.created

    def plant_decoys(self, kinds=("queue", "priority")) -> dict:
        """Among the newest jobs of the team's queue, which upstream's
        order takes first, one node's jobs for each kind of decoy:
        ``queue``: handed to another leaf queue (still preemptible and of
        lower priority: a preemptor blind to queues takes them);
        ``priority``: raised to the replicas' own priority (still
        preemptible and of the team's queue: a preemptor that takes equals
        takes them).  Returns kind -> job uids."""
        ledger = self.ledger
        parent = ledger.queue_parent[self.team]
        # A sibling leaf, or where the department has no other, a leaf of
        # another department.
        sibling = min((q for q in gen.leaf_queues(ledger) if q != self.team),
                      key=lambda q: ledger.queue_parent[q] != parent)
        newest = sorted(
            (j for j in self.jobs.values()
             if j.preemptible and j.queue == self.team),
            key=lambda j: -j.created)[:DECOY_JOBS * len(kinds)]
        planted = {}
        for k, kind in enumerate(kinds):
            jobs = newest[k * DECOY_JOBS:(k + 1) * DECOY_JOBS]
            for job in jobs:
                if kind == "queue":
                    nodes = np.array(list(job.pods.values()))
                    reqs = np.tile(job.req, (len(nodes), 1))
                    ledger.charge(job.queue, nodes, reqs, -1.0)
                    job.queue = sibling
                    ledger.charge(job.queue, nodes, reqs)
                else:
                    job.priority = float(self.traffic["gang"]["priority"])
                self._show(job)
            planted[kind] = [job.uid for job in jobs]
        self.cluster.invalidate_aggregates()
        return planted

    # -- one cycle ---------------------------------------------------------
    def _replica(self, step: int, index: int):
        """(PodGroupInfo, Gang) of replica group ``index`` of scale-up
        step ``step``: the pod-grouper's PodGroup for one group of a
        LeaderWorkerSet, ``minMember`` its size."""
        spec_ = self.traffic["gang"]
        uid = f"lws-{step:03d}-{index:02d}"
        pg = PodGroupInfo(uid, uid, queue_id=self.team,
                          priority=int(spec_["priority"]),
                          min_available=gen.gang_size(self.traffic),
                          preemptible=bool(spec_["preemptible"]),
                          creation_ts=self.cluster.now + index * TICK)
        names, reqs = [], []
        for role in spec_["roles"]:
            rr = base._requirements(role)
            for _ in range(int(role["count"])):
                name = f"{uid}-{len(names)}"
                pg.add_task(PodInfo(uid=name, name=name, res_req=rr))
                names.append(name)
                reqs.append(gen.res_vec(role))
        gang = gen.Gang(uid, self.team, names, np.array(reqs), None)
        gang.priority = float(spec_["priority"])
        return pg, gang

    def _before(self) -> tuple:
        """Completions and refills, and the cycle's scale-up step.
        Returns (the replicas that arrived, the job uids the refill
        made)."""
        done = [r for r in self.running if r[2] >= self.lifetime]
        for r in self.running:
            r[2] += 1
        first = self.next_job
        if done:
            self.running = [r for r in self.running if r not in done]
            left = np.zeros_like(self.ledger.used)
            for gang, pg, _ran in done:
                left += self._complete(gang, pg)
                self._fixed(gang.queue, -gang.req.sum(axis=0))
            self._fill(left)
        refilled = [f"occ-{i:06d}" for i in range(first, self.next_job)]
        step = len(self.records)
        arrived = []
        for index in range(self.per_cycle):
            pg, gang = self._replica(step, index)
            self.gangs.append(gang)
            self.cluster.podgroups[pg.uid] = pg
            self.pending.append((gang, pg))
            arrived.append(gang)
        self.cluster.invalidate_aggregates()
        return arrived, refilled

    def _read_writes(self, rec: CycleRecord) -> None:
        """The cycle's nominations and evictions, cut into commits: a
        commit writes its nominations and then its evictions, so a
        nomination after an eviction opens the next one."""
        commit = None
        for kind, pod, node in self.sched.cache.writes:
            if commit is None or (kind == "nominate" and commit.evicted):
                commit = Commit()
                rec.commits.append(commit)
            if kind == "nominate":
                commit.nominated.append((pod, self.node_index[node]))
                continue
            job = self.jobs.get(self.pod_job.get(pod))
            if job is None:
                commit.unknown_evictions += 1
                continue
            commit.evicted.append(Victim(
                pod, job.uid, job.queue, job.preemptible, job.priority,
                job.created, job.min_available, job.pods[pod], job.req))
        self.sched.cache.writes.clear()

    def _settle(self, rec: CycleRecord) -> None:
        """Read back what the cycle wrote, as the binder and the kubelets
        would see it."""
        cache = self.sched.cache
        self._read_writes(rec)
        evicted = rec.evicted
        rec.running_before = {v.job: len(self.jobs[v.job].pods)
                              for v in evicted}
        self._remove(evicted)
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
                reqs = gang.req[[row[n] for n in names]]
                self.ledger.charge(
                    gang.queue, np.array([bound[n] for n in names]), reqs)
                self._fixed(gang.queue, reqs.sum(axis=0))
                for task in pg.pods.values():
                    if task.uid in bound:
                        pg.update_task_status(task, PodStatus.RUNNING)
                self.running.append([gang, pg, 0])
            else:
                # Nominated onto what the victims release: the pods are
                # still pending at the apiserver.
                for task in pg.pods.values():
                    if task.status == PodStatus.PIPELINED:
                        self.cluster.nodes[task.node_name].remove_task(task)
                        task.node_name = ""
                        pg.update_task_status(task, PodStatus.PENDING)
                still.append((gang, pg))
        self.pending = still
        self.cluster.invalidate_aggregates()
        ledger = self.ledger
        rec.used_after = ledger.used.copy()
        rec.pods_after = ledger.pods.copy()
        rec.queue_used_after = {q: v.copy()
                                for q, v in ledger.queue_used.items()}
        rec.queue_fixed_after = {q: v.copy()
                                 for q, v in ledger.queue_fixed.items()}

    def cycle(self, annotate=None) -> CycleRecord:
        phase = loop.phases(annotate)
        with phase("bench:client_before"):
            arrived, refilled = self._before()
        ledger = self.ledger
        rec = CycleRecord(
            index=len(self.records),
            pending=[gang for gang, _pg in self.pending], arrived=arrived,
            used_before=ledger.used.copy(), pods_before=ledger.pods.copy(),
            refilled=refilled)
        self.cluster.now += 1.0
        loop.run_once(self.sched, rec, self.counters, phase)
        with phase("bench:client_after"):
            self._settle(rec)
        self.records.append(rec)
        return rec


# -- the trial before the fleet ----------------------------------------------
# 256 nodes under two departments of two leaf queues, each leaf at its
# deserved share and at its limit (64 nodes): the team's quarter under its
# training jobs (128 jobs), four replicas a cycle (16 nodes, 128
# evictions), so that three steps in flight and the next step's victims
# are the team's 64 nodes, and three steps of non-preemptible replicas
# (384 GPUs) stay inside its deserved share (512).  The solver's caps are
# cut to the team's 128 jobs.
TRIAL = {"nodes": 256, "replicas": 4, "whole": 16, "victims": 128,
         "share": 0.25, "departments": 2, "leaves": 2, "limit_factor": 1.0}
TRIAL_CYCLES = 3


def cut_cell(cell, nodes: int, replicas: int, whole: int, victims: int,
             share: float | None = None, departments: int | None = None,
             leaves: int | None = None, limit_factor: float | None = None,
             warm: int | None = None):
    """A copy of the cell with its fleet, its step and the solver's caps
    cut; the replica keeps its leader, its three workers and their
    requests."""
    cut = copy.copy(cell)
    cut.config = copy.deepcopy(cell.config)
    cut.traffic = copy.deepcopy(cell.traffic)
    cut.config["nodes"]["count"] = nodes
    occ = cut.config["occupancy"]
    occ["whole_node"]["gang_pods"] = whole
    if share is not None:
        occ["preemptible_nodes_share"] = share
    if departments is not None:
        cut.config["queues"].update(departments=departments,
                                    leaves_per_department=leaves)
    if limit_factor is not None:
        cut.config["queues"]["limit_factor"] = limit_factor
    cut.config["scheduler"].update(max_victims_considered=victims,
                                   scenario_prescreen_max=2 * victims)
    cut.traffic["replicas_per_cycle"] = replicas
    if warm is not None:
        # Under the bulk threshold the allocate action binds job by job,
        # a program ``prime`` does not compile: a second warm cycle holds
        # the first bind.
        cut.traffic["warm_cycles"] = warm
    return cut


def try_inqueue_preemption(cell, seed: int) -> dict:
    """Three cycles of the deployment on 256 nodes with four replicas a
    cycle, through the cell's own ``compare``, before the run's fleet is
    built: every replica group that arrives while the fleet is full is
    given four whole nodes of its queue's own training jobs in the cycle it
    arrives in, the newest jobs first, and is bound in the next.  The four
    newest jobs are decoys (``Client.plant_decoys``): a program whose
    preemptor takes a victim of another queue, or of its own priority,
    takes them first and stops here with status 1, soon, on every seed."""
    trial = cut_cell(cell, **TRIAL)
    t0 = time.perf_counter()
    client = Client(trial, seed)
    client.plant_decoys()
    for _ in range(TRIAL_CYCLES):
        client.cycle()
    records, ledger = client.records, client.ledger
    client.close()
    verdict = compare(records, ledger, trial)
    if not verdict["correct"]:
        raise SystemExit(
            f"{cell.name}: this program cannot run the configuration "
            f"{cell.entry['config']}: {TRIAL['replicas']} replica groups of "
            f"a LeaderWorkerSet a cycle, each a gang of four whole-node "
            f"pods, are not each given four nodes of their queue's own "
            f"lower-priority training jobs in their cycle and bound in the "
            f"next; compared (value, limit): "
            f"{ {k: v for k, v in verdict['compared'].items() if v[0]} }")
    return {"seconds": round(time.perf_counter() - t0, 3),
            "nodes": TRIAL["nodes"], "replicas": TRIAL["replicas"],
            "evictions_per_cycle": verdict["run"]["evictions_per_cycle"]}


def build(cell, seed: int, counters: tuple = ()) -> Client:
    from kai_scheduler_tpu.utils.tracing import TRACER
    trial = try_inqueue_preemption(cell, seed)
    client = Client(cell, seed, counters)
    client.trial = trial
    # The flight recorder keeps 512 spans a cycle and counts the rest as
    # dropped; a solve is some 50 (its scenarios, its prescreen, two
    # dispatches with their seam spans, the commit), and the span readers
    # sum what was kept.  Deepen it to hold the whole step.
    TRACER.max_spans_per_trace = max(
        TRACER.max_spans_per_trace,
        SPANS_A_SOLVE * client.per_cycle + 512)
    return client


# -- the kernels of the cycle -------------------------------------------------
def file_shape(cell) -> dict:
    """The shapes of the cycle's programs as the cell's files give them.

    The solver considers ``max_victims_considered`` training jobs for each
    preemptor, each in two steps (its surplus, then its core gang); the
    first step is simulated and fails before any dispatch (the queue
    stands at its limit until a replica's worth has left it), and the
    prescreen scores the next ``scenario_prescreen_max``.  A replica's pod
    needs a whole node, a node holds ``jobs_a_node`` jobs, so the step
    that seats it is ``2 x jobs_a_node x`` its size, and the confirm there
    scans the replica and the core gang of every job that went whole.  A
    cycle later the allocate action's wave holds the step bound and the
    step that has just arrived, a job a replica, two groups each."""
    occ, settings = cell.config["occupancy"], cell.config["scheduler"]
    n = int(cell.config["nodes"]["count"])
    pods = int(occ["job_pods"])
    jobs = int(round(n * float(occ["preemptible_nodes_share"]))) \
        * int(cell.config["nodes"]["gpu"]) // pods
    victims = min(jobs, int(settings["max_victims_considered"]))
    steps = min(2 * victims - int(settings["scenario_prescreen_after"]),
                int(settings["scenario_prescreen_max"]))
    surplus = pods - int(occ["min_available"])
    rows = (steps + 1) // 2 * int(occ["min_available"]) \
        + steps // 2 * surplus
    t = gen.gang_size(cell.traffic)
    roles = len(cell.traffic["gang"]["roles"])
    replicas = int(cell.traffic["replicas_per_cycle"])
    jobs_a_node = int(cell.config["nodes"]["gpu"]) // pods
    whole = t * jobs_a_node
    confirm = (t + whole * int(occ["min_available"]), whole + 1)
    largest = max(int(r["count"]) for r in cell.traffic["gang"]["roles"])
    wave_groups = 2 * replicas * roles
    return {"prefixes": gen.padded(steps), "rows": gen.padded(rows),
            "nodes": n, "resources": 3, "t": t, "t_pad": gen.padded(t),
            "groups": roles, "replicas": replicas,
            "seated_at_step": 2 * whole,
            # Real pods the exact scan steps over in a cycle: a confirm a
            # replica.
            "confirm_steps": replicas * confirm[0],
            # With the task rows' padding job.
            "confirms": [[gen.padded(confirm[0]),
                          gen.padded(confirm[1] + 1)]],
            # [groups, jobs, tasks, largest group] of the allocate wave,
            # padded as ``allocate_grouped`` pads them: every padding
            # group is a job of its own.
            "wave": [gen.padded(wave_groups),
                     gen.padded(2 * replicas + gen.padded(wave_groups)
                                - wave_groups),
                     gen.padded(2 * replicas * t), gen.padded(largest)],
            "label_cols": 1, "taint_cols": 1, "selector_cols": 1,
            "toleration_cols": 1}


def _lower_wave(sds, shape: dict):
    """The grouped fill lowered as the allocate action's bulk wave
    dispatches it (``allocate_grouped`` behind ``propose.place_wave``):
    the step that binds and the step that has just arrived, every job a
    gang of two groups."""
    from kai_scheduler_tpu.ops.allocate_grouped import (
        _allocate_groups_packed, _resolve_fused_mode)
    from kai_scheduler_tpu.ops.scoring import BINPACK
    groups, jobs, t_pad, largest = shape["wave"]
    r = shape["resources"]
    f, i = np.float64, np.int32
    return _allocate_groups_packed.lower(
        *base._node_tables(sds, shape),
        sds((groups, r), f), sds((groups, shape["selector_cols"]), i),
        sds((groups, shape["toleration_cols"]), i), sds((groups,), f),
        sds((groups,), i), sds((jobs,), bool), max_group=largest,
        t_pad=t_pad, group_indep=sds((groups,), bool), gpu_strategy=BINPACK,
        cpu_strategy=BINPACK, allow_pipeline=True, pipeline_only=False,
        single_group_jobs=False,
        fused_mode=_resolve_fused_mode(None, shape["nodes"]),
        releasing_empty=True, f32_keys=False)


def prime(client: Client, watch: loop.CompileWatch) -> dict:
    """Compile the programs of the cycle, each at the shape the cycle
    dispatches it, before the first guarded dispatch (the device guard
    gives a dispatch 30 s, compile included): the prescreen kernel of the
    preempt action's solver, the exact scan of its confirm, and the
    grouped fill of the allocate action's wave."""
    shape = file_shape(client.cell)
    sds = loop.device_operand
    lowerings = {"batch_prefix_feasibility":
                 lambda: base._lower(sds, shape)}
    for t_pad, j_pad in shape["confirms"]:
        lowerings[f"allocate_jobs_kernel[{t_pad},{j_pad}]"] = \
            lambda t=t_pad, j=j_pad: base._lower_confirm(sds, shape, t, j)
    lowerings[f"_allocate_groups_packed{shape['wave']}"] = \
        lambda: _lower_wave(sds, shape)
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
            **shape, "trial": getattr(client, "trial", None),
            "cache_misses": watch.since(before)["misses"]}


def prefix_feasibility_bytes(prefixes: int, nodes: int, groups: int,
                             calls: int, resources: int = 3) -> float:
    """Bytes a cycle's prescreens must move at the least: each of the
    ``calls`` writes one f32 releasing pool ``[K,N,R]``, the K states of
    the fleet it scores, and reads it again once for each of the replica's
    ``groups`` of identical pods (``consolidation_gangs``' rule for a gang
    of two runs).  By the WORK and not by the form that answers it: a call
    that scanned the replica's four pods one by one is read on the same
    yardstick."""
    return float(calls) * (1.0 + groups) * prefixes * nodes * resources * 4


# A step of a confirm reads allocatable, idle and releasing [N,R] f32, pod
# room [N] and the label and taint tables; no score row, no mask row:
# ``reclaim_gangs``' count, found here by the roofline reader.
exact_scan_bytes = base.exact_scan_bytes


def kernel_shapes(client: Client) -> dict:
    shape = client.primed
    return {
        "prefix_feasibility_bytes": {
            "prefixes": shape["prefixes"], "nodes": shape["nodes"],
            "groups": shape["groups"], "calls": shape["replicas"],
            "resources": shape["resources"]},
        "exact_scan_bytes": {
            "steps": shape["confirm_steps"],
            "nodes": shape["nodes"], "resources": shape["resources"],
            "label_cols": shape["label_cols"],
            "taint_cols": shape["taint_cols"]}}


def reckon(cell) -> dict:
    """What the cycle holds on the device, from the files: ``reclaim
    _gangs``' reckoning (the same kernel, operands and arrays) at this
    cell's ``[K,N,R]``, each prefix another state of the fleet, each read
    for the verdict."""
    out = base.reckon(cell)
    out["what"] += f", {file_shape(cell)['replicas']} calls a cycle"
    return out


def compile_for(cell, sds):
    return base._lower(sds, file_shape(cell)).compile()


# -- the comparison ---------------------------------------------------------
LIMITS = {
    "replicas_not_bound": 0, "gangs_partly_bound": 0, "foreign_binds": 0,
    "nodes_over_capacity": 0, "victims_not_preemptible": 0,
    "victims_from_other_queue": 0, "victims_not_lower_priority": 0,
    "evictions_without_preemptor": 0, "unknown_evictions": 0,
    "victim_gangs_below_minimum": 0, "evictions_beyond_need": 0,
    "evictions_not_reference": 0, "preemptors_not_seated": 0,
    "queues_non_preemptible_over_deserved": 0, "queues_over_limit": 0,
}


def books_of(records, ledger) -> list:
    """For every cycle of ``records`` the book as the cycle met it: job
    uid -> (queue, preemptible, priority, created, minimum, {pod: node},
    req [3]) in plain values, rebuilt backwards from the live book
    (``ledger.book``) through every later cycle's evictions and refills."""
    now = {uid: (j.queue, j.preemptible, j.priority, j.created,
                 j.min_available, dict(j.pods), np.asarray(j.req, float))
           for uid, j in ledger.book.items()}
    books = []
    for rec in reversed(records):
        for v in rec.evicted:
            if v.job not in now:
                now[v.job] = (v.queue, v.preemptible, v.priority,
                              v.created, v.min_available, {}, v.req)
            now[v.job][5][v.pod] = v.node
        books.append({uid: (*j[:5], dict(j[5]), j[6])
                      for uid, j in now.items()})
        for uid in rec.refilled:
            now.pop(uid, None)
    return books[::-1]


def _candidates(ref, book: dict, nodes: int, queue: str,
                priority: float) -> tuple:
    """What a preemptor of ``queue`` and ``priority`` may take of the
    ``book``: (the jobs, their uids in upstream's order, and the
    reference's per-node summary of their pods)."""
    legal = {uid: j for uid, j in book.items()
             if j[5] and ref.may_be_taken(j[0], j[1], j[2], queue, priority)}
    ordered = ref.victim_order([(uid, j[2], j[3])
                                for uid, j in legal.items()])
    flat = [(node, j[6]) for j in legal.values() for node in j[5].values()]
    count, most = ref.victims_by_node(
        nodes, [node for node, _r in flat], [req for _n, req in flat])
    return legal, ordered, count, most


def compare(records, ledger, cell) -> dict:
    """The verdict on the window's ``records``.  A replica is attempted
    where it arrived in the window with ``pending_cycles_max`` cycles left
    to bind in.  A cycle's commits are read one after another, each on the
    ledger and the book the commits before it left."""
    ref = cell.reference
    pending_max = int(cell.traffic["pending_cycles_max"])
    tree = cell.config["queues"]
    total = ledger.capacity.sum(axis=0)
    deserved = {q: ref.deserved_share(
        total, int(tree["departments"]), int(tree["leaves_per_department"]),
        leaf=parent is not None)
        for q, parent in ledger.queue_parent.items()}
    out = {k: 0 for k in LIMITS}
    evictions, binds, commits_n, prescreens, solves = [], [], [], [], []
    bound_in = {}                    # replica uid -> index of its bind cycle
    for rec, book in zip(records, books_of(records, ledger)):
        replica_of = {name: gang for gang in rec.pending
                      for name in gang.names}
        out["foreign_binds"] += rec.foreign_binds
        # What the cycle bound, entered in the ledger it started from: the
        # allocate action runs first, so it is the state the preemptors
        # met.
        used, pods = rec.used_before.copy(), rec.pods_before.copy()
        for gang in rec.pending:
            bound = rec.bound.get(gang.uid, {})
            out["gangs_partly_bound"] += ref.gang_faults(
                len(bound), len(gang.names))
            if bound:
                bound_in[gang.uid] = rec.index
                row = {n: i for i, n in enumerate(gang.names)}
                names = list(bound)
                nodes = np.array([bound[n] for n in names])
                np.add.at(used, nodes, gang.req[[row[n] for n in names]])
                np.add.at(pods, nodes, 1)
        waiting = [g for g in rec.pending if g.uid not in rec.bound]
        promised = np.zeros_like(used)       # nominated and not yet bound
        promised_pods = np.zeros_like(pods)
        asked = {}                           # queue -> [3] nominated
        gone = {}
        candidates = {}      # (queue, priority) -> what such a job may take
        for commit in rec.commits:
            out["unknown_evictions"] += commit.unknown_evictions
            seated = {}
            for pod, node in commit.nominated:
                gang = replica_of.get(pod)
                if gang is not None:
                    seated.setdefault(gang.uid, (gang, {}))[1][pod] = node
            whole = [g for g, at in seated.values()
                     if len(at) == len(g.names)]
            out["gangs_partly_bound"] += sum(
                ref.gang_faults(len(at), len(g.names))
                for g, at in seated.values())
            preemptor = whole[0] if whole else None
            faults = ref.victim_faults(
                [(v.queue, v.preemptible, v.priority)
                 for v in commit.evicted],
                (preemptor.queue, preemptor.priority) if preemptor else None)
            for name, value in faults.items():
                out[name] += value
            if commit.evicted and preemptor is None:
                if waiting:
                    out["preemptors_not_seated"] += 1
                else:
                    out["evictions_without_preemptor"] += \
                        len(commit.evicted)
            if preemptor is not None and commit.evicted:
                # The candidates the preemptor met: what may be taken, in
                # upstream's order, less what earlier commits took.
                key = (preemptor.queue, preemptor.priority)
                if key not in candidates:
                    candidates[key] = _candidates(ref, book, ledger.n, *key)
                legal, ordered, count, most = candidates[key]
                state = (ledger.capacity, used + promised,
                         pods + promised_pods, ledger.max_pods,
                         preemptor.req)
                wanted = ref.reference_victims(
                    *state, ((uid, legal[uid][4], legal[uid][5],
                              legal[uid][6]) for uid in ordered
                             if legal[uid][5]))
                mine = {v.pod for v in commit.evicted if v.job in legal}
                out["evictions_not_reference"] += len(
                    mine - (wanted or set()))
                fewest = ref.fewest_evictions(*state, count, most)
                out["evictions_beyond_need"] += max(
                    0, len(commit.evicted)
                    - (int(fewest) if np.isfinite(fewest) else 0))
            # The commit's effects, for the commits after it.
            for v in commit.evicted:
                used[v.node] -= v.req
                pods[v.node] -= 1
                gone[v.job] = gone.get(v.job, 0) + 1
                if v.job in book:
                    book[v.job][5].pop(v.pod, None)
                for legal, _o, count, _m in candidates.values():
                    count[v.node] -= v.job in legal
            for g, at in seated.values():
                row = {n: i for i, n in enumerate(g.names)}
                for pod, node in at.items():
                    promised[node] += g.req[row[pod]]
                    promised_pods[node] += 1
                    asked[g.queue] = asked.get(g.queue, 0.0) \
                        + g.req[row[pod]]
                if len(at) == len(g.names):
                    waiting = [w for w in waiting if w.uid != g.uid]
        out["victim_gangs_below_minimum"] += ref.gangs_left_below_minimum(
            rec.running_before, gone,
            {v.job: v.min_available for v in rec.evicted})
        # After the cycle, with what it promised counted where it is
        # promised: no node and no queue past its bound.
        out["nodes_over_capacity"] += ref.nodes_over_capacity(
            ledger.capacity, rec.used_after + promised,
            rec.pods_after + promised_pods, ledger.max_pods)
        # The queues the cycle gave something to (a bind, a nomination),
        # leaf to root: none past its limit, none with non-preemptible
        # work past its deserved share.
        for gang in rec.pending:
            if gang.uid in rec.bound:
                asked.setdefault(gang.queue, np.zeros(3))
        held, fixed = {}, {}
        for queue, total_asked in asked.items():
            while queue is not None:
                held[queue] = held.get(
                    queue, rec.queue_used_after[queue]) + total_asked
                fixed[queue] = fixed.get(
                    queue, rec.queue_fixed_after[queue]) + total_asked
                queue = ledger.queue_parent[queue]
        out["queues_over_limit"] += ref.queues_over(held, ledger.queue_limit)
        out["queues_non_preemptible_over_deserved"] += ref.queues_over(
            fixed, deserved)
        evictions.append(len(rec.evicted))
        binds.append(sum(len(b) for b in rec.bound.values()))
        commits_n.append(sum(1 for c in rec.commits if c.evicted))
        prescreens.append(sum(1 for span in rec.spans
                              if span[0] == "dispatch:scenario_prescreen"))
        solves.append(sum(1 for span in rec.spans
                          if span[0] == "solve:job"))
    last = records[-1].index
    due = [(r, g) for r in records for g in r.arrived
           if r.index + pending_max - 1 <= last]
    late = [g for r, g in due
            if bound_in.get(g.uid, last + 1) > r.index + pending_max - 1]
    out["replicas_not_bound"] = len(late)
    compared = {k: [out[k], LIMITS[k]] for k in LIMITS}
    return {
        "correct": all(v <= lim for v, lim in compared.values()),
        "compared": compared, "attempted": len(due),
        "bound_pods": sum(binds), "failed": len(late),
        "run": {"replicas": len(due), "evictions": sum(evictions),
                "evictions_per_cycle": sorted(set(evictions)),
                "binds_per_cycle": sorted(set(binds)),
                "commits_per_cycle": sorted(set(commits_n)),
                "solves_per_cycle": sorted(set(solves)),
                "prescreens_per_cycle": sorted(set(prescreens)),
                "bind_cycles_after_arrival": sorted(
                    {bound_in[g.uid] - r.index for r, g in due
                     if g.uid in bound_in})}}
