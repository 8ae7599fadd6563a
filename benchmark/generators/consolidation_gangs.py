"""The generator ``consolidation_gangs``: a gang of whole-node pods arrives
every cycle while every node of the fleet holds something and the idle
GPUs, a few on each node, would hold the gang many times over.

The fleet (``occupancy`` of the configuration): a share of the nodes
fragmented, each under one preemptible job of ``job_pods`` small pods, the
jobs spread over ``queues`` leaf queues that stay within their deserved
share; every other node under one non-preemptible whole-node pod.  Every
cycle the mix's gang arrives in a leaf queue of its own, inside its quota.
No node has a whole node's room, so the allocate action fails it; the
consolidation action evicts the jobs of as many nodes as the gang lacks
and, in the same commit, pipelines the gang onto what they release and
every evicted pod onto idle GPUs elsewhere.  The client reads the
evictions and the pipelined places back, removes the evicted pods as their
kubelets would, creates their replacements pending in the same PodGroups as
their controllers would, and shows the gang pending again, as the apiserver
would.  The next cycle's allocate action binds the gang and the
replacements, while the same cycle consolidates for the gang that arrives
then: once warm, every cycle holds one consolidation, the gang's binds and
the moved pods' binds.

A bound gang runs ``lifetime_cycles`` cycles and completes; new fragment
jobs fill the nodes it leaves (no node stays empty), and as many older
fragment jobs complete on the fullest nodes, those where moved jobs landed
on top of another, so that the fleet's pods and its idle GPUs stand still
once the first gang has completed (``warm_cycles`` covers the cycles
before that).  Moved jobs bin-pack onto nodes that other fragment jobs
hold, so some of the fleet's newest jobs share their nodes: which jobs a
cycle moves is the program's to choose, and the comparison counts every
pod it moves beyond the fewest.

Before the fleet is built the generator tries that last guarantee in one
cycle on 64 nodes (``try_fewest_moves``) and stops the run on a program
that cannot hold it: such a program cannot run this configuration.

The comparison holds every cycle of the window to the guarantees the
configuration states, by the client's own ledger and its book of the jobs
it submitted, with the plain reference the configuration names
(``reference/relocation.py``); every limit is 0.
"""

from __future__ import annotations

import copy
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


def _requirements(pod: dict) -> ResourceRequirements:
    return ResourceRequirements.from_spec(pod.get("cpu"), pod.get("memory"),
                                          pod.get("gpu", 0))


@dataclass
class Job:
    """What the client knows of a job it submitted."""
    uid: str
    queue: str
    preemptible: bool
    min_available: int
    req: np.ndarray                  # [3] of every pod
    rr: object                       # the pods' ResourceRequirements
    created: float
    pods: dict                       # pod name -> node index, running
    waiting: list = field(default_factory=list)   # pod names, pending
    made: int = 0                    # pods made so far, for their names


@dataclass
class Moved:
    """An evicted pod, with what the client's book says of its job."""
    pod: str
    job: str
    preemptible: bool
    node: int
    req: np.ndarray                  # [3]
    replaced_by: str = ""            # the pod its controller made


@dataclass
class CycleRecord:
    index: int
    pending: list                    # gangs pending, oldest first
    arrived: gen.Gang | None         # the gang that arrived in this cycle
    moved: list = field(default_factory=list)     # Moved, read back
    placed: set = field(default_factory=set)      # pods pipelined a place
    running_before: dict = field(default_factory=dict)   # job -> pods
    unknown_evictions: int = 0       # evicted pods the client never had
    bound: dict = field(default_factory=dict)     # gang uid -> {pod: node}
    rebound: set = field(default_factory=set)     # replacements bound
    foreign_binds: int = 0
    # The ledger the waiting gang met: the cycle's binds entered, its
    # evictions not; and what could be moved off each node then.
    used_met: np.ndarray | None = None            # [N,3]
    pods_met: np.ndarray | None = None            # [N]
    movable_sum: np.ndarray | None = None         # [N,3]
    movable_largest: np.ndarray | None = None     # [N,3]
    used_after: np.ndarray | None = None
    pods_after: np.ndarray | None = None
    t_sched: float = 0.0
    counters: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    trace_t0: float = 0.0

    @property
    def consolidator(self):
        """The gang the cycle's moves are for: the youngest pending one
        that the cycle did not bind."""
        waiting = [g for g in self.pending if g.uid not in self.bound]
        return waiting[-1] if waiting else None


class Client:
    """One consolidation, one gang bound and its moved pods bound again,
    every cycle, over a fleet with no empty node."""

    def __init__(self, cell, seed: int, counters: tuple = ()):
        from kai_scheduler_tpu.scheduler import Scheduler
        self.cell = cell
        self.config = config = cell.config
        self.traffic = traffic = cell.traffic
        settings = loop.scheduler_config(config, cell.config_path)
        rng = np.random.default_rng([int(seed), 1])
        self.ledger = ledger = gen.Ledger(config)
        alloc = gen.res_vec(config["nodes"])
        self.node_names = [gen.node_name(i) for i in range(ledger.n)]
        self.node_index = {name: i for i, name in enumerate(self.node_names)}
        nodes = {name: NodeInfo(name, alloc, labels={},
                                max_pods=ledger.max_pods)
                 for name in self.node_names}
        queues = gen.build_queues(config, ledger)
        self.cluster = ClusterInfo(nodes, {}, queues, topologies={},
                                   now=1000.0)
        occ = config["occupancy"]
        frag = occ["fragment"]
        # The gang's queue, the fragments' queues and the whole-node pods'
        # queues: from the seed.
        leaves = gen.leaf_queues(ledger)
        order = [leaves[int(i)] for i in rng.permutation(len(leaves))]
        self.gang_queue = order[0]
        self.fragment_queues = order[1:1 + int(frag["queues"])]
        self.frag_req = gen.res_vec(frag["pod"])
        # One requirements object for every pod of a template, as pods of
        # one template have: the queue roll-up counts per object.
        self.frag_rr = _requirements(frag["pod"])
        self.jobs: dict[str, Job] = {}
        self.pod_job: dict[str, str] = {}
        self.node_jobs: dict[int, list] = {}   # node -> its fragment jobs
        self.next_job = 0
        self.lifetime = int(traffic["lifetime_cycles"])
        self.pending: list = []      # [(Gang, PodGroupInfo)], oldest first
        self.running: list = []      # [[Gang, PodGroupInfo, cycles run]]
        self.gangs: list[gen.Gang] = []
        node_order = rng.permutation(ledger.n)
        n_frag = int(round(ledger.n * float(occ["fragmented_nodes_share"])))
        # The fleet's jobs are made with the cyclic collector off: some
        # hundred thousand objects that all stay alive, which it would
        # walk again and again while they are made.
        gc.disable()
        try:
            self._fragment(node_order[:n_frag])
            self._hold_whole_nodes(node_order[n_frag:],
                                   order[1 + int(frag["queues"]):])
        finally:
            gc.enable()
        self.sched = Scheduler(lambda: self.cluster, settings)
        self.records: list[CycleRecord] = []
        self.counters = tuple(counters)

    # -- the fleet ----------------------------------------------------------
    def _hold_whole_nodes(self, held, queues: list) -> None:
        """One running whole-node pod of a gang of ``gang_pods`` on every
        node of ``held``, the gangs in ``queues`` in order, each queue up
        to its deserved share of the fleet's nodes."""
        whole = self.config["occupancy"]["whole_node"]
        ledger = self.ledger
        req, rr = gen.res_vec(whole["pod"]), _requirements(whole["pod"])
        size = int(whole["gang_pods"])
        start = 0
        for queue in queues:
            room = ledger.n // len(gen.leaf_queues(ledger))
            while start < len(held) and room > 0:
                nodes = held[start:start + min(size, room)]
                start += len(nodes)
                room -= len(nodes)
                uid = f"whole-{self.next_job:06d}"
                self.next_job += 1
                job = Job(uid, queue, bool(whole["preemptible"]),
                          len(nodes), req, rr, self.cluster.now,
                          {f"{uid}-{k}": int(node)
                           for k, node in enumerate(nodes)})
                self._book(job)
                ledger.charge(queue, nodes, np.tile(req, (len(nodes), 1)))
        if start < len(held):
            raise SystemExit(
                f"{self.cell.config_path}: the deserved shares of "
                f"{len(queues)} leaf queues hold {start} of the "
                f"{len(held)} nodes that \"occupancy\" leaves to "
                f"whole-node pods")

    def _fragment(self, nodes) -> None:
        """One new running fragment job on every node of ``nodes``, all
        its pods there, the jobs dealt round to the fragments' queues."""
        created = self.cluster.now
        frag = self.config["occupancy"]["fragment"]
        pods, req = int(frag["job_pods"]), self.frag_req
        by_queue: dict[str, list] = {}
        for node in np.asarray(nodes).tolist():
            queue = self.fragment_queues[
                self.next_job % len(self.fragment_queues)]
            uid = f"frag-{self.next_job:06d}"
            self.next_job += 1
            job = Job(uid, queue, bool(frag["preemptible"]),
                      int(frag["min_available"]), req, self.frag_rr, created,
                      {f"{uid}-{k}": node for k in range(pods)}, made=pods)
            self._book(job)
            self.node_jobs.setdefault(node, []).append(uid)
            by_queue.setdefault(queue, []).append(node)
        for queue, at in by_queue.items():
            idx = np.repeat(np.array(at), pods)
            self.ledger.charge(queue, idx, np.tile(req, (len(idx), 1)))
        self.cluster.invalidate_aggregates()

    def _make_gang(self):
        pg, gang = gen.make_gang(self.traffic, len(self.gangs),
                                 self.gang_queue)
        pg.preemptible = bool(self.traffic["gang"]["preemptible"])
        pg.creation_ts = self.cluster.now
        self.gangs.append(gang)
        return pg, gang

    def _book(self, job: Job) -> None:
        """Enter a new job in the book and show it to the cluster."""
        self.jobs[job.uid] = job
        for name in job.pods:
            self.pod_job[name] = job.uid
        self._show(job)

    def _show(self, job: Job) -> None:
        """Put the job into the cluster as the book has it: its running
        pods on their nodes, its waiting pods pending."""
        pg = PodGroupInfo(job.uid, job.uid, queue_id=job.queue,
                          min_available=job.min_available,
                          preemptible=job.preemptible,
                          creation_ts=job.created)
        for name, node in job.pods.items():
            task = PodInfo(uid=name, name=name, res_req=job.rr,
                           status=PodStatus.RUNNING,
                           node_name=self.node_names[node])
            pg.add_task(task)
            self.cluster.nodes[task.node_name].add_task(task)
        for name in job.waiting:
            pg.add_task(PodInfo(uid=name, name=name, res_req=job.rr))
        self.cluster.podgroups[job.uid] = pg

    def _unshow(self, job: Job, homes: dict) -> None:
        """Take what the cluster has of the job off its nodes.  An evicted
        pod that the same commit pipelined elsewhere is on two nodes:
        pipelined where it would land, and releasing at home
        (``homes``: pod -> the node it was evicted from)."""
        nodes = self.cluster.nodes
        for task in self.cluster.podgroups.pop(job.uid).pods.values():
            if task.status == PodStatus.PENDING:
                continue
            nodes[task.node_name].remove_task(task)
            if task.status == PodStatus.PIPELINED and task.uid in homes:
                task.status = PodStatus.RELEASING
                task.node_name = self.node_names[homes[task.uid]]
                nodes[task.node_name].remove_task(task)

    def _retire(self, job: Job) -> None:
        """The job is done: off the cluster, the ledger and the book."""
        self._unshow(job, {})
        at = np.array(list(job.pods.values()))
        self.ledger.charge(job.queue, at, np.tile(job.req, (len(at), 1)),
                           -1.0)
        for node in set(job.pods.values()):
            self.node_jobs[node].remove(job.uid)
        for name in job.pods:
            del self.pod_job[name]
        del self.jobs[job.uid]

    def _complete(self, gang: gen.Gang, pg) -> list:
        """The gang is done: its pods leave the cluster and the ledger.
        Returns the nodes it leaves."""
        for task in pg.pods.values():
            node = self.cluster.nodes.get(task.node_name)
            if node is not None:
                node.remove_task(task)
        del self.cluster.podgroups[pg.uid]
        row = {n: i for i, n in enumerate(gang.names)}
        names = list(gang.bound)
        nodes = [gang.bound[n] for n in names]
        self.ledger.charge(gang.queue, np.array(nodes),
                           gang.req[[row[n] for n in names]], -1.0)
        return nodes

    def _thin(self, count: int) -> None:
        """Up to ``count`` fragment jobs complete, on the fullest nodes
        first and the oldest job of a node first, down to the newest of
        a node."""
        crowded = [n for n, jobs in self.node_jobs.items() if len(jobs) > 1]
        crowded.sort(key=lambda n: (-self.ledger.used[n, 2], n))
        for node in crowded:
            for uid in sorted(self.node_jobs[node],
                              key=lambda u: (self.jobs[u].created, u)):
                if count <= 0:
                    return
                job = self.jobs[uid]
                if job.waiting or any(len(self.node_jobs[n]) <= 1
                                      for n in set(job.pods.values())):
                    continue
                self._retire(job)
                count -= 1

    # -- one cycle ---------------------------------------------------------
    def _before(self) -> gen.Gang:
        """Completions and refills, and the cycle's arrival."""
        done = [r for r in self.running if r[2] >= self.lifetime]
        for r in self.running:
            r[2] += 1
        if done:
            self.running = [r for r in self.running if r not in done]
            left = [n for gang, pg, _ran in done
                    for n in self._complete(gang, pg)]
            self._fragment(left)
            self._thin(len(left))
        pg, gang = self._make_gang()
        self.cluster.podgroups[pg.uid] = pg
        self.cluster.invalidate_aggregates()
        self.pending.append((gang, pg))
        return gang

    def _movable(self, rec: CycleRecord) -> None:
        """What could be moved off each node, by the book: the running
        pods of preemptible jobs."""
        at, reqs = [], []
        for job in self.jobs.values():
            if job.preemptible and job.pods:
                at.extend(job.pods.values())
                reqs.extend([job.req] * len(job.pods))
        rec.movable_sum = np.zeros_like(self.ledger.used)
        rec.movable_largest = np.zeros_like(self.ledger.used)
        if at:
            np.add.at(rec.movable_sum, at, reqs)
            np.maximum.at(rec.movable_largest, at, reqs)

    def _settle(self, rec: CycleRecord) -> None:
        """Read back what the cycle bound, evicted and pipelined, as the
        binder, the kubelets and the controllers would see it."""
        cache, ledger = self.sched.cache, self.ledger
        member = {name: gang.uid for gang, _pg in self.pending
                  for name in gang.names}
        touched: dict[str, Job] = {}
        # Binds first: a pod may be bound and moved in one cycle.
        for uid, node_name in cache.bound:
            node = self.node_index[node_name]
            job = self.jobs.get(self.pod_job.get(uid))
            if uid in member:
                rec.bound.setdefault(member[uid], {})[uid] = node
            elif job is not None and uid in job.waiting:
                job.waiting.remove(uid)
                job.pods[uid] = node
                self.node_jobs.setdefault(node, [])
                if job.uid not in self.node_jobs[node]:
                    self.node_jobs[node].append(job.uid)
                ledger.charge(job.queue, np.array([node]), job.req[None, :])
                rec.rebound.add(uid)
                touched[job.uid] = job
            else:
                rec.foreign_binds += 1
        for gang, _pg in self.pending:
            bound = rec.bound.get(gang.uid)
            if bound:
                gang.bound.update(bound)
                names = list(bound)
                row = {n: i for i, n in enumerate(gang.names)}
                ledger.charge(gang.queue,
                              np.array([bound[n] for n in names]),
                              gang.req[[row[n] for n in names]])
        rec.used_met, rec.pods_met = ledger.used.copy(), ledger.pods.copy()
        self._movable(rec)
        # Then the moves: the evicted pods and the places pipelined.
        rec.placed = {uid for uid, _node in cache.pipelined}
        homes = {}
        for pod in cache.evicted:
            job = self.jobs.get(self.pod_job.get(pod))
            if job is None or pod not in job.pods:
                rec.unknown_evictions += 1
                continue
            rec.moved.append(Moved(pod, job.uid, job.preemptible,
                                   job.pods[pod], job.req))
            homes[pod] = job.pods[pod]
            touched[job.uid] = job
        rec.running_before = {m.job: len(self.jobs[m.job].pods)
                              for m in rec.moved}
        for job in touched.values():
            self._unshow(job, homes)
        for m in rec.moved:
            job = self.jobs[m.job]
            del job.pods[m.pod]
            del self.pod_job[m.pod]
            if m.node not in job.pods.values():
                self.node_jobs[m.node].remove(job.uid)
            ledger.charge(job.queue, np.array([m.node]), m.req[None, :],
                          -1.0)
            # The job's controller makes a pod in its place.
            m.replaced_by = f"{job.uid}-{job.made}"
            job.made += 1
            job.waiting.append(m.replaced_by)
            self.pod_job[m.replaced_by] = job.uid
        for job in touched.values():
            self._show(job)
        cache.evicted.clear()
        cache.pipelined.clear()
        cache.bound.clear()
        self.cluster.bind_requests.clear()
        still = []
        for gang, pg in self.pending:
            if gang.uid in rec.bound:
                for task in pg.pods.values():
                    if task.uid in rec.bound[gang.uid]:
                        pg.update_task_status(task, PodStatus.RUNNING)
                self.running.append([gang, pg, 0])
            else:
                # Pipelined onto what the moved pods release: the pods
                # are still pending at the apiserver.
                for task in pg.pods.values():
                    if task.status == PodStatus.PIPELINED:
                        self.cluster.nodes[task.node_name].remove_task(task)
                        task.node_name = ""
                        pg.update_task_status(task, PodStatus.PENDING)
                still.append((gang, pg))
        self.pending = still
        self.cluster.invalidate_aggregates()
        rec.used_after = ledger.used.copy()
        rec.pods_after = ledger.pods.copy()

    def cycle(self, annotate=None) -> CycleRecord:
        phase = loop.phases(annotate)
        with phase("bench:client_before"):
            arrived = self._before()
        rec = CycleRecord(
            index=len(self.records),
            pending=[gang for gang, _pg in self.pending], arrived=arrived)
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


# The fleet of ``try_fewest_moves``: wide enough for every leaf queue to
# hold its share of whole nodes, narrow enough to stand in a second.
TRIAL_NODES = 64


def try_fewest_moves(cell, seed: int) -> None:
    """Stop the run, before the fleet is built, on a program that cannot
    hold what this deployment adds to upstream's guarantees: a cycle moves
    no more pods than the fewest that seat the waiting job.

    One cycle of the cell's own loop over ``TRIAL_NODES`` nodes, the mix's
    gang cut to its master and one worker, judged by the cell's own
    ``compare``.  One node stands under two jobs, the second the newest of
    the fleet: the state the loop reaches wherever a moved job has landed
    on top of another, here from the first cycle on.  The gang lacks two
    whole nodes and two jobs that stand alone give them up, four pods; a
    program that takes its victims newest first, blind to what a job's
    leaving empties (this repo's before PR 37), moves the newest job too,
    six pods.  At the cell's width that is a cycle now and then, by the
    seed, with 258 pods moved and a wave of 131 jobs that compiles inside
    the window; such a program's runs would be incorrect on some seeds and
    not on others, so it is told at once, on every seed, that it cannot
    run this configuration."""
    frag = cell.config["occupancy"]["fragment"]
    if np.any(2 * int(frag["job_pods"]) * gen.res_vec(frag["pod"])
              > gen.res_vec(cell.config["nodes"])):
        return       # no job lands on top of another: nothing to try
    small = copy.copy(cell)
    small.config = copy.deepcopy(cell.config)
    small.traffic = copy.deepcopy(cell.traffic)
    small.config["nodes"]["count"] = TRIAL_NODES
    for role in small.traffic["gang"]["roles"]:
        role["count"] = 1
    client = Client(small, seed)
    shared = next(reversed(client.node_jobs))
    client.cluster.now += 1.0
    client._fragment([shared])
    rec = client.cycle()
    moved = len(rec.moved)
    beyond = compare([rec], client.ledger, small)["compared"][
        "moves_beyond_need"][0]
    client.close()
    if beyond:
        raise SystemExit(
            f"{cell.config_path}: this program cannot run the "
            f"configuration. On {TRIAL_NODES} nodes, one of them under two "
            f"fragment jobs, its consolidation action moved {moved} pods "
            f"to seat a job that lacked two whole nodes, {beyond} beyond "
            f"the fewest (\"guarantees\": a cycle moves no more pods "
            f"than the fewest whose leaving empties the whole nodes the "
            f"waiting job lacked)")


def build(cell, seed: int, counters: tuple = ()) -> Client:
    try_fewest_moves(cell, seed)
    return Client(cell, seed, counters)


# -- the kernels of the cycle -------------------------------------------------
def file_shape(cell) -> dict:
    """The shapes of the cycle's programs as the cell's files give them.

    The solver considers ``max_victims_considered`` fragment jobs, each in
    one step (a job at its gang minimum has no surplus to shed first); the
    first step is simulated and fails, and the prescreen scores the next
    ``scenario_prescreen_max``.  Every pod of the gang lacks a whole node
    and every step empties one, so the step that seats the gang is its
    size.  The solver confirms a scenario in one exact scan over the gang
    and every victim job it would place again, a job a chunk.  A cycle
    later the allocate action's wave holds those jobs, the gang and the
    gang that has just arrived: a group a fragment job, two a gang (the
    master, the workers), padded as ``allocate_grouped`` pads them; its
    second round holds the arrival alone."""
    occ, settings = cell.config["occupancy"], cell.config["scheduler"]
    frag = occ["fragment"]
    n = int(cell.config["nodes"]["count"])
    pods = int(frag["job_pods"])
    if pods != int(frag["min_available"]):
        raise SystemExit(f"{cell.config_path}: a fragment job stands at "
                         f"its gang minimum (one step a victim)")
    jobs = int(round(n * float(occ["fragmented_nodes_share"])))
    victims = min(jobs, int(settings["max_victims_considered"]))
    steps = min(victims - int(settings["scenario_prescreen_after"]),
                int(settings["scenario_prescreen_max"]))
    t = gen.gang_size(cell.traffic)
    roles = len(cell.traffic["gang"]["roles"])
    confirms = [(t + pods, 2), (t + t * pods, t + 1)]
    wave_groups = t + 2 * roles
    wave_jobs = t + 2 + gen.padded(wave_groups) - wave_groups
    largest = max(int(r["count"]) for r in cell.traffic["gang"]["roles"])
    return {"prefixes": gen.padded(steps), "rows": gen.padded(steps * pods),
            "nodes": n, "resources": 3, "t": t, "t_pad": gen.padded(t),
            "groups": roles, "moved": t * pods,
            "confirm_steps": sum(tasks for tasks, _jobs in confirms),
            # With the task rows' padding job.
            "confirms": [[gen.padded(tasks), gen.padded(jobs + 1)]
                         for tasks, jobs in confirms],
            # [groups, jobs, tasks, largest group] of the wave's rounds.
            "waves": [[gen.padded(wave_groups), gen.padded(wave_jobs),
                       gen.padded(2 * t + t * pods), gen.padded(largest)],
                      [gen.padded(roles), 1, gen.padded(t),
                       gen.padded(largest)]],
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


def _lower_wave(sds, shape: dict, groups: int, jobs: int, t_pad: int,
                largest: int):
    """The grouped fill lowered as the allocate action's bulk wave
    dispatches it (``allocate_grouped`` behind ``propose.place_wave``):
    several jobs, a gang of two groups among them, nothing releasing."""
    from kai_scheduler_tpu.ops.allocate_grouped import (
        _allocate_groups_packed, _resolve_fused_mode)
    from kai_scheduler_tpu.ops.scoring import BINPACK
    r = shape["resources"]
    f, i = np.float64, np.int32
    return _allocate_groups_packed.lower(
        *_node_tables(sds, shape),
        sds((groups, r), f), sds((groups, shape["selector_cols"]), i),
        sds((groups, shape["toleration_cols"]), i), sds((groups,), f),
        sds((groups,), i), sds((jobs,), bool), max_group=largest,
        t_pad=t_pad, group_indep=sds((groups,), bool), gpu_strategy=BINPACK,
        cpu_strategy=BINPACK, allow_pipeline=True, pipeline_only=False,
        single_group_jobs=False,
        fused_mode=_resolve_fused_mode(None, shape["nodes"]),
        releasing_empty=True, f32_keys=False)


def _lower_scan(sds, shape: dict, t_pad: int, j_pad: int,
                pipeline_only: bool):
    """The exact scan lowered with no node-axis operand: as the solver's
    confirm dispatches it (``_batched_confirm`` behind
    ``propose_placements_multi``: several jobs, pipeline only), and as the
    allocate action does for a gang of mixed rows alone in its cycle."""
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
        allow_pipeline=True, pipeline_only=pipeline_only)


def prime(client: Client, watch: loop.CompileWatch) -> dict:
    """Compile the programs of the cycle, each at the shape the cycle
    dispatches it, before the first guarded dispatch (the device guard
    gives a dispatch 30 s, compile included): the prescreen kernel, the
    exact scan of the solver's confirm in its two shapes, the grouped fill
    of the allocate action's wave in its two rounds, and the exact scan
    that the first cycle's allocate action gives the gang while it is the
    one job pending."""
    shape = file_shape(client.cell)
    sds = loop.device_operand
    lowerings = {"batch_prefix_feasibility": lambda: _lower(sds, shape)}
    for t_pad, j_pad in shape["confirms"]:
        lowerings[f"allocate_jobs_kernel[{t_pad},{j_pad}]"] = \
            lambda t=t_pad, j=j_pad: _lower_scan(sds, shape, t, j, True)
    for wave in shape["waves"]:
        lowerings["_allocate_groups_packed" + str(wave)] = \
            lambda w=wave: _lower_wave(sds, shape, *w)
    lowerings[f"allocate_jobs_kernel[{shape['t_pad']},2] first cycle"] = \
        lambda: _lower_scan(sds, shape, shape["t_pad"], 2, False)
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


def prefix_feasibility_bytes(prefixes: int, nodes: int, groups: int,
                             resources: int = 3) -> float:
    """Bytes one prescreen call must move at the least: it writes one f32
    releasing pool ``[K,N,R]``, the K states of the fleet it scores, and
    reads it again once for each of the gang's ``groups`` of identical
    pods (here two: the master, the workers), since pods of another shape
    fit other nodes.  NOT the exact scan's bytes a step times the gang's
    pods times K, which is what the scanning branch moves: a prescreen
    that scores a group in one step is no faster than the chip allows."""
    return (1.0 + groups) * prefixes * nodes * resources * 4


def exact_scan_bytes(steps: int, nodes: int, resources: int = 3,
                     label_cols: int = 0, taint_cols: int = 0) -> float:
    """Bytes the solver's confirms must move in a cycle, over ``steps``
    real pods (the gang's and those of the victim jobs it places again,
    both confirms together).  A step reads allocatable, idle and releasing
    [N,R] f32, pod room [N] and the label and taint tables; a confirm has
    no score row and no mask row (its job rows are ``[T]`` and ``[J]``,
    not ``[J,N]``), so ``benchmark/roofline.py``'s count of those is left
    out."""
    per_step = 3 * nodes * resources * 4 + nodes * 4 \
        + nodes * 4 * (label_cols + taint_cols)
    return float(steps) * per_step


def kernel_shapes(client: Client) -> dict:
    shape = client.primed
    return {
        "prefix_feasibility_bytes": {
            "prefixes": shape["prefixes"], "nodes": shape["nodes"],
            "groups": shape["groups"], "resources": shape["resources"]},
        "exact_scan_bytes": {
            "steps": shape["confirm_steps"],
            "nodes": shape["nodes"], "resources": shape["resources"],
            "label_cols": shape["label_cols"],
            "taint_cols": shape["taint_cols"]}}


def reckon(cell) -> dict:
    """What the consolidation cycle holds on the device, from the files.
    The client's buffers are the kernel's operands (node tables, release
    rows, task rows); the program's temporaries are ``[K,N,R]`` f32 arrays
    (the scattered releases, their running sum, the pools, and the vmapped
    scan's carries), each prefix another state of the fleet, and the
    scanning branch, which a gang of mixed rows takes, writes and reads
    every one."""
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
    "nodes_over_capacity": 0, "moved_not_preemptible": 0,
    "victim_gangs_split": 0, "evictions_without_consolidator": 0,
    "unknown_evictions": 0, "moved_without_place": 0,
    "moved_not_rebound": 0, "moves_beyond_need": 0,
}


def compare(records, ledger, cell) -> dict:
    """The verdict on the window's ``records``.  A gang is attempted where
    it arrived in the window with ``pending_cycles_max`` cycles left to
    bind in; a moved pod is held to its next cycle where the window has
    it."""
    ref = cell.reference
    pending_max = int(cell.traffic["pending_cycles_max"])
    out = {k: 0 for k in LIMITS}
    moves, binds, places, prescreens, counted = [], [], [], [], []
    dispatches = set()
    bound_in = {}                    # gang uid -> index of its bind cycle
    for i, rec in enumerate(records):
        waiting = rec.consolidator
        faults = ref.move_faults([(m.pod, m.preemptible) for m in rec.moved],
                                 rec.placed)
        for name, value in faults.items():
            out[name] += value
        gone = {}
        for m in rec.moved:
            gone[m.job] = gone.get(m.job, 0) + 1
        out["victim_gangs_split"] += ref.jobs_moved_in_part(
            rec.running_before, gone)
        out["evictions_without_consolidator"] += \
            ref.moves_without_consolidator(
                len(rec.moved),
                waiting.req.sum(axis=0) if waiting is not None else None,
                (ledger.capacity - rec.used_met).sum(axis=0))
        out["unknown_evictions"] += rec.unknown_evictions
        out["foreign_binds"] += rec.foreign_binds
        out["nodes_over_capacity"] += ref.nodes_over_capacity(
            ledger.capacity, rec.used_after, rec.pods_after,
            ledger.max_pods)
        for gang in rec.pending:
            bound = rec.bound.get(gang.uid, {})
            out["gangs_partly_bound"] += ref.gang_faults(
                len(bound), len(gang.names))["gangs_partly_bound"]
            if bound:
                bound_in[gang.uid] = rec.index
        if waiting is not None and rec.moved:
            fewest = ref.fewest_moves(
                ledger.capacity, rec.used_met, rec.pods_met, ledger.max_pods,
                waiting.req, rec.movable_sum, rec.movable_largest)
            out["moves_beyond_need"] += max(0, len(rec.moved) - fewest)
        if i + 1 < len(records):
            out["moved_not_rebound"] += ref.replacements_not_bound(
                {m.pod: m.replaced_by for m in rec.moved},
                records[i + 1].rebound)
        moves.append(len(rec.moved))
        binds.append(sum(len(b) for b in rec.bound.values())
                     + len(rec.rebound))
        places.append(len(rec.placed))
        prescreens.append(sum(1 for span in rec.spans
                              if span[0] == "dispatch:scenario_prescreen"))
        counted.append(rec.counters.get("scenario_prescreen_counted_total",
                                        0.0))
        dispatches.update(span[0] for span in rec.spans
                          if span[0].startswith("dispatch:")
                          and not span[0].endswith(("_fetch", "_retry")))
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
        "run": {"gangs": len(due), "evictions": sum(moves),
                "evictions_per_cycle": sorted(set(moves)),
                "binds_per_cycle": sorted(set(binds)),
                "places_per_cycle": sorted(set(places)),
                "prescreens_per_cycle": sorted(set(prescreens)),
                "counted_prescreens_per_cycle": sorted(set(counted)),
                "dispatches": sorted(dispatches),
                "bind_cycles_after_arrival": sorted(
                    {bound_in[r.arrived.uid] - r.index for r in due
                     if r.arrived.uid in bound_in})}}
