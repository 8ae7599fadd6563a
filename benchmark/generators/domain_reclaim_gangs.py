"""The generator ``domain_reclaim_gangs``: gangs that must lie inside ONE
topology domain reclaim on a full fleet, several a cycle, from several
starved queues.

The fleet is ``reclaim_gangs``' (its client imported from the file beside
this one, nothing of it edited) laid out BY RACK: full, a share of it under
one leaf queue's preemptible jobs, the others under whole-node pods of
other queues.  What a required topology level changes belongs here:

- the nodes carry the mesh's labels (``nodes.labels`` of the
  configuration: contiguous blocks by node index) and the cluster its
  ``topologies``; the occupier holds WHOLE racks drawn from the seed, the
  whole-node gangs runs of whole racks;
- the victims' creation order, which is upstream's victim order read
  backwards, is STRIPED: the occupier's racks in stripes of
  ``occupancy.stripe_racks``, and inside a stripe waves of
  ``occupancy.wave_jobs`` jobs (one hostgroup) handed out round-robin over
  the stripe's racks.  So the newest jobs lie a wave to a rack: GPUs
  enough for a gang are free SOMEWHERE long before they are free in ONE
  rack.  The refill after a gang leaves walks the same order;
- ``gangs_per_cycle`` gangs arrive a cycle, round-robin over
  ``reclaimer_queues`` of the leaf queues that run nothing.  Where they
  do not go evenly (ISSUE 53's 8 over 3) the run is not correct, and that
  is a finding, not a fault of the files: the allocate action serves the
  least-served queue first, so the queue that was handed fewer gangs is
  owed a turn a cycle later and takes, with its NEW gang, the rack freed
  for a sibling's older one (``gangs_not_bound``; PERF.md section 7).
  Every gang carries the traffic file's ``topology`` (a required level);
- the victims are elastic where the configuration says so
  (``occupancy.min_available`` under ``job_pods``): a job is then two
  steps, its surplus and its gang, and what a scenario places again of it
  is every pod it took, a chunk and then a pod at a time;
- the client gives the scheduler a cache that keeps the ORDER of what a
  cycle wrote (``preempt_replicas``' ``OrderedCache``: a commit nominates
  its places, the gang's and those of the victims it places again, and
  then evicts), so that the comparison holds every COMMIT to the
  reference on the state that commit met
  (``reference/domain_eviction.py``);
- ``compare`` holds every cycle of the window to thirteen counts, every
  limit 0 (whole numbers of pods, jobs, nodes, racks and queues);
- the byte counts of the rooflines are fed by what the cycle DISPATCHED
  (the program's counters in the traced cycle's record, the commits read
  back), not by the files;
- ``try_rack_reclaim``: one rack-bound gang on a small striped fleet before
  the run's is built.  A program that cannot reclaim under a required
  level (its prescreen blind to domains, its solver out of scenarios after
  16 capacity-feasible prefixes) stops there with status 1.
"""

from __future__ import annotations

import copy
import gc
import os
import time
from dataclasses import dataclass, field
from itertools import islice

import numpy as np

from benchmark.harness import cluster as gen
from benchmark.harness import loop, spec
from kai_scheduler_tpu.api import ClusterInfo, NodeInfo, PodStatus

_HERE = os.path.dirname(os.path.abspath(__file__))
base = spec.load_module(os.path.join(_HERE, "reclaim_gangs.py"),
                        "generator", "reclaim_gangs")
lws = spec.load_module(os.path.join(_HERE, "preempt_replicas.py"),
                       "generator", "preempt_replicas")

TICK = lws.TICK
EMPTY_LEAVES = 3         # the leaf queues that run nothing
# The program's counters the byte counts are fed by (each also a per-layer
# metric of the cell, which is how the harness comes to read them).
CALLS = "scenario_prescreen_calls_total"
CELLS = "scenario_prescreen_pool_cells_total"
RUNS = "scenario_prescreen_scan_steps_total"


@dataclass
class Victim:
    """An evicted pod, with what the client's book says of its job."""
    pod: str
    job: str
    queue: str
    preemptible: bool
    min_available: int
    node: int                        # where it stood when it was evicted
    req: np.ndarray                  # [3]


@dataclass
class Commit:
    """What one commit wrote: places nominated, then pods evicted."""
    gang: str | None = None
    nominated: list = field(default_factory=list)   # (gang pod, node)
    replaced: list = field(default_factory=list)    # (victim pod, node)
    evicted: list = field(default_factory=list)     # Victim
    unknown_evictions: int = 0


@dataclass
class CycleRecord:
    index: int
    pending: list                    # gangs pending in this cycle
    arrived: list                    # the gangs that arrived in it
    used_before: np.ndarray          # [N,3] the ledger before the cycle
    pods_before: np.ndarray          # [N]
    queue_used_before: dict          # queue -> [3], leaf to root
    head: list = field(default_factory=list)   # the newest victim jobs
    commits: list = field(default_factory=list)
    running_before: dict = field(default_factory=dict)   # job -> pods
    deleted: dict = field(default_factory=dict)          # job -> pods gone
    bound: dict = field(default_factory=dict)     # gang uid -> {pod: node}
    foreign_binds: int = 0
    used_after: np.ndarray | None = None
    pods_after: np.ndarray | None = None
    t_sched: float = 0.0
    counters: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    trace_t0: float = 0.0


def blocks(config: dict) -> dict:
    """level -> nodes in a domain of it, by the configuration's labels."""
    return {lab["key"]: int(lab["block"])
            for lab in config["nodes"]["labels"]}


class Client(base.Client):
    """``reclaim_gangs``' loop over racks, ``gangs_per_cycle`` a cycle."""

    def __init__(self, cell, seed: int, counters: tuple = ()):
        # ``reclaim_gangs.Client.__init__`` with the mesh's labels, the
        # cluster's topologies and the striped order.
        from kai_scheduler_tpu.scheduler import Scheduler
        self.made = 0                # jobs made so far: the creation clock
        self.cell = cell
        self.config = config = cell.config
        self.traffic = traffic = cell.traffic
        settings = loop.scheduler_config(config, cell.config_path)
        rng = np.random.default_rng([int(seed), 1])
        self.ledger = ledger = gen.Ledger(config)
        shape = config["nodes"]
        alloc = gen.res_vec(shape)
        self.node_names = [gen.node_name(i) for i in range(ledger.n)]
        nodes = {name: NodeInfo(
            name, alloc, max_pods=ledger.max_pods,
            labels={lab["key"]: f"{lab['key']}{i // int(lab['block']):05d}"
                    for lab in shape["labels"]})
            for i, name in enumerate(self.node_names)}
        queues = gen.build_queues(config, ledger)
        leaves = gen.leaf_queues(ledger)
        ordered = [leaves[int(i)] for i in rng.permutation(len(leaves))]
        parent = ledger.queue_parent
        self.occupier = ordered[0]
        # The queues that run nothing: leaves of ONE department, another
        # than the occupier's (siblings of equal share are served in
        # turn; of two departments the one that holds less is served
        # first with all it has).  ``reclaimer_queues`` of them reclaim.
        home = next(parent[q] for q in ordered[1:]
                    if parent[q] != parent[self.occupier])
        empty = [q for q in ordered if parent[q] == home][:EMPTY_LEAVES]
        self.reclaimers = empty[:int(traffic["reclaimer_queues"])]
        self.reclaimer = empty[-1]
        self.per_cycle = int(traffic["gangs_per_cycle"])
        self.cluster = ClusterInfo(nodes, {}, queues,
                                   topologies=config["topologies"],
                                   now=1000.0)
        occ = config["occupancy"]
        self.occ_req = gen.res_vec(occ["pod"])
        self.occ_rr = base._requirements(occ["pod"])
        self.jobs = {}
        self.pod_job = {}
        self.next_job = 0
        self._order_nodes(rng)
        gc.disable()
        try:
            # The whole-node gangs go to the other leaves, each up to its
            # deserved share, and run out before the reclaimers' turn.
            self._hold_whole_nodes(
                [q for q in ordered
                 if q != self.occupier and q not in empty] + empty)
            self._fill()
        finally:
            gc.enable()
        idle = [q for q in empty if ledger.queue_used[q].any()]
        if idle:
            raise SystemExit(f"{cell.config_path}: the reclaimers' queues "
                             f"{idle} were given whole-node pods")
        self.sched = Scheduler(lambda: self.cluster, settings)
        self.sched.cache = lws.OrderedCache()
        self.lifetime = int(traffic["lifetime_cycles"])
        self.head_jobs = 2 * int(settings.max_victims_considered)
        self.node_index = {name: i for i, name in enumerate(self.node_names)}
        self.pending = []
        self.running = []
        self.gangs = []
        self.records = []
        self.counters = tuple(counters)

    def _order_nodes(self, rng) -> None:
        """``node_order``: the occupier's racks first, in the order its
        jobs are made (stripes of racks, waves of one hostgroup round-robin
        over a stripe's racks), then the whole-node pods' racks, rack by
        rack."""
        occ, n = self.config["occupancy"], self.ledger.n
        size = blocks(self.config)
        rack = size["rack"]
        jobs_a_node = int(self.config["nodes"]["gpu"]) // (
            int(occ["job_pods"]) * int(occ["pod"]["gpu"]))
        wave = int(occ["wave_jobs"]) // jobs_a_node      # nodes a wave
        stripe = int(occ["stripe_racks"])
        held = int(round(n * float(occ["preemptible_nodes_share"])))
        if n % rack or held % rack or rack % wave:
            raise SystemExit(
                f"{self.cell.config_path}: {n} nodes, {held} of them the "
                f"occupier's, do not come to whole racks of {rack} nodes "
                f"in waves of {wave}")
        racks = rng.permutation(n // rack)
        mine, others = racks[:held // rack], racks[held // rack:]
        order = []
        for first in range(0, len(mine), stripe):
            group = mine[first:first + stripe]
            for k in range(len(group) * (rack // wave)):
                start = int(group[k % len(group)]) * rack \
                    + (k // len(group)) * wave
                order.append(np.arange(start, start + wave))
        order += [np.arange(int(r) * rack, (int(r) + 1) * rack)
                  for r in others]
        self.node_order = np.concatenate(order)
        self.node_rank = np.empty(n, np.int64)
        self.node_rank[self.node_order] = np.arange(n)

    # -- the book ---------------------------------------------------------
    def _book(self, job) -> None:
        """A new job gets the next creation time before the cluster sees
        it: the book's order is the order of creation."""
        job.created = self.made * TICK
        self.made += 1
        super()._book(job)

    def _show(self, job) -> None:
        super()._show(job)
        pg = self.cluster.podgroups.get(job.uid)
        if pg is not None:
            pg.creation_ts = job.created

    def _head(self) -> list:
        """The newest ``head_jobs`` preemptible jobs of the book, newest
        first, as the comparison needs them: (uid, queue, created,
        min_available, req, {pod: node}).  The book is in creation order
        (a dict keeps insertion order; nothing is ever re-entered)."""
        newest = islice((j for j in reversed(self.jobs.values())
                         if j.preemptible), self.head_jobs)
        return [(j.uid, j.queue, j.created, j.min_available, j.req,
                 dict(j.pods)) for j in newest]

    # -- one cycle ---------------------------------------------------------
    def _before(self) -> list:
        """Completions and refills, and the cycle's arrivals."""
        done = [r for r in self.running if r[2] >= self.lifetime]
        for r in self.running:
            r[2] += 1
        if done:
            self.running = [r for r in self.running if r not in done]
            self._fill(sum(self._complete(gang, pg)
                           for gang, pg, _ran in done))
        arrived = []
        for k in range(self.per_cycle):
            index = len(self.gangs)
            pg, gang = gen.make_gang(
                self.traffic, index,
                self.reclaimers[k % len(self.reclaimers)])
            pg.creation_ts = self.cluster.now + k * TICK
            self.gangs.append(gang)
            self.cluster.podgroups[pg.uid] = pg
            self.pending.append((gang, pg))
            arrived.append(gang)
        self.cluster.invalidate_aggregates()
        return arrived

    def _read_writes(self, rec: CycleRecord) -> tuple:
        """The cycle's nominations and evictions, cut into commits (a
        commit writes its nominations and then its evictions, so a
        nomination after an eviction, or for another gang, opens the next
        one), with the book moved along: a victim placed again stands
        where it was placed when a later commit takes it.  Returns, for
        every pod a commit evicted: pod -> the nodes it was evicted from,
        in order (the first is where it stood when the cycle began), and
        pod -> where it stands placed at the cycle's end, if it does."""
        member = {name: gang.uid for gang, _pg in self.pending
                  for name in gang.names}
        raw, commit, gang = [], None, None
        for kind, pod, node in self.sched.cache.writes:
            if commit is None or (kind == "nominate" and (
                    commit[1] or member.get(pod, gang) != gang)):
                commit, gang = ([], []), member.get(pod)
                raw.append(commit)
            commit[kind == "evict"].append((pod, node))
        self.sched.cache.writes.clear()
        left, placed = {}, {}
        for nominations, evictions in raw:
            out = Commit()
            rec.commits.append(out)
            for pod, _none in evictions:
                job = self.jobs.get(self.pod_job.get(pod))
                if job is None:
                    out.unknown_evictions += 1
                    continue
                left.setdefault(pod, []).append(job.pods[pod])
                placed.pop(pod, None)
                out.evicted.append(Victim(
                    pod, job.uid, job.queue, job.preemptible,
                    job.min_available, job.pods[pod], job.req))
            for pod, node in nominations:
                where = self.node_index[node]
                if pod in member:
                    out.gang = member[pod]
                    out.nominated.append((pod, where))
                elif pod in self.pod_job:
                    out.replaced.append((pod, where))
                    self.jobs[self.pod_job[pod]].pods[pod] = where
                    placed[pod] = where
        return left, placed

    def _delete(self, pod: str, left: list, placed) -> None:
        """Take an evicted pod off the cluster's nodes.  It is RELEASING on
        every node a commit evicted it from (the statement leaves it there
        and, where the same commit placed it again, puts it PIPELINED on
        the new node as well; a later commit's eviction turns that into
        one more RELEASING), and PIPELINED where it stands placed."""
        task = self.cluster.podgroups[self.pod_job[pod]].pods[pod]
        nodes, names = self.cluster.nodes, self.node_names
        for status, at in [(PodStatus.RELEASING, n) for n in left] + (
                [] if placed is None else [(PodStatus.PIPELINED, placed)]):
            task.status, task.node_name = status, names[at]
            nodes[task.node_name].remove_task(task)
        # Off every node: ``_show`` finds nothing of it to take away.
        task.status = PodStatus.PENDING

    def _settle(self, rec: CycleRecord) -> None:
        """Read back what the cycle wrote, as the binder and the kubelets
        would see it.  An eviction deletes the pod, placed again or not
        (PERF.md section 7).  A pod that no commit placed again, or that
        a later commit took again for good, is gone.  One that still
        stands placed at the cycle's end comes back where the scheduler
        nominated it: its controller makes the pod anew and the binder
        binds it there, and the book shows it running at its new place
        (a move, which is what the scenario decided; were it left out,
        the GPUs a scenario gave back to its victims would stand idle
        and the fleet would no longer be full)."""
        cache = self.sched.cache
        left, placed = self._read_writes(rec)
        touched = {}
        for pod, nodes in left.items():
            at = placed.get(pod)
            self._delete(pod, nodes, at)
            job = self.jobs[self.pod_job[pod]]
            touched[job.uid] = job
            self.ledger.charge(job.queue, np.array([nodes[0]]),
                               job.req[None, :], -1.0)
            if at is not None:
                # ``_read_writes`` entered its new place in the book.
                self.ledger.charge(job.queue, np.array([at]),
                                   job.req[None, :])
                continue
            del self.pod_job[pod]
            rec.running_before.setdefault(
                job.uid, len(job.pods) + rec.deleted.get(job.uid, 0))
            rec.deleted[job.uid] = rec.deleted.get(job.uid, 0) + 1
            del job.pods[pod]
        for job in touched.values():
            self._show(job)
            if not job.pods:
                del self.jobs[job.uid]
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
                               for q, v in ledger.queue_used.items()},
            head=self._head())
        self.cluster.now += 1.0
        loop.run_once(self.sched, rec, self.counters, phase)
        with phase("bench:client_after"):
            self._settle(rec)
        self.records.append(rec)
        return rec


# -- the trial before the fleet ----------------------------------------------
# 1,024 nodes in racks of 64 (16 racks), the cell's queue tree (a leaf's
# deserved share is 64 nodes, 512 GPUs: three gangs in flight stay inside
# it; the occupier's quarter is its limit): four racks the occupier's, in
# stripes of two, 512 jobs.  ONE gang of 160 one-GPU pods a cycle: 160
# GPUs are free somewhere after the newest 40 jobs have gone and in one
# rack after 72 (the stripe's first rack has waves 0, 2 and 4), 32 more:
# twice what a solver simulates for one job.
TRIAL = {"nodes": 1024, "gang": 160, "gangs": 1, "whole": 64,
         "victims": 512, "stripe": 2}
TRIAL_CYCLES = 3


def cut_cell(cell, nodes: int, gang: int, gangs: int, whole: int,
             victims: int, share: float | None = None,
             stripe: int | None = None, departments: int | None = None,
             leaves: int | None = None, limit_factor: float | None = None):
    """A copy of the cell with its fleet, its gang, its step and the
    solver's caps cut; the mesh keeps its levels and their sizes, the gang
    its one master, its constraint and its pods' requests."""
    cut = copy.copy(cell)
    cut.config = copy.deepcopy(cell.config)
    cut.traffic = copy.deepcopy(cell.traffic)
    cut.config["nodes"]["count"] = nodes
    occ = cut.config["occupancy"]
    occ["whole_node"]["gang_pods"] = whole
    if share is not None:
        occ["preemptible_nodes_share"] = share
    if stripe is not None:
        occ["stripe_racks"] = stripe
    if departments is not None:
        cut.config["queues"].update(departments=departments,
                                    leaves_per_department=leaves)
    if limit_factor is not None:
        cut.config["queues"]["limit_factor"] = limit_factor
    cut.config["scheduler"].update(max_victims_considered=victims,
                                   scenario_prescreen_max=victims)
    cut.traffic["gangs_per_cycle"] = gangs
    cut.traffic["reclaimer_queues"] = min(
        gangs, int(cut.traffic["reclaimer_queues"]))
    master, worker = cut.traffic["gang"]["roles"]
    worker["count"] = gang - int(master["count"])
    return cut


def try_rack_reclaim(cell, seed: int) -> dict:
    """Three cycles of the deployment on 1,024 nodes with one gang a
    cycle, through the cell's own ``compare``, before the run's fleet is
    built: a gang of 160 pods that must lie inside one rack arrives inside
    its quota while the fleet is full, has its GPUs reclaimed inside ONE
    rack in the cycle it arrives in (the smallest prefix of upstream's
    order that allows it: 72 jobs, where 40 free as many GPUs somewhere),
    and is bound there in the next.

    A program whose prescreen answers for the fleet and not for a rack
    (as this repo's did before PR 53) finds every prefix from the 40th on
    feasible, simulates ``max_scenarios_per_job`` (16) of them, gives up,
    and the gang is pending for ever: it stops here with status 1, soon."""
    trial = cut_cell(cell, **TRIAL)
    t0 = time.perf_counter()
    client = Client(trial, seed)
    for _ in range(TRIAL_CYCLES):
        client.cycle()
    records, ledger = client.records, client.ledger
    client.close()
    verdict = compare(records, ledger, trial)
    if not verdict["correct"]:
        raise SystemExit(
            f"{cell.name}: this program cannot run the configuration "
            f"{cell.entry['config']}: a PyTorchJob of {TRIAL['gang']} pods "
            f"that must lie inside one rack, for which one rack frees up "
            f"32 victim jobs after the fleet does, is not reclaimed for "
            f"inside one rack in its cycle and bound there in the next; "
            f"compared (value, limit): "
            f"{ {k: v for k, v in verdict['compared'].items() if v[0]} }")
    return {"seconds": round(time.perf_counter() - t0, 3),
            "nodes": TRIAL["nodes"], "gang": TRIAL["gang"],
            "prefix_jobs": verdict["run"]["prefix_jobs_per_commit"]}


def build(cell, seed: int, counters: tuple = ()) -> Client:
    from kai_scheduler_tpu.utils.tracing import TRACER
    trial = try_rack_reclaim(cell, seed)
    client = Client(cell, seed, counters)
    client.trial = trial
    # The flight recorder keeps 512 spans a cycle and counts the rest as
    # dropped, and the span readers sum what was kept (an ``action:reclaim``
    # that closes after the limit is not there at all).  A solve is some
    # 100 spans (``preempt_replicas``' figure) and, in its confirm, one
    # ``extra_scores:<plugin>`` span a registered fn for EVERY job of the
    # call, and a confirm is two calls: the gang and each victim of the
    # prefix, then each victim that stands again (four fns: the first
    # traced run of this cell kept 2 of its 8 prescreens, my chip run,
    # PR 53).  Deepen it to hold the whole cycle: a prefix is a stripe's
    # first rack's worth of jobs at the longest while the fleet stands
    # full, and was seen a quarter longer where racks stood part idle;
    # twice that is allowed for.
    occ = cell.config["occupancy"]
    waves = -(-gen.gang_size(cell.traffic)
              // (int(occ["job_pods"]) * int(occ["wave_jobs"])))
    longest = 2 * ((waves - 1) * int(occ["stripe_racks"]) + 1) \
        * int(occ["wave_jobs"])
    TRACER.max_spans_per_trace = max(
        TRACER.max_spans_per_trace,
        512 + client.per_cycle * (lws.SPANS_A_SOLVE + 8 * longest))
    return client


# -- the kernels of the cycle -------------------------------------------------
def file_shape(cell) -> dict:
    """The shapes of the cycle's programs as the cell's files give them,
    for ``prime`` alone (the byte counts are fed by what was dispatched).

    A solve considers ``max_victims_considered`` jobs, two steps each
    where they are elastic; the first step is simulated and fails before
    any placement call (no rack holds the gang), and the prescreen scores
    the others in the domain form: the fleet as a table of racks.  The
    confirm scans the gang and every job of its prefix; the prefix
    shrinks from gang to gang of a cycle (the racks of the stripe are
    part freed already), so the confirm's padded shapes are a few."""
    occ, settings = cell.config["occupancy"], cell.config["scheduler"]
    n = int(cell.config["nodes"]["count"])
    size = blocks(cell.config)
    level = cell.traffic["gang"]["topology"]["required"]
    pods, minimum = int(occ["job_pods"]), int(occ["min_available"])
    surplus = max(0, pods - minimum)
    jobs = int(round(n * float(occ["preemptible_nodes_share"]))) \
        * int(cell.config["nodes"]["gpu"]) // pods
    victims = min(jobs, int(settings["max_victims_considered"]))
    # An elastic victim is two steps, its surplus and then its gang.
    after = int(settings["scenario_prescreen_after"])
    sizes = ([surplus, minimum] if surplus else [pods]) * victims
    steps = min(len(sizes) - after, int(settings["scenario_prescreen_max"]))
    rows = sum(sizes[after:after + steps])  # the release rows: a pod each
    t = gen.gang_size(cell.traffic)
    # The table of domains as the program lays it out.
    from kai_scheduler_tpu.ops.topology import domain_slots
    domains = n // size[level]
    slot_node, d_pad = domain_slots(np.arange(n) // size[level], n)
    # A confirm is two calls.  The first scans the gang and the NEXT CHUNK
    # of every victim of the prefix (its gang minimum; one pod of the
    # prefix's last job where only its surplus went): t + minimum * j
    # tasks, or one less, of j + 1 jobs and the padding job.  The second
    # scans what else the scenario took of the victims that stand again,
    # a pod a job: r tasks of r jobs and the padding job.  With nine gangs
    # to stripes of eight racks the prefixes of a window run from one wave
    # to several hundred jobs, so every pair of padded sizes that
    # ``max_victims_considered`` allows is compiled.
    confirms = sorted({(gen.padded(t + minimum * j - last), gen.padded(j + 2))
                       for j in range(1, victims + 1) for last in (0, 1)})
    rests = sorted({(gen.padded(r), gen.padded(r + 1))
                    for r in range(1, surplus * victims + 1)})
    return {"prefixes": gen.padded(steps), "rows": gen.padded(rows),
            "nodes": n, "resources": 3, "t": t, "t_pad": gen.padded(t),
            "domains": domains, "d_pad": d_pad,
            "slots": len(slot_node),
            "runs": len(cell.traffic["gang"]["roles"]),
            "confirms": [list(c) for c in confirms],
            "rests": [list(c) for c in rests],
            # No pod selects on a label, so the mesh's are in no
            # vocabulary: the tables have their one empty column.
            "label_cols": 1, "taint_cols": 1, "selector_cols": 1,
            "toleration_cols": 1}


def _lower(sds, shape: dict):
    """``batch_prefix_feasibility`` lowered as ``_prefix_prescreen``
    dispatches it for a gang with a required level: the domain form."""
    from kai_scheduler_tpu.ops.scenario_batch import \
        batch_prefix_feasibility
    from kai_scheduler_tpu.ops.scoring import BINPACK
    r, t, m = shape["resources"], shape["t_pad"], shape["rows"]
    f, i = np.float64, np.int32
    return batch_prefix_feasibility.lower(
        *base._node_tables(sds, shape),
        sds((m,), i), sds((m,), i), sds((m, r), f),
        sds((t, r), f), sds((t,), i), sds((t, shape["selector_cols"]), i),
        sds((t, shape["toleration_cols"]), i),
        num_prefixes=shape["prefixes"], gpu_strategy=BINPACK,
        cpu_strategy=BINPACK, slot_node=sds((shape["slots"],), i),
        domain_ok=sds((shape["d_pad"],), bool),
        num_domains=shape["d_pad"])


def _lower_scan(sds, shape: dict, t_pad: int, j_pad: int, kind: str):
    """The exact scan lowered as the cycle dispatches it: ``confirm``, the
    solver's first call (pipeline only; ONE ``[N]`` row that holds the
    first job, the gang, to its rack, the victims behind it anywhere);
    ``rest``, its second (what else the scenario took of the victims that
    stand again, a pod a job, a victim's jobs a chain) and ``rest-single``
    (the same where no victim brings two); ``bind``, the allocate
    action's one chunk a cycle later (a ``[J,N]`` row a job)."""
    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
    from kai_scheduler_tpu.ops.scoring import BINPACK
    f, i = np.float64, np.int32
    n = shape["nodes"]
    subset = {
        "confirm": {"job_node_mask": None,
                    "first_job_node_mask": sds((n,), bool)},
        "rest": {"job_node_mask": None, "job_follows": sds((j_pad,), bool)},
        "rest-single": {"job_node_mask": None},
        "bind": {"job_node_mask": sds((j_pad, n), bool)}}[kind]
    return allocate_jobs_kernel.lower(
        *base._node_tables(sds, shape),
        sds((t_pad, shape["resources"]), f), sds((t_pad,), i),
        sds((t_pad, shape["selector_cols"]), i),
        sds((t_pad, shape["toleration_cols"]), i), sds((j_pad,), bool), None,
        task_node_mask=None, task_anti_domain=None, task_aff_domain=None,
        job_extra_scores=None, **subset,
        gpu_strategy=BINPACK, cpu_strategy=BINPACK,
        allow_pipeline=True, pipeline_only=kind != "bind")


def _lower_aggregates(sds, shape: dict):
    """``domain_aggregates`` lowered as ``subset_nodes`` dispatches it for
    the gang's level."""
    from kai_scheduler_tpu.ops.topology import domain_aggregates
    n, r = shape["nodes"], shape["resources"]
    f = np.float64
    return domain_aggregates.lower(
        sds((n, r), f), sds((n,), f), sds((n,), np.int32), sds((r,), f),
        float(shape["t"]), shape["domains"])


def prime(client: Client, watch: loop.CompileWatch) -> dict:
    """Compile the programs of the cycle, each at the shape the cycle
    dispatches it, before the first guarded dispatch (the device guard
    gives a dispatch 30 s, compile included): the prescreen's domain form,
    the exact scan of the solver's confirm (its two calls) in its padded
    shapes, the exact scan of the allocate action's bind, and
    ``subset_nodes``' aggregates."""
    shape = file_shape(client.cell)
    sds = loop.device_operand
    lowerings = {
        "batch_prefix_feasibility": lambda: _lower(sds, shape),
        "domain_aggregates": lambda: _lower_aggregates(sds, shape),
        f"allocate_jobs_kernel[{shape['t_pad']},2] bind":
        lambda: _lower_scan(sds, shape, shape["t_pad"], 2, "bind")}
    for kind, pairs in (("confirm", shape["confirms"]),
                        ("rest", shape["rests"]),
                        ("rest-single", shape["rests"][:2])):
        for t_pad, j_pad in pairs:
            lowerings[f"allocate_jobs_kernel[{t_pad},{j_pad}] {kind}"] = \
                lambda t=t_pad, j=j_pad, k=kind: _lower_scan(sds, shape, t,
                                                             j, k)
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


def prefix_feasibility_bytes(cells: float, runs_a_call: float,
                             resources: int = 3) -> float:
    """Bytes a cycle's prescreen calls must move at the least, by the
    WORK they were dispatched (``cells``: the ``[K, slots]`` cells of the
    pools the calls built, the program's own count; ``runs_a_call``: the
    gang's runs of identical pods): a call writes one f32 releasing pool
    ``[K, slots, R]``, the K states of the fleet as a table of domains,
    and reads it again once for each run (``pool_reclaim_gangs``' rule;
    the host's rule a domain reads the same cells in the same pass).  NOT
    the bytes of every temporary the vmapped forms make: the count does
    not follow the form, so it can never pass 100 %."""
    return (1.0 + runs_a_call) * cells * resources * 4


def exact_scan_bytes(steps: int, nodes: int, resources: int = 3,
                     label_cols: int = 0, taint_cols: int = 0) -> float:
    """``reclaim_gangs``' count (a step reads allocatable, idle and
    releasing [N,R] f32, pod room [N] and the label and taint tables) and
    the step's ``[N]`` bool row of its job's node subset."""
    return base.exact_scan_bytes(steps, nodes, resources, label_cols,
                                 taint_cols) + float(steps) * nodes


def kernel_shapes(client: Client) -> dict:
    """What the TRACED cycle dispatched (the window's first): the
    prescreen's cells and runs from the program's counters in that
    cycle's record, the exact scan's real steps from what its commits and
    its binds read back.  A program without the counters gives no
    prescreen shape, and the roofline's line leaves the share out."""
    shape = client.primed
    warm = int(client.traffic.get("warm_cycles", 1))
    shapes = {}
    if len(client.records) <= warm:
        return shapes
    rec = client.records[warm]
    calls = rec.counters.get(CALLS, 0)
    if calls and CELLS in rec.counters and RUNS in rec.counters:
        shapes["prefix_feasibility_bytes"] = {
            "cells": float(rec.counters[CELLS]),
            "runs_a_call": float(rec.counters[RUNS]) / calls,
            "resources": shape["resources"]}
    steps = sum(len(c.nominated) + len(c.evicted) for c in rec.commits) \
        + sum(len(b) for b in rec.bound.values())
    shapes["exact_scan_bytes"] = {
        "steps": steps, "nodes": shape["nodes"],
        "resources": shape["resources"],
        "label_cols": shape["label_cols"],
        "taint_cols": shape["taint_cols"]}
    return shapes


def reckon(cell) -> dict:
    """What the cycle holds on the device, from the files: the client's
    buffers are the kernels' operands (node tables, release rows, task
    rows, the slot table, the bind's ``[2,N]`` subset); the program's
    temporaries are ``[K, slots, R]`` f32 arrays (the scattered releases,
    their running sum, the pools, the run loop's carries), each prefix
    another state of the fleet as a table of domains."""
    shape = file_shape(cell)
    k, n, r = shape["prefixes"], shape["nodes"], shape["resources"]
    subset = 2 * n
    operands = 4 * (n * (3 * r + shape["label_cols"] + shape["taint_cols"]
                         + 1)
                    + shape["rows"] * (2 + r) + shape["slots"]
                    + shape["t_pad"] * (r + 1 + shape["selector_cols"]
                                        + shape["toleration_cols"])) + subset
    one = k * shape["slots"] * r * 4
    return {"bytes": float(operands),
            "program_bytes": float(base.PRESCREEN_ARRAYS * one),
            "what": f"batch_prefix_feasibility, domain form [K={k}, "
                    f"slots={shape['slots']} ({shape['domains']} domains), "
                    f"R={r}] f32 = {one:,} bytes an array x "
                    f"{base.PRESCREEN_ARRAYS}, operands {operands:,} bytes"}


def compile_for(cell, sds):
    return _lower(sds, file_shape(cell)).compile()


# -- the comparison ---------------------------------------------------------
LIMITS = {
    "gangs_not_bound": 0, "gangs_partly_bound": 0, "foreign_binds": 0,
    "nodes_over_capacity": 0, "gangs_outside_one_rack": 0,
    "victims_not_preemptible": 0, "victims_from_own_queue": 0,
    "evictions_without_reclaimer": 0, "unknown_evictions": 0,
    "victim_gangs_below_minimum": 0, "victims_outside_upstream_prefix": 0,
    "evictions_beyond_need": 0, "quota_faults": 0,
}


def _steps_of(head: list, where: dict, gone: set, own_queue: str,
              cap: int, ref) -> list:
    """The victim steps a reclaimer of ``own_queue`` is offered, in
    upstream's order: of the newest jobs (``head``) those of other queues
    with a pod still in place, ``cap`` of them; a job's surplus beyond its
    gang minimum (its last pods by name) before its core gang.  Each step
    is (pods, nodes [m], reqs [m,3])."""
    order = ref.victim_order([0.0] * len(head), [h[2] for h in head])
    steps = []
    taken = 0
    for i in order.tolist():
        uid, queue, _created, minimum, req, pods = head[i]
        if queue == own_queue:
            continue
        active = sorted(p for p in pods if p not in gone)
        if not active:
            continue
        taken += 1
        if taken > cap:
            break
        for part in (active[minimum:], active[:minimum]):
            if part:
                steps.append((part, np.array([where[p] for p in part]),
                              np.tile(req, (len(part), 1))))
    return steps


def compare(records, ledger, cell) -> dict:
    """The verdict on the window's ``records``.  A gang is attempted where
    it arrived in the window with ``pending_cycles_max`` cycles left to
    bind in.  Every commit is held to the reference on the state it met:
    the ledger before the cycle, the cycle's binds (allocate runs first),
    and the commits before it."""
    ref = cell.reference
    pending_max = int(cell.traffic["pending_cycles_max"])
    cap = int(cell.config["scheduler"]["max_victims_considered"])
    level = cell.traffic["gang"]["topology"]["required"]
    seg = ledger.levels[level]
    tree = cell.config["queues"]
    total = ledger.capacity.sum(axis=0)
    parent = ledger.queue_parent
    deserved = {q: ref.deserved_share(
        total, int(tree["departments"]), int(tree["leaves_per_department"]),
        leaf=parent[q] is not None) for q in parent}
    out = {k: 0 for k in LIMITS}
    gangs = {}
    bound_in = {}
    binds, writes, stay, own, prefixes, prescreens = \
        [], [], [], [], [], []
    for rec in records:
        gangs.update({g.uid: g for g in rec.pending})
        out["foreign_binds"] += rec.foreign_binds
        used, pods = rec.used_before.copy(), rec.pods_before.copy()
        queue_used = {q: v.copy() for q, v in rec.queue_used_before.items()}

        def charge(queue, amount):
            while queue is not None:
                queue_used[queue] = queue_used[queue] + amount
                queue = parent[queue]

        # Allocate runs first: what the cycle bound is in place when the
        # reclaimers are solved.
        for gang in rec.pending:
            bound = rec.bound.get(gang.uid, {})
            out["gangs_partly_bound"] += int(0 < len(bound)
                                             < len(gang.names))
            if not bound:
                continue
            bound_in[gang.uid] = rec.index
            row = {n: i for i, n in enumerate(gang.names)}
            names = list(bound)
            nodes = np.array([bound[n] for n in names])
            reqs = gang.req[[row[n] for n in names]]
            np.add.at(used, nodes, reqs)
            np.add.at(pods, nodes, 1)
            charge(gang.queue, reqs.sum(axis=0))
            out["gangs_outside_one_rack"] += int(
                ref.domains_apart(nodes, seg) > 0)
        where = {p: n for h in rec.head for p, n in h[5].items()}
        gone = set()
        for commit in rec.commits:
            gang = gangs.get(commit.gang)
            queue = gang.queue if gang is not None else None
            out["unknown_evictions"] += commit.unknown_evictions
            for name, value in ref.victim_faults(
                    [(v.queue, v.preemptible) for v in commit.evicted],
                    queue).items():
                out[name] += value
            evicted = {v.pod for v in commit.evicted}
            if gang is not None:
                steps = _steps_of(rec.head, where, gone, queue, cap, ref)
                k, _dom = ref.first_seating_prefix(
                    ledger.capacity, used, pods, ledger.max_pods, seg,
                    [(nodes, reqs) for _p, nodes, reqs in steps], gang.req)
                want = {p for part, _n, _r in steps[:k or 0] for p in part}
                out["victims_outside_upstream_prefix"] += len(evicted ^ want)
                prefixes.append(len({v.job for v in commit.evicted}))
            # The commit, entered: its victims leave where they stood,
            # its gang and the victims it places again take their places.
            for v in commit.evicted:
                used[v.node] -= v.req
                pods[v.node] -= 1
            placed = dict(commit.replaced)
            stood = {v.pod: v for v in commit.evicted}
            for pod, node in commit.replaced:
                if pod in stood:
                    used[node] += stood[pod].req
                    pods[node] += 1
                    own.append(node == stood[pod].node)
                    where[pod] = node
            lost = {}
            for v in commit.evicted:
                if v.pod not in placed:
                    gone.add(v.pod)
                    lost.setdefault(v.queue, []).append(v.req)
            asks = np.zeros(3)
            if gang is not None and commit.nominated:
                row = {n: i for i, n in enumerate(gang.names)}
                nodes = np.array([n for _p, n in commit.nominated])
                rows = gang.req[[row[p] for p, _n in commit.nominated]]
                np.add.at(used, nodes, rows)
                np.add.at(pods, nodes, 1)
                asks = rows.sum(axis=0)
                out["gangs_outside_one_rack"] += int(
                    ref.domains_apart(nodes, seg) > 0
                    or len(nodes) != len(gang.names))
            kept = len(evicted) - len(placed)
            out["evictions_beyond_need"] += max(
                0, kept - len(commit.nominated))
            out["quota_faults"] += ref.quota_faults(
                deserved, ledger.queue_limit, queue_used, lost, queue, asks,
                parent)
            for q, took in lost.items():
                charge(q, -np.sum(took, axis=0))
            if queue is not None:
                charge(queue, asks)
            out["nodes_over_capacity"] += ref.nodes_over_capacity(
                ledger.capacity, used, pods, ledger.max_pods)
            writes.append(len(commit.evicted))
            stay.append(kept)
        out["victim_gangs_below_minimum"] += ref.gangs_left_below_minimum(
            rec.running_before, rec.deleted,
            {h[0]: h[3] for h in rec.head})
        out["nodes_over_capacity"] += ref.nodes_over_capacity(
            ledger.capacity, rec.used_after, rec.pods_after,
            ledger.max_pods)
        binds.append(sum(len(b) for b in rec.bound.values()))
        prescreens.append(sum(1 for span in rec.spans
                              if span[0] == "dispatch:scenario_prescreen"))
    elsewhere = len(own) - sum(own)
    last = records[-1].index
    due = [(r, g) for r in records for g in r.arrived
           if r.index + pending_max - 1 <= last]
    late = [g for r, g in due
            if bound_in.get(g.uid, last + 1) > r.index + pending_max - 1]
    out["gangs_not_bound"] = len(late)
    compared = {k: [out[k], LIMITS[k]] for k in LIMITS}
    cycles = max(1, len(records))
    return {
        "correct": all(v <= lim for v, lim in compared.values()),
        "compared": compared, "attempted": len(due),
        "bound_pods": sum(binds), "failed": len(late),
        "run": {"gangs": len(due),
                "gangs_bound": len(due) - len(late),
                "commits": len(writes),
                "evictions_written": sum(writes),
                "pods_placed_again_on_own_node": int(sum(own)),
                "pods_placed_again_elsewhere": int(elsewhere),
                "pods_that_stay_evicted": sum(stay),
                "pods_deleted": sum(sum(r.deleted.values())
                                    for r in records),
                "evictions_written_per_cycle": sum(writes) / cycles,
                "prefix_jobs_per_commit": sorted(set(prefixes)),
                "binds_per_cycle": sorted(set(binds)),
                "prescreens_per_cycle": sorted(set(prescreens)),
                "bind_cycles_after_arrival": sorted(
                    {bound_in[g.uid] - r.index for r, g in due
                     if g.uid in bound_in})}}
