"""The generator ``pool_reclaim_gangs``: ``reclaim_gangs``' loop on a fleet
of node POOLS, for a gang that may use some of them only.

The loop is ``reclaim_gangs``' (its client imported from the file beside
this one, nothing of it edited): a gang a cycle that reclaims its GPUs and
is bound a cycle later.  What pools change belongs here:

- the fleet: contiguous blocks of nodes by index, a pool each, with the
  pool's labels and taints (``nodes.pools`` of the configuration), as
  NodePool CRs name their nodes.  A share of EACH pool's nodes, drawn from
  the seed inside the pool, is under the occupying queue's preemptible
  jobs, each pod pinned to its pool by ``nodeSelector`` and tolerating the
  pool's taints; ``occupancy.idle_nodes`` of one pool stand idle and the
  refill keeps off them; every other node is under a whole-node pod.
- the gang: every pod carries the traffic file's
  ``node_affinity_required`` and ``tolerations``.
- ``prime`` compiles the programs such a gang's cycle dispatches: every one
  of them takes a ``[T,N]`` bool ``task_node_mask``, the prescreen too
  (the scanned form, ``ops/scenario_batch.py``).
- ``compare`` reads ``reclaim_gangs``' eleven counts with the gang's row
  (the fewest evictions are those on nodes the gang may use, beside what is
  idle THERE) and adds three: ``pods_outside_pool``,
  ``evictions_on_excluded_nodes``, ``placements_not_reference``
  (``reference/pool_eviction.py``).
- the rooflines' byte counts by the WORK and not by the form that answers
  it today (``prefix_feasibility_bytes``).
- ``try_masked_reclaim``: the deployment on a small fleet before the run's
  is built.  A program whose prescreen declines for a static mask never
  binds a gang that needs more than ``max_scenarios_per_job`` steps, and
  stops there with status 1.
"""

from __future__ import annotations

import copy
import gc
import os
import time

import numpy as np

from benchmark.harness import cluster as gen
from benchmark.harness import loop, spec
from kai_scheduler_tpu.api import (ClusterInfo, NodeInfo, PodGroupInfo,
                                   PodInfo, PodStatus)

base = spec.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "reclaim_gangs.py"), "generator", "reclaim_gangs")


def pool_of_nodes(config: dict) -> np.ndarray:
    """[N] index into ``nodes.pools`` of every node: contiguous blocks by
    node index, in the file's order."""
    shape = config["nodes"]
    sizes = [int(p["nodes"]) for p in shape["pools"]]
    if sum(sizes) != int(shape["count"]):
        raise SystemExit(f"the pools hold {sum(sizes)} nodes and the fleet "
                         f"{shape['count']}")
    return np.repeat(np.arange(len(sizes)), sizes)


def gang_constraints(traffic: dict) -> tuple:
    """(required node-affinity terms, tolerations) of every pod of the
    mix's gang."""
    gang = traffic["gang"]
    return (gang.get("node_affinity_required") or [],
            set(gang.get("tolerations") or ()))


class Client(base.Client):
    """``reclaim_gangs``' client over pools."""

    def __init__(self, cell, seed: int, counters: tuple = ()):
        # ``reclaim_gangs.Client.__init__`` with the fleet's labels and
        # taints, and the seed's order drawn inside each pool.
        from kai_scheduler_tpu.scheduler import Scheduler
        self.cell = cell
        self.config = config = cell.config
        self.traffic = traffic = cell.traffic
        settings = loop.scheduler_config(config, cell.config_path)
        rng = np.random.default_rng([int(seed), 1])
        self.ledger = ledger = gen.Ledger(config)
        shape = config["nodes"]
        alloc = gen.res_vec(shape)
        self.pools = shape["pools"]
        self.pool_of = pool_of_nodes(config)
        # What the client gave every node, as plain dicts and sets: the
        # comparison reads these and never the scheduler's tables.
        ledger.node_labels = [dict(self.pools[p]["labels"])
                              for p in self.pool_of]
        ledger.node_taints = [set(self.pools[p]["taints"])
                              for p in self.pool_of]
        self.node_names = [gen.node_name(i) for i in range(ledger.n)]
        nodes = {name: NodeInfo(name, alloc, labels=ledger.node_labels[i],
                                taints=ledger.node_taints[i],
                                max_pods=ledger.max_pods)
                 for i, name in enumerate(self.node_names)}
        queues = gen.build_queues(config, ledger)
        leaves = gen.leaf_queues(ledger)
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
        self.occ_rr = base._requirements(occ["pod"])
        self.jobs = {}
        self.pod_job = {}
        self.next_job = 0
        self._order_nodes(rng)
        gc.disable()
        try:
            self._hold_whole_nodes([leaves[int(i)] for i in order])
            self._fill()
        finally:
            gc.enable()
        self.sched = Scheduler(lambda: self.cluster, settings)
        self.lifetime = int(traffic["lifetime_cycles"])
        self.node_index = {name: i for i, name in enumerate(self.node_names)}
        self.pending = []
        self.running = []
        self.gangs = []
        self.records = []
        self.counters = tuple(counters)

    def _order_nodes(self, rng) -> None:
        """``node_order``: the victims' nodes first (the occupancy's share
        of each pool, drawn inside the pool and then mixed, so that the
        victims the survey lists first lie in every pool), then the
        whole-node pods'.  The idle nodes are in neither part."""
        occ, n = self.config["occupancy"], self.ledger.n
        share = float(occ["preemptible_nodes_share"])
        idle = occ["idle_nodes"]
        victims, whole = [], []
        self.idle_nodes = np.zeros(0, np.int64)
        for p, pool in enumerate(self.pools):
            inside = rng.permutation(np.flatnonzero(self.pool_of == p))
            cut = int(round(len(inside) * share))
            victims.append(inside[:cut])
            rest = inside[cut:]
            if pool["name"] == idle["pool"]:
                self.idle_nodes = rest[:int(idle["count"])]
                rest = rest[int(idle["count"]):]
            whole.append(rest)
        victims = rng.permutation(np.concatenate(victims))
        if len(victims) != int(round(n * share)) \
                or len(self.idle_nodes) != int(idle["count"]):
            raise SystemExit(
                f"{self.cell.config_path}: a share of {share} of each pool "
                f"is {len(victims)} nodes and of the fleet "
                f"{int(round(n * share))}; {len(self.idle_nodes)} of "
                f"{idle['count']} idle nodes in pool {idle['pool']!r}")
        self.node_order = np.concatenate(
            [victims, rng.permutation(np.concatenate(whole))])
        self.node_rank = np.full(n, n, np.int64)
        self.node_rank[self.node_order] = np.arange(len(self.node_order))
        # What the first fill may take: everything but the idle nodes.
        self.fillable = self.ledger.capacity.copy()
        self.fillable[self.idle_nodes] = 0.0

    def _hold_whole_nodes(self, leaves: list) -> None:
        # ``spread_reclaim_gangs``' order: the occupier's department and
        # the reclaimer's get their whole-node pods first, so that both
        # ask the same sums on every seed.
        parent = self.ledger.queue_parent
        first = [parent[self.occupier], parent[self.reclaimer]]
        super()._hold_whole_nodes(sorted(
            leaves, key=lambda q: (first + [parent[q]]).index(parent[q])))

    def _fill(self, left=None) -> None:
        """The refill keeps off the idle nodes, at the start and after a
        gang that sat there completes: the occupier is at its limit."""
        super()._fill(self.fillable if left is None
                      else np.minimum(left, self.fillable))

    def _book(self, job) -> None:
        """A preemptible job's pods are made for the pool of their node:
        pinned to it by ``nodeSelector`` and tolerating its taints."""
        pool = self.pools[self.pool_of[next(iter(job.pods.values()))]]
        job.selector = dict(pool["labels"]) if job.preemptible else {}
        job.tolerations = set(pool["taints"]) if job.preemptible else set()
        super()._book(job)

    def _show(self, job) -> None:
        # ``reclaim_gangs.Client._show`` with the pods' constraints.
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
                           node_name=self.node_names[node],
                           node_selector=dict(job.selector),
                           tolerations=set(job.tolerations))
            pg.add_task(task)
            cluster.nodes[task.node_name].add_task(task)
        cluster.podgroups[job.uid] = pg

    def _before(self):
        gang = super()._before()
        terms, tolerations = gang_constraints(self.traffic)
        for task in self.pending[-1][1].pods.values():
            task.node_affinity_required = copy.deepcopy(terms)
            task.tolerations = set(tolerations)
        return gang

    def _settle(self, rec) -> None:
        """Before ``reclaim_gangs`` reads the cycle back: where the cycle
        nominated a place for a pod (the gang's onto what its victims
        release, a victim's where it would run again), with the
        constraints the client put on that pod: (pod, node, selector,
        affinity terms, tolerations)."""
        terms, tolerations = gang_constraints(self.traffic)
        member = {name for gang, _pg in self.pending for name in gang.names}
        rec.nominated = []
        for pod, node in self.sched.cache.pipelined:
            job = self.jobs.get(self.pod_job.get(pod))
            if pod in member:
                rec.nominated.append(
                    (pod, self.node_index[node], {}, terms, tolerations))
            elif job is not None:
                rec.nominated.append(
                    (pod, self.node_index[node], job.selector, [],
                     job.tolerations))
        super()._settle(rec)


# -- the trial before the fleet ----------------------------------------------
# 512 nodes of the three pools (3/8, 1/2, 1/8), a quarter of each under
# victims, 8 A100 nodes idle (64 GPUs, the gang's size), and a PyTorchJob
# of 64 GPUs: two pods a step, so the gang fits at step 32, twice the 16
# scenarios a solver simulates for one job.
TRIAL = {"nodes": 512, "pools": (192, 256, 64), "idle": 8, "whole": 32,
         "gang": 64, "victims": 64}
TRIAL_CYCLES = 3


def cut_cell(cell, nodes: int, pools: tuple, idle: int, whole: int,
             gang: int, victims: int, share: float | None = None,
             departments: int | None = None, leaves: int | None = None):
    """A copy of the cell with its fleet, gang and solver caps cut: the
    pools keep their labels and taints, the gang keeps its one master and
    its constraints, the workers make up ``gang``."""
    cut = copy.copy(cell)
    cut.config = copy.deepcopy(cell.config)
    cut.traffic = copy.deepcopy(cell.traffic)
    cut.config["nodes"]["count"] = nodes
    for pool, size in zip(cut.config["nodes"]["pools"], pools):
        pool["nodes"] = size
    occ = cut.config["occupancy"]
    occ["idle_nodes"]["count"] = idle
    occ["whole_node"]["gang_pods"] = whole
    if share is not None:
        occ["preemptible_nodes_share"] = share
    if departments is not None:
        cut.config["queues"].update(departments=departments,
                                    leaves_per_department=leaves)
    cut.config["scheduler"].update(max_victims_considered=victims,
                                   scenario_prescreen_max=victims)
    master, worker = cut.traffic["gang"]["roles"]
    worker["count"] = gang - int(master["count"])
    return cut


def try_masked_reclaim(cell, seed: int) -> dict:
    """Three cycles of the deployment on 512 nodes of its three pools,
    through the cell's own ``compare``, before the run's fleet is built: a
    gang with a required node affinity that arrives inside its quota
    while the nodes it may use are full has its GPUs reclaimed THERE in
    the cycle it arrives in, and is bound in the next.

    The gang needs 32 steps of victims.  A program that declines the
    scenario prescreen for any hard mask (as this repo's did before PR 44)
    simulates ``max_scenarios_per_job`` (16) prefixes, gives up, and the
    gang is pending for ever: it stops here with status 1, soon."""
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
            f"with a required node affinity, whose fit needs "
            f"{TRIAL['gang'] // 2} steps of victims, is not reclaimed for "
            f"on the nodes it may use in its cycle and bound in the next; "
            f"compared (value, limit): "
            f"{ {k: v for k, v in verdict['compared'].items() if v[0]} }")
    return {"seconds": round(time.perf_counter() - t0, 3),
            "nodes": TRIAL["nodes"], "gang": TRIAL["gang"],
            "evictions_per_cycle": verdict["run"]["evictions_per_cycle"]}


def build(cell, seed: int, counters: tuple = ()) -> Client:
    trial = try_masked_reclaim(cell, seed)
    client = Client(cell, seed, counters)
    client.trial = trial
    return client


# -- the kernels of the cycle -------------------------------------------------
def file_shape(cell) -> dict:
    """``reclaim_gangs``' shapes (after the victim filter the solver still
    finds more than ``max_victims_considered`` jobs on the nodes the gang
    may use), and the exact scan's real steps a cycle: the two confirms'
    and the bind's of last cycle's gang, as ``spread_reclaim_gangs``
    counts them.  Every call carries a ``[T,N]`` mask."""
    shape = base.file_shape(cell)
    shape["scan_steps"] = shape["confirm_steps"] + shape["t"]
    # The gang's runs of identical adjacent rows: what a prescreen that
    # lands a run a step would take (``prefix_feasibility_bytes``).
    shape["runs"] = len(cell.traffic["gang"]["roles"])
    return shape


def _mask(sds, shape: dict, t_pad: int):
    return sds((t_pad, shape["nodes"]), bool)


def _lower(sds, shape: dict):
    """``batch_prefix_feasibility`` lowered as ``_prefix_prescreen``
    dispatches it for a gang under a static mask."""
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
        num_prefixes=shape["prefixes"], task_node_mask=_mask(sds, shape, t),
        gpu_strategy=BINPACK, cpu_strategy=BINPACK)


def _lower_scan(sds, shape: dict, t_pad: int, j_pad: int,
                pipeline_only: bool):
    """The exact scan lowered with its ``[T,N]`` mask: as the solver's
    confirm dispatches it (several jobs, pipeline only), and as the
    allocate action does for the gang's one chunk."""
    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
    from kai_scheduler_tpu.ops.scoring import BINPACK
    f, i = np.float64, np.int32
    return allocate_jobs_kernel.lower(
        *base._node_tables(sds, shape),
        sds((t_pad, shape["resources"]), f), sds((t_pad,), i),
        sds((t_pad, shape["selector_cols"]), i),
        sds((t_pad, shape["toleration_cols"]), i), sds((j_pad,), bool), None,
        task_node_mask=_mask(sds, shape, t_pad), task_anti_domain=None,
        task_aff_domain=None, job_extra_scores=None, job_node_mask=None,
        gpu_strategy=BINPACK, cpu_strategy=BINPACK,
        allow_pipeline=True, pipeline_only=pipeline_only)


def prime(client: Client, watch: loop.CompileWatch) -> dict:
    """Compile the programs of the cycle, each at the shape the cycle
    dispatches it, before the first guarded dispatch (the device guard
    gives a dispatch 30 s, compile included): the masked prescreen, the
    masked exact scan of the allocate action's one chunk (the bind, and
    the attempt that finds the gang's nodes full), and the masked exact
    scan of the solver's confirm in its two shapes."""
    shape = file_shape(client.cell)
    sds = loop.device_operand
    lowerings = {
        "batch_prefix_feasibility": lambda: _lower(sds, shape),
        f"allocate_jobs_kernel[{shape['t_pad']},2] bind":
        lambda: _lower_scan(sds, shape, shape["t_pad"], 2, False)}
    for t_pad, j_pad in shape["confirms"]:
        lowerings[f"allocate_jobs_kernel[{t_pad},{j_pad}]"] = \
            lambda t=t_pad, j=j_pad: _lower_scan(sds, shape, t, j, True)
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


def prefix_feasibility_bytes(prefixes: int, nodes: int, runs: int,
                             resources: int = 3) -> float:
    """Bytes one prescreen call must move at the least, by the WORK and
    not by the form that answers it: it writes one f32 releasing pool
    ``[K,N,R]``, the K states of the fleet it scores, and reads it again
    once for each of the gang's ``runs`` of identical pods
    (``consolidation_gangs``' rule), with the run's ``[N]`` bool mask row
    beside it.  NOT the scanned form's bytes a pod: the count does not
    follow ``t_pad``, so that a prescreen that lands a run a step under a
    mask row is read on the yardstick the scan was, and can never pass
    100 % of it."""
    return (1.0 + runs) * prefixes * nodes * resources * 4 \
        + float(runs) * nodes


def exact_scan_bytes(steps: int, nodes: int, resources: int = 3,
                     label_cols: int = 0, taint_cols: int = 0) -> float:
    """``reclaim_gangs``' count (a step reads allocatable, idle and
    releasing [N,R] f32, pod room [N] and the label and taint tables) and
    the step's ``[N]`` bool row of the mask."""
    return base.exact_scan_bytes(steps, nodes, resources, label_cols,
                                 taint_cols) + float(steps) * nodes


def kernel_shapes(client: Client) -> dict:
    shape = client.primed
    shapes = base.kernel_shapes(client)
    shapes["prefix_feasibility_bytes"]["runs"] = shape["runs"]
    shapes["exact_scan_bytes"]["steps"] = shape["scan_steps"]
    return shapes


def reckon(cell) -> dict:
    """What the cycle holds on the device, from the files.  Under a mask
    the prescreen program is the exact scan vmapped over the prefixes: its
    carries are ``[K,N,R]`` f32 arrays (the scattered releases, their
    running sum, the pools, and the scan's idle, releasing and checkpoint
    states), each prefix another state of the fleet, each changed by every
    pod of the gang and read for the verdict.  The ``[T,N]`` bool masks
    are the client's: one a call, the largest the confirm's."""
    out = base.reckon(cell)
    shape = file_shape(cell)
    mask = max(t for t, _j in shape["confirms"]) * shape["nodes"]
    out["bytes"] += float(mask)
    out["what"] += (f"; the vmapped exact scan's carries (masked), and a "
                    f"[T,N] bool mask a call, {mask:,} bytes the largest")
    return out


def compile_for(cell, sds):
    return _lower(sds, file_shape(cell)).compile()


# -- the comparison ---------------------------------------------------------
FILTERED = 'reclaim_victims_filtered_total{reason="excluded-node"}'
LIMITS = {**base.LIMITS, "pods_outside_pool": 0,
          "evictions_on_excluded_nodes": 0, "placements_not_reference": 0}


class _ReadWithRow:
    """The reference as ``reclaim_gangs.compare`` calls it, with the fewest
    evictions read on the nodes the gang may use."""

    def __init__(self, ref, admits):
        self._ref, self._admits = ref, admits

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def fewest_evictions(self, *state):
        return self._ref.fewest_evictions(*state, self._admits)


def compare(records, ledger, cell) -> dict:
    """``reclaim_gangs``' verdict on the window's ``records`` read with the
    gang's row, and the three counts of the pools."""
    ref = cell.reference
    terms, tolerations = gang_constraints(cell.traffic)
    rows = {}

    def row(selector: dict, terms: list, tolerations) -> np.ndarray:
        key = (tuple(sorted(selector.items())), repr(terms),
               tuple(sorted(tolerations)))
        if key not in rows:
            rows[key] = ref.admitted(ledger.node_labels, ledger.node_taints,
                                     selector, terms, tolerations)
        return rows[key]

    # Every pod of the gang carries the same constraints: one row.
    gang_row = row({}, terms, tolerations)
    shim = copy.copy(cell)
    shim.reference = _ReadWithRow(ref, gang_row)
    out = base.compare(records, ledger, shim)
    outside = excluded = wrong = checked = 0
    for rec in records:
        for _pod, node, selector, pod_terms, pod_tolerations in getattr(
                rec, "nominated", ()):
            outside += ref.pods_outside(
                [node], row(selector, pod_terms, pod_tolerations))
        if rec.evicted:
            excluded += ref.evictions_on_excluded_nodes(
                [v.node for v in rec.evicted], gang_row)
        used, pods = rec.used_before.copy(), rec.pods_before.copy()
        for gang in rec.pending:
            bound = rec.bound.get(gang.uid, {})
            if not bound:
                continue
            row_of = {n: i for i, n in enumerate(gang.names)}
            names = list(bound)
            nodes = np.array([bound[n] for n in names])
            strayed = ref.pods_outside(nodes, gang_row)
            outside += strayed
            # A gang with a pod outside its pool is that count's: there is
            # no order among the admitted nodes to hold it to.
            if len(bound) == len(gang.names) and not strayed:
                in_order = np.array([bound[n] for n in gang.names])
                wrong += ref.placements_not_reference(
                    ledger.capacity, used, pods, ledger.max_pods, gang.req,
                    in_order, gang_row)
                checked += len(in_order)
            np.add.at(used, nodes, gang.req[[row_of[n] for n in names]])
            np.add.at(pods, nodes, 1)
    compared = out["compared"]
    compared["pods_outside_pool"] = [outside, LIMITS["pods_outside_pool"]]
    compared["evictions_on_excluded_nodes"] = [
        excluded, LIMITS["evictions_on_excluded_nodes"]]
    compared["placements_not_reference"] = [
        wrong, LIMITS["placements_not_reference"]]
    out["correct"] = all(v <= lim for v, lim in compared.values())
    out["run"]["placements_checked"] = checked
    out["run"]["nodes_the_gang_may_use"] = int(gang_row.sum())
    out["run"]["victims_filtered_per_cycle"] = sorted({
        int(rec.counters[FILTERED]) for rec in records
        if FILTERED in rec.counters})
    out["run"]["gang_roles"] = [
        {"name": r["name"], "count": int(r["count"])}
        for r in cell.traffic["gang"]["roles"]]
    return out

