"""Traffic and data generation: the fleet and its jobs, from the seed.

One general generator reads a configuration file (the deployment) and a
traffic file (the mix).  It builds the scheduler's input objects, which
are the system's interface, and beside them a plain numpy ledger of what a
client of the scheduler knows: every node's capacity, what is in use on
it, and every pod's resources and labels.  The comparison that decides
``correct`` reads that ledger and never the scheduler's own accounting.

Every seed gives the same amount of work: the same number of occupying
gangs, of the same size, on another set of racks and in another order of
queues.
"""

from __future__ import annotations

import numpy as np

from kai_scheduler_tpu.api import (ClusterInfo, NodeInfo, PodGroupInfo,
                                   PodInfo, PodStatus, QueueInfo,
                                   QueueQuota)
from kai_scheduler_tpu.api import resources as rs
from kai_scheduler_tpu.api.resources import ResourceRequirements


def res_vec(spec: dict) -> np.ndarray:
    """[cpu milli-cores, memory bytes, gpus] of a {cpu, memory, gpu} spec."""
    return rs.vec_from_spec(spec.get("cpu"), spec.get("memory"),
                            spec.get("gpu", 0))


def node_name(i: int) -> str:
    return f"node-{i:06d}"


class Ledger:
    """The client's view of the fleet, as arrays over the node axis."""

    def __init__(self, config: dict):
        shape = config["nodes"]
        n = int(shape["count"])
        self.n = n
        self.capacity = np.tile(res_vec(shape), (n, 1))       # [N,3]
        self.max_pods = int(shape.get("max_pods", 110))
        self.used = np.zeros((n, 3))
        self.pods = np.zeros(n, np.int64)
        # level -> [N] int domain id (contiguous blocks of nodes).
        self.levels = {lab["key"]: np.arange(n) // int(lab["block"])
                       for lab in shape.get("labels", [])}
        self.queue_parent: dict[str, str | None] = {}
        self.queue_limit: dict[str, np.ndarray] = {}
        self.queue_used: dict[str, np.ndarray] = {}

    def charge(self, queue: str, node_idx: np.ndarray, req: np.ndarray,
               sign: float = 1.0) -> None:
        """Add (or with sign -1 remove) pods [K] with requests [K,3]."""
        np.add.at(self.used, node_idx, sign * req)
        np.add.at(self.pods, node_idx, int(sign))
        total = sign * req.sum(axis=0)
        q = queue
        while q is not None:
            self.queue_used[q] = self.queue_used[q] + total
            q = self.queue_parent[q]


def build_queues(config: dict, ledger: Ledger) -> dict:
    """Departments with leaf queues under them, equal deserved shares and
    a limit of ``limit_factor`` times the deserved share."""
    tree = config["queues"]
    deps, leaves = int(tree["departments"]), int(tree["leaves_per_department"])
    factor = float(tree["limit_factor"])
    total = ledger.capacity.sum(axis=0)
    queues = {}

    def add(name, parent, share):
        deserved = total * share
        limit = deserved * factor
        queues[name] = QueueInfo(
            name, parent=parent,
            quota=QueueQuota.from_spec(deserved=deserved, limit=limit))
        ledger.queue_parent[name] = parent
        ledger.queue_limit[name] = limit
        ledger.queue_used[name] = np.zeros(3)

    for d in range(deps):
        dep = f"dep{d}"
        add(dep, None, 1.0 / deps)
        for q in range(leaves):
            leaf = f"{dep}-q{q}"
            add(leaf, dep, 1.0 / (deps * leaves))
            queues[dep].children.append(leaf)
    return queues


def leaf_queues(ledger: Ledger) -> list[str]:
    return sorted(q for q, p in ledger.queue_parent.items() if p is not None)


def build_fleet(config: dict, seed: int):
    """(ClusterInfo, Ledger) of the deployment at its starting occupancy."""
    rng = np.random.default_rng([int(seed), 1])
    ledger = Ledger(config)
    shape = config["nodes"]
    alloc = res_vec(shape)
    labels = shape.get("labels", [])
    nodes = {}
    for i in range(ledger.n):
        name = node_name(i)
        nodes[name] = NodeInfo(
            name, alloc,
            labels={lab["key"]: f"{lab['key']}{i // int(lab['block']):05d}"
                    for lab in labels},
            max_pods=ledger.max_pods)
    queues = build_queues(config, ledger)

    # Starting occupancy: whole-node pods of gangs that each fill a run of
    # whole racks, on racks and in queues drawn from the seed.
    occ = config["occupancy"]
    pod_req = res_vec(occ["pod"])
    gang_pods = int(occ["gang_pods"])
    n_gangs = int(round(ledger.n * float(occ["share"]) / gang_pods))
    starts = rng.permutation(ledger.n // gang_pods)[:n_gangs] * gang_pods
    leaves = leaf_queues(ledger)
    order = rng.permutation(len(leaves))
    podgroups = {}
    req = ResourceRequirements.from_spec(
        occ["pod"].get("cpu"), occ["pod"].get("memory"),
        occ["pod"].get("gpu", 0))
    for g, start in enumerate(starts.tolist()):
        queue = leaves[int(order[g % len(leaves)])]
        uid = f"occ-{g:03d}"
        pg = PodGroupInfo(uid, uid, queue_id=queue, min_available=gang_pods)
        idx = np.arange(start, start + gang_pods)
        for k, i in enumerate(idx.tolist()):
            pg.add_task(PodInfo(
                uid=f"{uid}-{k}", name=f"{uid}-{k}", res_req=req,
                status=PodStatus.RUNNING, node_name=node_name(i)))
        podgroups[uid] = pg
        ledger.charge(queue, idx, np.tile(pod_req, (len(idx), 1)))

    cluster = ClusterInfo(nodes, podgroups, queues,
                          topologies=config.get("topologies", {}),
                          now=1000.0)
    return cluster, ledger


class Gang:
    """One pending job of the mix, with what the client knows of it."""

    def __init__(self, uid: str, queue: str, names: list, req: np.ndarray,
                 topology: dict | None):
        self.uid = uid
        self.queue = queue
        self.names = names            # pod names, in submission order
        self.req = req                # [T,3]
        self.topology = topology      # {name, required, preferred} or None
        self.bound: dict[str, int] = {}    # pod name -> node index


def gang_size(traffic: dict) -> int:
    return sum(int(r["count"]) for r in traffic["gang"]["roles"])


def padded(t: int) -> int:
    """A kernel's padded axis (the exact kernel's tasks, the prescreen's
    prefixes and rows): the next power of two."""
    t_pad = 1
    while t_pad < t:
        t_pad *= 2
    return t_pad


def make_gang(traffic: dict, index: int, queue: str):
    """(PodGroupInfo, Gang) number ``index`` of the mix."""
    spec = traffic["gang"]
    uid = f"gang-{index:04d}"
    topo = spec.get("topology")
    pg = PodGroupInfo(
        uid, uid, queue_id=queue, min_available=gang_size(traffic),
        topology_name=topo["name"] if topo else None,
        required_topology_level=topo.get("required") if topo else None,
        preferred_topology_level=topo.get("preferred") if topo else None)
    names, reqs = [], []
    ordinal = 0
    for role in spec["roles"]:
        rr = ResourceRequirements.from_spec(
            role.get("cpu"), role.get("memory"), role.get("gpu", 0))
        vec = res_vec(role)
        for _ in range(int(role["count"])):
            name = f"{uid}-{role['name']}-{ordinal}"
            pg.add_task(PodInfo(uid=name, name=name, res_req=rr))
            names.append(name)
            reqs.append(vec)
            ordinal += 1
    return pg, Gang(uid, queue, names, np.array(reqs), topo)
