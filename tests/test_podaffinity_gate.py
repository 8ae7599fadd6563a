"""The pod-affinity gate asks the snapshot layer which pods carry an
inter-pod term (``Session.term_carriers``) where it listed every running
pod (PR 41).  The walk-based ``hard_masks`` and ``wave_filter`` of the
parent are kept here as the plain reference: on every seeded fleet, and
again after an evict, a rollback and a commit inside one session, the
gate answers ``None`` where the reference does and the same ``[T,N]``
mask bit for bit where it gives a mask.  Beside it: what the walk counter
reads, and that the fact is the same whichever way the snapshot layer
came by it (a full pack, a patched pack, the ``ClusterCache`` paths)."""

import numpy as np
import pytest

from kai_scheduler_tpu.api import AffinityTerm, PodStatus
from kai_scheduler_tpu.api.snapshot import survey_pods
from kai_scheduler_tpu.framework import propose
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.framework.session import InMemoryCache, Session
from kai_scheduler_tpu.framework.statement import Statement
from kai_scheduler_tpu.ops.scoring import BINPACK
from kai_scheduler_tpu.plugins.podaffinity import (PodAffinityPlugin,
                                                   _same_term)
from kai_scheduler_tpu.scheduler import Scheduler
from kai_scheduler_tpu.utils.metrics import METRICS
from kai_scheduler_tpu.utils.tracing import TRACER

from tests.fixtures import build_cluster, build_session

WALKS = "podaffinity_pod_walks_total"
APPS = ("web", "db", "cache")


# ---------------------------------------------------------------------------
# The parent's gate, as it stood before PR 41: the plain reference
# ---------------------------------------------------------------------------

def reference_active_pods(ssn) -> list:
    out = []
    for pg in ssn.cluster.podgroups.values():
        for task in pg.pods.values():
            if not task.is_active_allocated() or not task.node_name:
                continue
            idx = ssn.node_index(task.node_name)
            if idx < 0:
                continue
            out.append((task.labels, task.namespace, idx,
                        task.anti_affinity_terms, task.job_id))
    return out


def reference_hard_masks(plugin, tasks):
    ssn = plugin.ssn
    has_own_terms = any(t.affinity_terms or t.anti_affinity_terms
                        for t in tasks)
    pods = reference_active_pods(ssn)
    if not has_own_terms and not any(anti for _l, _n, _i, anti, _j in pods):
        return None
    n = ssn.node_idle.shape[0]
    out = np.ones((len(tasks), n), bool)
    touched = False
    sym_repellers = [(labels, ns, idx, term)
                     for labels, ns, idx, anti, _j in pods for term in anti]
    selected = plugin._selected_in_gang_affinity(tasks)
    for i, task in enumerate(tasks):
        row = out[i]
        for term in task.affinity_terms:
            if selected is not None and _same_term(term, selected):
                continue
            if plugin._in_gang(term, tasks):
                mask = plugin._term_mask(term, pods)
                if not mask.any() and term.matches(task.labels,
                                                   task.namespace):
                    continue
                row &= mask
                touched = True
                continue
            row &= plugin._term_mask(term, pods)
            touched = True
        for term in task.anti_affinity_terms:
            row &= ~plugin._term_mask(term, pods)
            touched = True
        for _labels, _ns, idx, term in sym_repellers:
            if term.matches(task.labels, task.namespace):
                dom, _n_dom = plugin._domains(term.topology_key)
                if dom[idx] >= 0:
                    row &= ~(dom == dom[idx])
                    touched = True
    return out if touched else None


def reference_wave_filter(ssn):
    if ssn.gpu_strategy != BINPACK or ssn.cpu_strategy != BINPACK:
        return None
    repeller_terms = [
        term
        for pg in ssn.cluster.podgroups.values()
        for t in pg.pods.values() if t.is_active_allocated()
        for term in t.anti_affinity_terms]

    def takes(pg, tasks) -> bool:
        host_side = (
            not tasks
            or any(t.is_fractional or t.resource_claims
                   or t.res_req.mig_resources for t in tasks)
            or any(ps.has_own_topology_constraint()
                   for ps in pg.pod_sets.values())
            or pg.required_topology_level or pg.preferred_topology_level
            or any(t.status == PodStatus.PIPELINED
                   for t in pg.pods.values())
            or any(t.nominated_node or t.pod_affinity_peers
                   or t.pod_anti_affinity_peers for t in tasks)
            or any(t.affinity_terms or t.anti_affinity_terms
                   or t.preferred_affinity_terms
                   or t.preferred_anti_affinity_terms
                   or t.node_affinity_required or t.node_affinity_preferred
                   or t.host_ports or t.pvc_names
                   or any(term.matches(t.labels, t.namespace)
                          for term in repeller_terms) for t in tasks))
        return not host_side
    return takes


# ---------------------------------------------------------------------------
# Seeded fleets
# ---------------------------------------------------------------------------

N_NODES = 12


def _term(app, key, weight=1.0):
    return {"selector": {"app": app}, "topology_key": key, "weight": weight}


def fleet(kind: str, seed: int) -> dict:
    """Twelve nodes in three zones under ten running two-pod jobs with
    ``app`` labels, three pending gangs, and what ``kind`` adds: carriers
    of one status or another, a chunk with terms of its own, a repeller
    that matches a chunk."""
    rng = np.random.default_rng(4100 + seed)
    nodes = {f"n{i:02d}": {"gpu": 8, "labels": {"zone": f"z{i % 3}"}}
             for i in range(N_NODES)}

    def somewhere():
        return f"n{int(rng.integers(N_NODES)):02d}"

    def app():
        return APPS[int(rng.integers(len(APPS)))]

    def key():
        return ("zone", "kubernetes.io/hostname")[int(rng.integers(2))]

    jobs = {}
    for j in range(10):
        jobs[f"run{j}"] = {"queue": "q", "tasks": [
            {"gpu": 1, "status": "RUNNING", "node": somewhere(),
             "labels": {"app": app()}} for _ in range(2)]}
    for j in range(3):
        jobs[f"in{j}"] = {"queue": "q", "min_available": 2, "tasks": [
            {"gpu": 1, "labels": {"app": APPS[j]}} for _ in range(2)]}

    def carriers(**placed):
        for j in range(3):
            jobs[f"guard{j}"] = {"queue": "q", "tasks": [
                {"gpu": 1, "labels": {"app": "guard"},
                 "anti_affinity_terms": [_term(app(), key())], **placed}]}

    if kind == "no-carrier":
        pass
    elif kind == "carriers-running":
        carriers(status="RUNNING", node=somewhere())
        jobs["guard1"]["tasks"][0]["node"] = somewhere()
    elif kind == "carriers-pending":
        carriers()
    elif kind == "carriers-releasing":
        carriers(status="RELEASING", node=somewhere())
    elif kind == "carriers-off-snapshot":
        carriers(status="RUNNING", node="gone-node")
    elif kind == "carriers-preferred-only":
        for j in range(3):
            jobs[f"soft{j}"] = {"queue": "q", "tasks": [
                {"gpu": 1, "status": "RUNNING", "node": somewhere(),
                 "labels": {"app": "soft"},
                 "preferred_anti_affinity_terms": [_term(app(), key(), 2.0)],
                 "preferred_affinity_terms": [_term(app(), key())]}]}
    elif kind == "chunk-own-terms":
        for task in jobs["in0"]["tasks"]:
            task["affinity_terms"] = [_term("db", "zone")]
            task["preferred_affinity_terms"] = [_term("cache", "zone", 3.0)]
        jobs["in1"]["tasks"][0]["anti_affinity_terms"] = [
            _term("web", "kubernetes.io/hostname")]
        jobs["in2"]["tasks"][1]["preferred_anti_affinity_terms"] = [
            _term("db", "zone")]
    elif kind == "symmetric-repeller":
        for j, node in enumerate(("n01", "n05")):
            jobs[f"guard{j}"] = {"queue": "q", "tasks": [
                {"gpu": 1, "status": "RUNNING", "node": node,
                 "labels": {"app": "guard"},
                 "anti_affinity_terms": [_term(APPS[j], "zone"),
                                         _term("cache", key())]}]}
    elif kind == "mixed":
        carriers(status="RUNNING", node=somewhere())
        jobs["guard2"]["tasks"][0].update(status="PENDING", node="")
        jobs["in0"]["tasks"][0]["anti_affinity_terms"] = [
            _term("guard", "zone")]
        jobs["in1"]["tasks"][1]["affinity_terms"] = [_term("web", "zone")]
    else:
        raise ValueError(kind)
    return {"nodes": nodes, "queues": {"q": {}}, "jobs": jobs}


KINDS = ("no-carrier", "carriers-running", "carriers-pending",
         "carriers-releasing", "carriers-off-snapshot",
         "carriers-preferred-only", "chunk-own-terms", "symmetric-repeller",
         "mixed")
# Where the reference gives a mask to some pending chunk that has no term
# of its own: there the comparison is of the carriers' masks, and not of
# None with None.
REPELLED = {"carriers-running", "symmetric-repeller", "mixed"}


def plugin_of(ssn) -> PodAffinityPlugin:
    (plugin,) = [p for p in ssn.plugins if isinstance(p, PodAffinityPlugin)]
    return plugin


def pending_chunks(ssn) -> list:
    """``(job, tasks)``: each job's pending pods as one chunk, and each
    pod alone as the host allocation path asks."""
    out = []
    for pg in ssn.cluster.podgroups.values():
        tasks = [t for t in pg.pods.values()
                 if t.status == PodStatus.PENDING]
        if tasks:
            out.append((pg, tasks))
            out.extend((pg, [t]) for t in tasks)
    return out


def assert_gate_equals_reference(ssn) -> int:
    """The number of chunks with no term of their own that the reference
    gave a mask."""
    plugin = plugin_of(ssn)
    takes, want_takes = propose.wave_filter(ssn), reference_wave_filter(ssn)
    masks = 0
    for pg, tasks in pending_chunks(ssn):
        want = reference_hard_masks(plugin, tasks)
        got = plugin.hard_masks(tasks)
        assert (got is None) == (want is None), (pg.uid, len(tasks))
        if want is not None:
            masks += not any(t.affinity_terms or t.anti_affinity_terms
                             for t in tasks)
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(got, want), (pg.uid, len(tasks))
        assert takes(pg, tasks) == want_takes(pg, tasks), pg.uid
    return masks


def running_pods(ssn) -> list:
    """The running pods on snapshot nodes, carriers first."""
    pods = [t for pg in ssn.cluster.podgroups.values()
            for t in pg.pods.values()
            if t.status == PodStatus.RUNNING and t.node_name
            in ssn.cluster.nodes]
    return sorted(pods, key=lambda t: (not t.anti_affinity_terms, t.uid))


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("kind", KINDS)
def test_gate_equals_walk_reference_through_a_session(kind, seed):
    ssn = build_session(fleet(kind, seed))
    masks = assert_gate_equals_reference(ssn)
    assert (masks > 0) == (kind in REPELLED)

    # An evict: a running pod (a carrier first, where there is one) turns
    # RELEASING and stops repelling.
    victims = running_pods(ssn)[:2]
    stmt = Statement(ssn)
    for victim in victims:
        stmt.evict(victim)
    assert all(v.status == PodStatus.RELEASING for v in victims)
    assert_gate_equals_reference(ssn)

    # A rollback: they repel again.
    stmt.rollback()
    assert all(v.status == PodStatus.RUNNING for v in victims)
    assert assert_gate_equals_reference(ssn) == masks

    # A commit: one evicted for good, and a pending pod (a carrier first)
    # placed inside the session, which repels from then on.
    pending = sorted(
        (t for pg in ssn.cluster.podgroups.values()
         for t in pg.pods.values() if t.status == PodStatus.PENDING),
        key=lambda t: (not t.anti_affinity_terms, t.uid))
    stmt = Statement(ssn)
    stmt.evict(victims[0])
    stmt.allocate(pending[0], "n07")
    assert_gate_equals_reference(ssn)
    stmt.commit()
    assert pending[0].is_active_allocated()
    assert_gate_equals_reference(ssn)


# ---------------------------------------------------------------------------
# What the gate says of itself: the counter and the span attribute
# ---------------------------------------------------------------------------

def walks() -> float:
    return METRICS.counters[WALKS]


class Loop:
    """One persistent ClusterInfo under one Scheduler (``HostArena``), a
    gang arriving a cycle, as the benchmark's cells drive it."""

    def __init__(self, spec):
        self.cluster = build_cluster(spec)
        self.sched = Scheduler(lambda: self.cluster, SchedulerConfig())
        self.seq = 0

    def arrive(self, **pod):
        from kai_scheduler_tpu.api import PodGroupInfo, PodInfo
        from kai_scheduler_tpu.api.resources import ResourceRequirements
        self.seq += 1
        uid = f"new{self.seq}"
        pg = PodGroupInfo(uid, uid, queue_id="q", min_available=2)
        for k in range(2):
            pg.add_task(PodInfo(
                uid=f"{uid}-{k}", name=f"{uid}-{k}",
                res_req=ResourceRequirements.from_spec("1", "1Gi", 1),
                **pod))
        self.cluster.podgroups[uid] = pg
        self.cluster.invalidate_aggregates()
        return pg

    def cycle(self):
        TRACER.begin_cycle(self.seq)
        try:
            ssn = self.sched.run_once()
            forms = [sp.attrs.get("affinity")
                     for sp in TRACER.get_trace().spans
                     if sp.name == "propose:operands"]
        finally:
            TRACER.end_cycle()
        for pg in ssn.cluster.podgroups.values():
            for t in pg.pods.values():
                if t.status == PodStatus.BINDING:
                    pg.update_task_status(t, PodStatus.RUNNING)
        return ssn, forms


def test_a_fleet_with_no_term_never_walks_its_pods():
    loop = Loop(fleet("no-carrier", 1))
    METRICS.reset()
    for _ in range(3):
        loop.arrive(labels={"app": "web"})
        ssn, forms = loop.cycle()
        assert ssn.term_carriers == []
        assert forms and set(forms) == {"none"}
    assert loop.cluster.podgroups["new3"].pods["new3-0"].node_name
    # The counter is there to be read as 0: the benchmark's
    # ``affinity_pod_walks`` leaves out a counter that is not.
    assert walks() == 0


def test_carriers_answer_the_gate_without_a_walk():
    loop = Loop(fleet("symmetric-repeller", 1))
    METRICS.reset()
    loop.arrive(labels={"app": "web"})
    ssn, forms = loop.cycle()
    assert [t.uid for t in ssn.term_carriers] == ["guard0-0", "guard1-0"]
    assert forms and set(forms) == {"carriers"}
    assert walks() == 0
    # guard0 on n01 (zone z1) repels app=web from its zone.
    zones = {name: node.labels["zone"]
             for name, node in loop.cluster.nodes.items()}
    placed = [t.node_name for t in
              loop.cluster.podgroups["new1"].pods.values()]
    assert all(placed) and all(zones[n] != "z1" for n in placed)


def test_a_chunk_with_its_own_terms_walks_once_a_tick():
    ssn = build_session(fleet("chunk-own-terms", 1))
    plugin = plugin_of(ssn)
    chunk = list(ssn.cluster.podgroups["in0"].pods.values())
    plain = list(ssn.cluster.podgroups["in2"].pods.values())[:1]
    start = walks()
    assert plugin.hard_masks(plain) is None
    assert walks() == start
    assert plugin.hard_masks(chunk) is not None
    plugin.hard_masks(chunk)
    plugin.affinity_domains(chunk)
    plugin.extra_scores(chunk)
    assert walks() == start + 1
    stmt = Statement(ssn)
    stmt.evict(running_pods(ssn)[0])
    plugin.hard_masks(chunk)
    plugin.hard_masks(chunk)
    assert walks() == start + 2
    stmt.rollback()
    plugin.hard_masks(chunk)
    assert walks() == start + 3


def test_the_span_says_walked_for_a_chunk_with_its_own_terms():
    loop = Loop(fleet("no-carrier", 2))
    METRICS.reset()
    loop.arrive(labels={"app": "web"},
                anti_affinity_terms=[AffinityTerm(
                    {"app": "db"}, "zone", namespaces=["default"])])
    ssn, forms = loop.cycle()
    assert "walked" in forms
    assert [t.uid for t in ssn.term_carriers] == ["new1-0", "new1-1"]
    assert walks() >= 1


# ---------------------------------------------------------------------------
# The fact, however the snapshot layer came by it
# ---------------------------------------------------------------------------

def carrier_uids(ssn) -> list:
    return sorted(t.uid for t in ssn.term_carriers)


@pytest.mark.parametrize("kind", ["no-carrier", "mixed",
                                  "carriers-preferred-only"])
def test_full_pack_patched_pack_and_bare_session_agree(kind):
    spec = fleet(kind, 3)
    want = sorted(
        t.get("uid", f"{name}-{i}")
        for name, job in spec["jobs"].items()
        for i, t in enumerate(job["tasks"])
        if any(t.get(k) for k in (
            "affinity_terms", "anti_affinity_terms",
            "preferred_affinity_terms", "preferred_anti_affinity_terms")))
    assert bool(want) == (kind != "no-carrier")
    loop = Loop(spec)
    full, _ = loop.cycle()
    assert full.pack_stats["full_rebuild"]
    loop.arrive(labels={"app": "db"})
    patched, _ = loop.cycle()
    assert not patched.pack_stats["full_rebuild"]
    bare = Session(loop.cluster, SchedulerConfig(), InMemoryCache())
    assert carrier_uids(full) == carrier_uids(patched) == want
    assert carrier_uids(bare) == want
    assert survey_pods(loop.cluster)[1] == bare.term_carriers
    # The arena's own list, not a walk of the session's.
    assert patched.term_carriers is loop.sched.host_arena.term_carriers


def _anti_affinity(app):
    return {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"app": app}},
            "topologyKey": "zone"}]}}


def test_cluster_cache_paths_and_the_host_arena_agree(monkeypatch):
    from kai_scheduler_tpu.controllers import InMemoryKubeAPI
    from kai_scheduler_tpu.controllers.cache_builder import ClusterCache
    from kai_scheduler_tpu.controllers.kubeapi import make_pod
    from kai_scheduler_tpu.controllers.podgrouper import POD_GROUP_LABEL
    from test_incremental_cache import seed_cluster

    monkeypatch.setenv("KAI_COLUMNAR", "1")
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)

    def session():
        cluster = cache.snapshot()
        side = InMemoryCache()
        side.arena = cache.arena
        return cluster, Session(cluster, SchedulerConfig(), side)

    def host_arena_sessions(cluster):
        sched = Scheduler(lambda: cluster, SchedulerConfig())
        return sched.run_once(), sched.run_once()

    session()
    cluster, ssn = session()
    assert cache.last_columnar_stats["path"] == "columnar"
    # Said by the builder of the snapshot, in the session's form: a list.
    assert cluster.term_carriers == [] and ssn.term_carriers == []
    assert "no_affinity_terms" not in cluster.columnar_hints
    full, patched = host_arena_sessions(cluster.clone())
    assert full.term_carriers == patched.term_carriers == []

    api.create(make_pod("guard", labels={POD_GROUP_LABEL: "pg0",
                                         "app": "guard"},
                        affinity=_anti_affinity("web")))
    cluster, ssn = session()
    assert cache.last_columnar_stats == {"path": "object",
                                         "reason": "complex-pods"}
    assert cluster.term_carriers is None      # nobody proved it: a walk
    (guard,) = ssn.term_carriers
    assert guard.name == "guard" and guard.anti_affinity_terms
    full, patched = host_arena_sessions(cluster.clone())
    assert carrier_uids(full) == carrier_uids(patched) == [guard.uid]

    api.delete("Pod", "guard")
    cluster, ssn = session()
    assert cache.last_columnar_stats["path"] == "columnar"
    assert ssn.term_carriers == []


def test_a_pod_that_gains_a_term_is_seen_by_the_next_session():
    loop = Loop(fleet("no-carrier", 4))
    METRICS.reset()
    loop.arrive(labels={"app": "web"})
    ssn, forms = loop.cycle()
    assert ssn.term_carriers == [] and set(forms) == {"none"}
    # Between two cycles run0's first pod is replaced by one that repels
    # app=web from its zone.
    guard = next(iter(loop.cluster.podgroups["run0"].pods.values()))
    guard.anti_affinity_terms = [AffinityTerm(
        {"app": "web"}, "zone", namespaces=["default"])]
    zone = loop.cluster.nodes[guard.node_name].labels["zone"]
    pg = loop.arrive(labels={"app": "web"})
    ssn, forms = loop.cycle()
    assert not ssn.pack_stats["full_rebuild"]
    assert ssn.term_carriers == [guard] and set(forms) == {"carriers"}
    placed = [t.node_name for t in pg.pods.values()]
    assert all(placed)
    assert all(loop.cluster.nodes[n].labels["zone"] != zone for n in placed)
    # And one that loses it, likewise.
    guard.anti_affinity_terms = []
    loop.arrive(labels={"app": "web"})
    ssn, forms = loop.cycle()
    assert ssn.term_carriers == [] and set(forms) == {"none"}
    assert walks() == 0
