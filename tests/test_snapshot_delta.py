"""Arena delta-pack chaos suite (marker ``chaos``, tier-1).

The persistent device arena (framework/arena.py) replaces the per-cycle
world rebuild with incremental snapshot packs and scatter-based device
updates.  Its correctness contract is absolute: a delta-built snapshot
must be **bit-identical** to a from-scratch ``pack()`` of the same
cluster, and scheduling on the arena path must produce **identical
placements** to a fresh session — under any interleaving of cluster
events.  This suite drives randomized event sequences (add/delete/modify
node & pod, selector-bearing pods, bind, evict, group churn, resync /
watch-gap boundaries) against a ``ClusterCache`` and checks both
invariants at every step, plus the degraded-mode contract (arena device
caches dropped on breaker/CPU-fallback transitions, scheduling results
unchanged).

Seeded in the chaos-matrix style: the sweep seed comes from
``KAI_FAULT_SEED`` (tools/chaos_matrix.py --arena replays the suite under
many seeds) and composes with the per-test parametrized seed.
"""

import dataclasses
import os

import numpy as np
import pytest

from kai_scheduler_tpu.actions.allocate import AllocateAction
from kai_scheduler_tpu.api.snapshot import pack
from kai_scheduler_tpu.controllers import InMemoryKubeAPI
from kai_scheduler_tpu.controllers.cache_builder import ClusterCache
from kai_scheduler_tpu.controllers.kubeapi import make_pod
from kai_scheduler_tpu.controllers.podgrouper import POD_GROUP_LABEL
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.framework.session import InMemoryCache, Session
from kai_scheduler_tpu.utils.deviceguard import (configure_device_guard,
                                                 reset_device_guard)
from kai_scheduler_tpu.utils.metrics import METRICS

pytestmark = pytest.mark.chaos

SWEEP_SEED = int(os.environ.get("KAI_FAULT_SEED", "0") or 0)


def _node(api, name, gpu=8, labels=None):
    api.create({"kind": "Node",
                "metadata": {"name": name, "labels": dict(labels or {})},
                "spec": {},
                "status": {"allocatable": {"cpu": "32", "memory": "256Gi",
                                           "nvidia.com/gpu": gpu,
                                           "pods": 110}}})


def _group(api, name, queue="q0", min_member=1):
    api.create({"kind": "PodGroup", "metadata": {"name": name},
                "spec": {"queue": queue, "minMember": min_member}})


def _pod(api, name, group, gpu=0, node_selector=None, tolerations=None):
    api.create(make_pod(name, labels={POD_GROUP_LABEL: group}, gpu=gpu,
                        node_selector=node_selector,
                        tolerations=tolerations))


class Mutator:
    """Randomized cluster-event generator over the API store."""

    def __init__(self, api: InMemoryKubeAPI, cache: ClusterCache,
                 rng: np.random.Generator):
        self.api = api
        self.cache = cache
        self.rng = rng
        self.node_seq = 0
        self.pod_seq = 0
        self.group_seq = 0

    def _pods(self):
        return [p for p in self.api.list("Pod")
                if p["metadata"].get("labels", {}).get(POD_GROUP_LABEL)]

    def _pick(self, items):
        return items[int(self.rng.integers(0, len(items)))] if items \
            else None

    # -- the event vocabulary ---------------------------------------------
    def add_node(self):
        self.node_seq += 1
        labels = {"zone": f"z{self.node_seq % 3}"} \
            if self.rng.random() < 0.5 else None
        _node(self.api, f"dyn-n{self.node_seq}", labels=labels)

    def delete_node(self):
        node = self._pick(self.api.list("Node"))
        if node is not None:
            self.api.delete("Node", node["metadata"]["name"])

    def modify_node(self):
        node = self._pick(self.api.list("Node"))
        if node is not None:
            self.api.patch("Node", node["metadata"]["name"],
                           {"metadata": {"labels": {
                               "zone": f"z{int(self.rng.integers(0, 4))}"}}})

    def add_group(self):
        self.group_seq += 1
        name = f"dyn-pg{self.group_seq}"
        size = int(self.rng.integers(1, 4))
        _group(self.api, name, queue=f"q{self.group_seq % 2}",
               min_member=size)
        for k in range(size):
            self.pod_seq += 1
            sel = {"zone": "z1"} if self.rng.random() < 0.3 else None
            _pod(self.api, f"dyn-p{self.pod_seq}", name,
                 gpu=int(self.rng.integers(0, 3)), node_selector=sel)

    def add_pod(self):
        group = self._pick(self.api.list("PodGroup"))
        if group is not None:
            self.pod_seq += 1
            _pod(self.api, f"dyn-p{self.pod_seq}",
                 group["metadata"]["name"],
                 gpu=int(self.rng.integers(0, 2)))

    def delete_pod(self):
        pod = self._pick(self._pods())
        if pod is not None:
            self.api.delete("Pod", pod["metadata"]["name"],
                            pod["metadata"].get("namespace", "default"))

    def modify_pod(self):
        pod = self._pick(self._pods())
        if pod is not None:
            gpu = int(self.rng.integers(0, 3))
            self.api.patch(
                "Pod", pod["metadata"]["name"],
                {"spec": {"containers": [
                    {"name": "main", "resources": {"requests": {
                        "cpu": "1", "memory": "1Gi",
                        **({"nvidia.com/gpu": gpu} if gpu else {})}}}]}},
                pod["metadata"].get("namespace", "default"))

    def bind_pod(self):
        pod = self._pick([p for p in self._pods()
                          if not p["spec"].get("nodeName")])
        node = self._pick(self.api.list("Node"))
        if pod is not None and node is not None:
            self.api.patch("Pod", pod["metadata"]["name"],
                           {"spec": {"nodeName":
                                     node["metadata"]["name"]}},
                           pod["metadata"].get("namespace", "default"))

    def evict_pod(self):
        pod = self._pick([p for p in self._pods()
                          if p["spec"].get("nodeName")])
        if pod is not None:
            self.api.patch("Pod", pod["metadata"]["name"],
                           {"metadata": {"deletionTimestamp": "1"}},
                           pod["metadata"].get("namespace", "default"))

    def delete_group(self):
        group = self._pick(self.api.list("PodGroup"))
        if group is not None:
            self.api.delete("PodGroup", group["metadata"]["name"])

    def resync(self):
        # A watch gap forced a re-list (the PR2 reconciler's 410-GONE
        # path fires the cache's resync callback exactly like this).
        self.cache._on_watch_resync()

    def noop(self):
        pass

    OPS = ("add_node", "delete_node", "modify_node", "add_group",
           "add_pod", "delete_pod", "modify_pod", "bind_pod", "evict_pod",
           "delete_group", "resync", "noop", "noop")

    def step(self):
        for _ in range(int(self.rng.integers(0, 3))):
            getattr(self, str(self.rng.choice(self.OPS)))()


def seed_cluster(api):
    for i in range(10):
        _node(api, f"n{i}", labels={"zone": f"z{i % 3}"})
    for q in range(2):
        api.create({"kind": "Queue", "metadata": {"name": f"q{q}"},
                    "spec": {}})
    for j in range(4):
        _group(api, f"pg{j}", queue=f"q{j % 2}", min_member=2)
        for k in range(2):
            _pod(api, f"p{j}-{k}", f"pg{j}", gpu=1 if j % 2 == 0 else 0)


def assert_snapshots_identical(a, b):
    """Field-by-field bit-identity of two SnapshotTensors."""
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray):
            assert va.shape == vb.shape and va.dtype == vb.dtype, \
                f"{f.name}: shape/dtype {va.shape}/{va.dtype} != " \
                f"{vb.shape}/{vb.dtype}"
            assert np.array_equal(va, vb), f"{f.name}: values differ"
        elif f.name == "codec":
            assert (va.key_cols, va.value_codes, va.taint_codes) == \
                (vb.key_cols, vb.value_codes, vb.taint_codes), \
                "codec vocabulary differs"
        elif f.name == "pack_epoch":
            continue  # monotonic by design, never equal
        else:
            assert va == vb, f"{f.name}: {va!r} != {vb!r}"


def placements_of(ssn):
    return sorted(
        (t.uid, t.node_name, t.status.name)
        for pg in ssn.cluster.podgroups.values()
        for t in pg.pods.values())


def run_allocate_both_paths(api, cache):
    """Allocate on the arena path and on a from-scratch session; both see
    the same store, so their placements must match exactly."""
    cluster_a = cache.snapshot()
    side_cache = InMemoryCache()
    side_cache.arena = cache.arena   # arena path, commits stay in-memory
    ssn_a = Session(cluster_a, SchedulerConfig(), side_cache)
    ssn_a.open()
    AllocateAction().execute(ssn_a)

    cluster_b = ClusterCache(api).snapshot()
    ssn_b = Session(cluster_b, SchedulerConfig(), InMemoryCache())
    ssn_b.open()
    AllocateAction().execute(ssn_b)
    assert placements_of(ssn_a) == placements_of(ssn_b)
    return ssn_a


# ---------------------------------------------------------------------------
# Property: delta pack is bit-identical to a from-scratch rebuild
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_delta_pack_bit_identical_under_random_events(seed):
    rng = np.random.default_rng(1000 * SWEEP_SEED + seed)
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    mut = Mutator(api, cache, rng)

    deltas = 0
    for step in range(30):
        mut.step()
        cluster = cache.snapshot()
        snap_delta, stats = cache.arena.pack(cluster)
        snap_full = pack(cluster)
        assert_snapshots_identical(snap_delta, snap_full)
        if not stats["full_rebuild"]:
            deltas += 1
            assert stats["delta_ratio"] <= 1.0
    # The suite must actually exercise the delta path — an arena that
    # silently full-rebuilds every cycle would pass identity vacuously.
    assert deltas >= 5, f"only {deltas}/30 steps took the delta path"


@pytest.mark.parametrize("seed", [1, 2])
def test_allocate_identical_on_arena_and_fresh_paths(seed):
    rng = np.random.default_rng(2000 * SWEEP_SEED + seed)
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    mut = Mutator(api, cache, rng)
    for step in range(8):
        mut.step()
        run_allocate_both_paths(api, cache)


# ---------------------------------------------------------------------------
# Resync / watch-gap boundaries invalidate the arena wholesale
# ---------------------------------------------------------------------------

def test_resync_during_delta_forces_full_rebuild():
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    # Warm: establish the delta path.
    cache.arena.pack(cache.snapshot())
    _snap, stats = cache.arena.pack(cache.snapshot())
    assert not stats["full_rebuild"]
    gen = cache.arena.generation
    # The watch gap lands mid-sequence; the next snapshot must rebuild
    # from scratch (pod parse cache AND arena) and still be identical.
    cache._on_watch_resync()
    cluster = cache.snapshot()
    snap_delta, stats = cache.arena.pack(cluster)
    assert stats["full_rebuild"] and stats["reason"] == "watch-resync"
    assert cache.arena.generation == gen + 1
    assert_snapshots_identical(snap_delta, pack(cluster))
    # The cycle after the rebuild resumes the delta path.
    _snap, stats = cache.arena.pack(cache.snapshot())
    assert not stats["full_rebuild"]


def test_topology_and_vocab_changes_force_full_rebuild():
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    cache.arena.pack(cache.snapshot())

    _node(api, "late-node")  # topology change
    cluster = cache.snapshot()
    snap, stats = cache.arena.pack(cluster)
    assert stats["full_rebuild"] and stats["reason"] == "node-change"
    assert_snapshots_identical(snap, pack(cluster))

    _pod(api, "sel-pod", "pg0", node_selector={"zone": "z9"})  # vocab
    cluster = cache.snapshot()
    snap, stats = cache.arena.pack(cluster)
    assert stats["full_rebuild"] and stats["reason"] == "vocab-change"
    assert_snapshots_identical(snap, pack(cluster))


def test_stale_or_foreign_cluster_never_takes_delta_path():
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    old_cluster = cache.snapshot()
    cache.arena.pack(old_cluster)
    fresh_cluster = cache.snapshot()          # newer stamp
    _snap, stats = cache.arena.pack(old_cluster)   # stale view
    assert stats["full_rebuild"] and stats["reason"] == "unstamped-cluster"
    # The stale pack poisoned the delta baseline: even the latest cluster
    # must rebuild (the dirty set no longer describes changes since the
    # baseline), and only a fresh snapshot restores the delta path.
    _snap, stats = cache.arena.pack(fresh_cluster)
    assert stats["full_rebuild"] and stats["reason"] == "stale-baseline"
    _snap, stats = cache.arena.pack(cache.snapshot())
    assert not stats["full_rebuild"]


# ---------------------------------------------------------------------------
# Device-side: scatter path, residency, and degraded-mode invalidation
# ---------------------------------------------------------------------------

def test_scatter_updates_only_dirty_rows_and_matches_full_upload():
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    METRICS.counters.pop("arena_scatter_rows", None)
    ssn = run_allocate_both_paths(api, cache)
    assert ssn.pack_stats is not None
    # Second cycle adopts the resident device state: the rows the first
    # cycle's statements touched arrive by scatter, not a full upload.
    ssn2 = run_allocate_both_paths(api, cache)
    assert cache.arena.state.resident
    scattered = METRICS.counters.get("arena_scatter_rows", 0)
    assert 0 < scattered < len(ssn2.cluster.nodes) * len(placements_of(ssn2))


def test_static_tensors_upload_once_per_generation():
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    run_allocate_both_paths(api, cache)
    static_before = cache.arena._static_dev
    assert static_before is not None
    run_allocate_both_paths(api, cache)   # same generation: same buffers
    assert cache.arena._static_dev is static_before
    _node(api, "gen-bump")                # topology change: new generation
    run_allocate_both_paths(api, cache)
    assert cache.arena._static_dev is not static_before


def test_breaker_open_during_scatter_invalidates_and_still_schedules():
    """Chaos: the device dies while the arena is resident.  The guard
    degrades dispatches to the CPU fallback; the arena must drop its
    device caches on the transition (never hand a stale device buffer to
    the fallback path) and scheduling must continue with identical
    results."""
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    try:
        configure_device_guard(deadline_s=5.0, retries=0,
                               breaker_threshold=1, fallback_enabled=True,
                               fault=None, fault_seed=SWEEP_SEED)
        run_allocate_both_paths(api, cache)   # healthy warm-up, resident
        assert cache.arena.state.resident
        inval0 = METRICS.counters.get("arena_device_invalidation_total", 0)
        # Kill the device path: every dispatch now errors and falls back.
        from kai_scheduler_tpu.utils.deviceguard import device_guard
        device_guard().set_fault("error", seed=SWEEP_SEED)
        ssn = run_allocate_both_paths(api, cache)
        assert ssn is not None
        assert METRICS.counters.get(
            "arena_device_invalidation_total", 0) > inval0
        # Recovery transition (breaker closes) invalidates once more and
        # scheduling stays identical on the re-uploaded arena.
        device_guard().clear_fault()
        run_allocate_both_paths(api, cache)
        run_allocate_both_paths(api, cache)
    finally:
        reset_device_guard()


def test_sharded_provider_cluster_packs_from_scratch():
    """A node-pool-filtered cluster rewrites the node axis out from under
    the arena: the operator's shard provider clears the stamp, and the
    pack must fall back to a full rebuild rather than patch mismatched
    rows."""
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    cache.arena.pack(cache.snapshot())
    cluster = cache.snapshot()
    cluster.arena_stamp = None     # what _shard_provider does on filter
    snap, stats = cache.arena.pack(cluster)
    assert stats["full_rebuild"] and stats["reason"] == "unstamped-cluster"
    assert_snapshots_identical(snap, pack(cluster))


# ---------------------------------------------------------------------------
# The host arena: a bare ClusterInfo under one Scheduler, no watch stream
# ---------------------------------------------------------------------------
#
# ``Scheduler`` carries the host half of the pack from one session to the
# next whatever its cache (framework/arena.py ``HostArena``): the dirty
# rows come from the stamps the NodeInfo mutators leave.  The loop below is
# the benchmark client's (benchmark/generators/closed_loop_gangs.py): a
# bound gang completes through ``node.remove_task`` and ``del
# cluster.podgroups[...]``, a gang arrives, ``run_once`` commits, the
# bound pods turn RUNNING.  After every Session construction each array of
# ``ssn.snapshot`` must equal a from-scratch ``pack()`` of the same
# cluster, dtype and bytes, and a patched pack must have patched exactly
# the rows the test touched.

def _bare_spec(nodes=12, busy=range(0, 12, 3), labels=None, tainted=(),
               selector=None, tolerations=()):
    """``nodes`` four-GPU nodes, those of ``tainted`` with the taint
    ``dedicated``; a RUNNING two-GPU pod on each of ``busy`` (with
    ``selector`` and ``tolerations`` if given)."""
    return {
        "nodes": {f"n{i:02d}": {"gpu": 4, "cpu": "32", "mem": "256Gi",
                                "labels": labels(i) if labels else None,
                                "taints": ("dedicated",) if i in tainted
                                else ()}
                  for i in range(nodes)},
        "queues": {"q0": {}, "q1": {}},
        "jobs": {"base": {"queue": "q0", "min_available": 1, "tasks": [
            {"name": f"base-{i}", "gpu": 2, "status": "RUNNING",
             "node": f"n{i:02d}", "selector": selector or {},
             "tolerations": tolerations} for i in busy]}},
    }


class BareLoop:
    """One persistent ClusterInfo, one Scheduler, the client's moves."""

    def __init__(self, spec=None):
        from kai_scheduler_tpu.scheduler import Scheduler
        from tests.fixtures import build_cluster
        self.spec = spec or _bare_spec()
        self.cluster = build_cluster(self.spec)
        self.sched = Scheduler(self._provide, SchedulerConfig())
        self.provided = None     # a stand-in the next provider call returns
        self.expected = None     # pack() from scratch at provider time
        self.touched = set()     # nodes touched since the last session
        self.seq = 0
        self.sessions = []

    def _provide(self):
        cluster, self.provided = self.provided or self.cluster, None
        bucket = self.sched.config.node_pad_bucket
        n = len(cluster.nodes)
        pad = max(bucket, -(-n // bucket) * bucket) if bucket else None
        # A bystander's pack of the very cluster the session is about to
        # pack: it reads what the session reads and must disturb nothing.
        self.expected = pack(cluster, pad_nodes_to=pad)
        return cluster

    def arrive(self, size, gpu=1, gpu_fraction=0.0, **pod):
        from kai_scheduler_tpu.api import PodGroupInfo, PodInfo
        from kai_scheduler_tpu.api.resources import ResourceRequirements
        self.seq += 1
        uid = f"gang-{self.seq:03d}"
        pg = PodGroupInfo(uid, uid, queue_id="q1", min_available=size)
        for k in range(size):
            pg.add_task(PodInfo(
                uid=f"{uid}-{k}", name=f"{uid}-{k}",
                res_req=ResourceRequirements.from_spec(
                    "1", "1Gi", gpu, gpu_fraction=gpu_fraction), **pod))
        self.cluster.podgroups[uid] = pg
        self.cluster.invalidate_aggregates()
        return pg

    def complete(self, pg):
        for task in pg.pods.values():
            node = self.cluster.nodes.get(task.node_name)
            if node is not None:
                node.remove_task(task)
                self.touched.add(node.name)
        del self.cluster.podgroups[pg.uid]
        self.cluster.invalidate_aggregates()

    def cycle(self):
        """One ``run_once``: the session's snapshot is held to the
        from-scratch pack of the same cluster and its verdict to the
        rows touched since the session before; then the binds settle."""
        from kai_scheduler_tpu.api import PodStatus
        touched, self.touched = self.touched, set()
        ssn = self.sched.run_once()
        assert_snapshots_identical(ssn.snapshot, self.expected)
        stats = ssn.pack_stats
        assert stats["total_rows"] == len(ssn.cluster.nodes)
        if not stats["full_rebuild"]:
            # At least: an action that tried victims and rolled back
            # (reclaim for a gang left pending) re-stamps rows too.
            assert stats["changed_rows"] >= len(touched), (stats, touched)
        cache = self.sched.cache
        bound = dict(cache.bound)
        self.touched.update(bound.values())
        self.touched.update(node for _uid, node in cache.pipelined)
        cache.bound.clear()
        cache.pipelined.clear()
        ssn.cluster.bind_requests.clear()
        for pg in ssn.cluster.podgroups.values():
            for task in pg.pods.values():
                if task.uid in bound:
                    pg.update_task_status(task, PodStatus.RUNNING)
        self.sessions.append(ssn)
        return ssn


def _placed(pg):
    return sorted((t.uid, t.node_name, t.status.name)
                  for t in pg.pods.values())


def _case_apply_bulk(loop):
    """Plain pods: the exact kernel proposes, ``apply_bulk`` commits
    through the native table's batch call."""
    live = []
    for _ in range(4):
        if len(live) >= 2:
            loop.complete(live.pop(0))
        pg = loop.arrive(5)
        loop.cycle()
        assert all(t.node_name for t in pg.pods.values()), _placed(pg)
        live.append(pg)


def _case_per_task(loop):
    """Fractional pods take the statement's task-by-task path
    (``Statement.allocate``, sharing groups and whole-device charges)."""
    live = []
    for _ in range(4):
        if len(live) >= 2:
            loop.complete(live.pop(0))
        pg = loop.arrive(3, gpu=0, gpu_fraction=0.5)
        loop.cycle()
        assert all(t.gpu_group for t in pg.pods.values()), _placed(pg)
        live.append(pg)


def _case_rollback_and_abort(loop):
    """A statement rolled back through the native undo path and one left
    to ``abort_uncommitted``: the rows they touched read as they did, and
    are patched all the same."""
    from kai_scheduler_tpu.api import PodStatus
    loop.arrive(4)
    ssn = loop.cycle()
    tasks = list(loop.arrive(3).pods.values())
    st = ssn.statement()
    st.apply_bulk([(t, "n01", False) for t in tasks])
    assert all(t.status == PodStatus.ALLOCATED for t in tasks)
    st.rollback()
    st = ssn.statement()
    st.allocate(tasks[0], "n02")
    st.pipeline(tasks[1], "n04")
    assert ssn.abort_uncommitted() == 1
    assert all(t.status == PodStatus.PENDING and not t.node_name
               for t in tasks)
    loop.touched.update(("n01", "n02", "n04"))
    loop.cycle()
    loop.cycle()


def _case_evict_and_pipeline(loop):
    """An eviction leaves RELEASING rows; a gang that fits only with what
    is being released is pipelined onto them."""
    from kai_scheduler_tpu.api import PodStatus
    loop.arrive(2)
    ssn = loop.cycle()
    victims = [t for t in ssn.cluster.podgroups["base"].pods.values()][:2]
    st = ssn.statement()
    for t in victims:
        st.evict(t)
        loop.touched.add(t.node_name)
    st.commit()
    assert all(t.status == PodStatus.RELEASING for t in victims)
    waiting = loop.arrive(11)    # 10 GPUs idle, 4 more being released
    ssn = loop.cycle()
    assert ssn.snapshot.node_releasing.any()
    assert {t.status for t in waiting.pods.values()} \
        == {PodStatus.PIPELINED}, _placed(waiting)
    ssn = loop.cycle()
    assert ssn.snapshot.node_releasing.any()


def _case_bystander_pack(loop):
    """The generator's ``prime``: a direct ``pack(cluster)`` with a gang
    pending, between two cycles, takes no delta path and eats no mark."""
    loop.arrive(4)
    loop.cycle()
    probe = loop.arrive(3)
    assert pack(loop.cluster).num_tasks == 3
    del loop.cluster.podgroups[probe.uid]
    loop.cluster.invalidate_aggregates()
    loop.cycle()     # patches the first cycle's binds: nothing was eaten
    assert loop.sessions[-1].pack_stats["changed_rows"] > 0
    loop.cycle()


def _case_vocabulary_at_rest(loop):
    """Selectors, tolerations, labels and taints that do not change do
    not stand in the way: the codec and its rows are carried."""
    assert loop.cluster.nodes["n00"].taints
    for _ in range(3):
        pg = loop.arrive(3)
        ssn = loop.cycle()
        assert all(t.node_name for t in pg.pods.values()), _placed(pg)
        loop.complete(pg)
    assert ssn.snapshot.codec.key_cols and ssn.snapshot.codec.taint_codes


_AT_REST = _bare_spec(labels=lambda i: {"zone": f"z{i % 3}", "rack": "r"},
                      tainted=(0, 3), selector={"zone": "z0"},
                      tolerations=("dedicated",))
_NEARLY_FULL = _bare_spec(busy=())
_NEARLY_FULL["jobs"]["base"]["tasks"] = [
    {"name": f"base-{i}", "gpu": 2, "status": "RUNNING",
     "node": f"n{i // 2:02d}"} for i in range(18)]

BARE_CASES = {
    "apply_bulk": (_case_apply_bulk, None),
    "per_task": (_case_per_task, None),
    "rollback_and_abort": (_case_rollback_and_abort, None),
    "evict_and_pipeline": (_case_evict_and_pipeline, _NEARLY_FULL),
    "bystander_pack": (_case_bystander_pack, None),
    "vocabulary_at_rest": (_case_vocabulary_at_rest, _AT_REST),
}


@pytest.mark.parametrize("case", sorted(BARE_CASES))
def test_bare_cluster_sessions_equal_a_pack_from_scratch(case):
    drive, spec = BARE_CASES[case]
    loop = BareLoop(spec)
    drive(loop)
    stats = [s.pack_stats for s in loop.sessions]
    assert stats[0]["full_rebuild"] \
        and stats[0]["reason"] == "no-previous-pack"
    # The property must not hold vacuously: every later session patched.
    assert [s["full_rebuild"] for s in stats[1:]] \
        == [False] * (len(stats) - 1), stats


# -- what the host arena cannot prove, it packs from scratch ---------------

def _clone(loop):
    loop.provided = loop.cluster.clone()


def _equal_content(loop):
    from tests.fixtures import build_cluster
    loop.provided = build_cluster(loop.spec)


def _node_added(loop):
    from kai_scheduler_tpu.api import NodeInfo
    cluster = loop.cluster
    like = cluster.nodes["n00"]
    cluster.nodes["n99"] = NodeInfo("n99", like.allocatable.copy())
    cluster.node_order = sorted(cluster.nodes)
    for i, name in enumerate(cluster.node_order):
        cluster.nodes[name].idx = i


def _node_replaced(loop):
    from kai_scheduler_tpu.api import NodeInfo
    old = loop.cluster.nodes["n05"]
    assert not old.pod_infos
    loop.cluster.nodes["n05"] = NodeInfo(
        "n05", old.allocatable * 2.0, idx=old.idx)


def _selector_arrives(loop):
    loop.arrive(1, node_selector={"zone": "z1"})


def _toleration_arrives(loop):
    loop.arrive(1, tolerations={"dedicated"})


def _relabelled(loop):
    loop.cluster.nodes["n04"].labels["zone"] = "z9"


def _pad_changes(loop):
    loop.sched.config.node_pad_bucket = 16


def _mostly_dirty(loop):
    pg = loop.arrive(10, gpu=2)   # ten nodes of twelve
    for k, task in enumerate(pg.pods.values()):
        task.node_name = f"n{k:02d}"
        pg.update_task_status(task, _running())
        loop.cluster.nodes[task.node_name].add_task(task)


def _bystander_session(loop):
    """Another Session over the same cluster re-binds every node's
    ``used`` and ``releasing`` to its own table: the carried one is
    stale, and every node says so."""
    Session(loop.cluster, SchedulerConfig(), InMemoryCache())


def _running():
    from kai_scheduler_tpu.api import PodStatus
    return PodStatus.RUNNING


REBUILD_CASES = {
    "cloned_cluster": (_clone, "other-cluster", None),
    "equal_content_new_object": (_equal_content, "other-cluster", None),
    "node_order_changed": (_node_added, "topology-change", None),
    "node_replaced": (_node_replaced, "node-change", None),
    "selector_arrives": (_selector_arrives, "vocab-change", None),
    "toleration_arrives": (_toleration_arrives, "vocab-change", None),
    "selected_label_changes": (_relabelled, "vocab-change", _AT_REST),
    "pad_nodes_to_changes": (_pad_changes, "node-bucket-growth", None),
    "mostly_dirty": (_mostly_dirty, "mostly-dirty", None),
    "bystander_session": (_bystander_session, "mostly-dirty", None),
}


@pytest.mark.parametrize("case", sorted(REBUILD_CASES))
def test_bare_cluster_rebuilds_where_it_cannot_prove(case):
    change, reason, spec = REBUILD_CASES[case]
    loop = BareLoop(spec)
    loop.arrive(3)
    loop.cycle()
    loop.arrive(2)
    assert not loop.cycle().pack_stats["full_rebuild"]
    change(loop)
    stats = loop.cycle().pack_stats       # identical to pack() all the same
    assert stats["full_rebuild"] and stats["reason"] == reason, stats
    # ... and the baseline it left is patched again.
    loop.cycle()
    loop.arrive(2)
    stats = loop.cycle().pack_stats
    assert not stats["full_rebuild"], stats


def test_host_arena_engages_once_and_says_so_on_the_snapshot_span():
    """Counts only (ROADMAP D13): over k cycles on one persistent cluster
    the full-rebuild counter moves once, every later ``snapshot`` span
    carries ``full_rebuild=False`` and as many ``changed_rows`` as nodes
    were touched, and no ``snapshot_delta`` span is opened on this path
    (``benchmark/layer_metrics/snapshot_ms.json`` sums both names)."""
    from kai_scheduler_tpu.utils.tracing import TRACER
    loop = BareLoop()
    rebuilds0 = METRICS.counters.get("arena_full_rebuild_total", 0)
    live = []
    for k in range(6):
        if len(live) >= 2:
            loop.complete(live.pop(0))
        live.append(loop.arrive(4))
        touched = set(loop.touched)
        loop.cycle()
        spans = TRACER.get_trace().spans
        assert not [s.name for s in spans if s.name == "snapshot_delta"]
        (snapshot,) = [s for s in spans if s.name == "snapshot"]
        verdict = snapshot.attrs
        assert verdict["total_rows"] == 12
        if k == 0:
            assert verdict["full_rebuild"] is True
            assert verdict["reason"] == "no-previous-pack"
            continue
        assert verdict["full_rebuild"] is False and verdict["reason"] == ""
        assert verdict["changed_rows"] == len(touched) > 0, (verdict, touched)
        assert METRICS.gauges["snapshot_delta_ratio"] \
            == pytest.approx(len(touched) / 12)
    assert METRICS.counters["arena_full_rebuild_total"] - rebuilds0 == 1


# -- what a PodGroup keeps of its pods from one session to the next --------

def _visits():
    return METRICS.counters["queue_aggregate_pod_visits_total"]


def _snapshot_span():
    from kai_scheduler_tpu.utils.tracing import TRACER
    (span,) = [s for s in TRACER.get_trace().spans if s.name == "snapshot"]
    return span.attrs


@pytest.mark.parametrize("nodes", [64, 12])
def test_a_session_counts_the_pods_of_the_podgroups_that_changed(nodes):
    """``queue_aggregate_pod_visits_total``: a session after a cluster is
    built counts every pod the queue sums are the first to ask for; the
    next counts the pods of the PodGroups that changed in between, none
    where nothing did; and the ``snapshot`` span says how many it counted
    (``aggregate_pod_visits``).  A gang that waits is counted before the
    sums ask: the pack selects the pending jobs first
    (``is_ready_for_scheduling``), and the one walk fills both kept
    things (PR 56)."""
    loop = BareLoop(_bare_spec(nodes=nodes, busy=range(0, nodes, 3)))
    base = len(loop.cluster.podgroups["base"].pods)
    first = loop.arrive(3)
    loop.cycle()                  # binds the gang, the client runs it
    assert _snapshot_span()["aggregate_pod_visits"] == base
    assert {t.status.name for t in first.pods.values()} == {"RUNNING"}
    v0 = _visits()
    loop.cycle()                  # nothing pending: the gang's new status
    assert _visits() - v0 == 3
    assert _snapshot_span()["aggregate_pod_visits"] == 3
    v0 = _visits()
    loop.cycle()                  # nothing changed
    assert _visits() - v0 == 0
    assert _snapshot_span()["aggregate_pod_visits"] == 0
    second = loop.arrive(4)
    loop.complete(first)          # a PodGroup that left counts for nothing
    loop.cycle()
    assert _snapshot_span()["aggregate_pod_visits"] == 0
    assert second.uncounted_pods() == len(second.pods)    # bound, and run
    v0 = _visits()
    loop.cluster.podgroups["base"].queue_id = "q1"    # read, not kept
    loop.cluster.invalidate_aggregates()
    loop.cycle()
    assert _visits() - v0 == len(second.pods)
    assert loop.sessions[-1].snapshot.queue_allocated[0].sum() == 0.0


def _plain_vocabulary_walk(cluster):
    """``vocabulary_signature`` as it is defined, pod by pod."""
    pods, carriers, keys = [], [], set()
    for pg in cluster.podgroups.values():
        for t in pg.pods.values():
            if t.node_selector or t.tolerations:
                pods.append((t.uid, tuple(t.node_selector.items()),
                             tuple(sorted(t.tolerations))))
                keys.update(t.node_selector)
            if (t.affinity_terms or t.anti_affinity_terms
                    or t.preferred_affinity_terms
                    or t.preferred_anti_affinity_terms):
                carriers.append(t)
    nodes = [(name, tuple((k, v) for k, v in node.labels.items()
                          if k in keys), tuple(node.taints))
             for name, node in cluster.nodes.items()
             if node.taints or (keys and node.labels)]
    return (pods, nodes), carriers


@pytest.mark.parametrize("spec", [None, _AT_REST],
                         ids=["bare", "selected_and_tainted"])
def test_vocabulary_signature_is_read_off_the_pods_every_time(spec):
    """A selector-bearing gang arrives and leaves (the ``pools98k``
    shape) between packs, and a pod gains a toleration and a term in
    place: the signature is the plain walk's each time, so nothing of it
    may be kept on a PodGroup (``tests/test_podaffinity_gate.py`` holds
    the carriers to the same; ROADMAP S11d)."""
    from kai_scheduler_tpu.api import AffinityTerm
    from kai_scheduler_tpu.api.snapshot import vocabulary_signature
    loop = BareLoop(spec)

    def held():
        got = vocabulary_signature(loop.cluster)
        assert got == _plain_vocabulary_walk(loop.cluster)
        return got

    at_rest = held()
    loop.cycle()
    gang = loop.arrive(2, node_selector={"zone": "z1"},
                       tolerations={"dedicated"})
    arrived = held()
    assert arrived != at_rest and len(arrived[0][0]) == len(at_rest[0][0]) + 2
    assert loop.cycle().pack_stats["reason"] == "vocab-change"
    loop.complete(gang)
    assert held() == at_rest
    assert loop.cycle().pack_stats["reason"] == "vocab-change"
    loop.cycle()
    # In place, through no door of the PodGroup's.
    pod = next(iter(loop.cluster.podgroups["base"].pods.values()))
    pod.tolerations = set(pod.tolerations) | {"spot"}
    pod.anti_affinity_terms = [AffinityTerm({"app": "web"}, "zone")]
    signature, carriers = held()
    assert signature != at_rest[0] and carriers == [pod]
    assert loop.cycle().pack_stats["reason"] == "vocab-change"
    assert loop.sessions[-1].term_carriers == [pod]


def test_the_benchmark_reads_the_pods_a_cycle_counted():
    """``benchmark/layer_metrics/aggregate_pod_visits.json`` as the harness
    reads it: the counter's movement over ``run_once``, 0.0 where nothing
    changed and not nothing; a program without the counter, as the parent
    is, leaves the metric out."""
    import json
    from benchmark.harness import readers
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    # Asked by name: later PRs append their metrics after this one.
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "aggregate_pod_visits"]
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           entry["name"] + ".json")) as fh:
        doc = json.load(fh)
    assert entry["workloads"][:7] == [w["name"]
                                      for w in bench["workloads"][:7]]
    assert doc["reader"] == {"kind": "counter_delta",
                             "counter": "queue_aggregate_pod_visits_total"}
    assert {k: v for k, v in doc.items() if k != "reader"} \
        == {k: v for k, v in entry.items() if k != "workloads"}
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("pods/cycle", "lower", "program_counter",
                                "snapshot and pack", "cycle_ms")
    wanted = readers.counters_wanted([doc])

    class Rec:
        counters = {}
        spans = []

    assert readers.read_all([doc], {"records": [Rec]}) == {}
    METRICS.reset()
    loop = BareLoop()
    loop.arrive(3)
    reads = []
    for _ in range(3):
        before = {c: METRICS.counters.get(c, 0.0) for c in wanted}
        loop.cycle()
        Rec.counters = {c: METRICS.counters[c] - before[c]
                        for c in wanted if c in METRICS.counters}
        reads.append(readers.read_all([doc], {"records": [Rec]}))
    unit = "pods/cycle"
    assert reads[1:] == [{"aggregate_pod_visits": {"value": 3.0,
                                                   "unit": unit}},
                         {"aggregate_pod_visits": {"value": 0.0,
                                                   "unit": unit}}]
    # The cluster's four running pods; the waiting gang's three were
    # counted when the pack asked whether it was ready.
    assert reads[0]["aggregate_pod_visits"]["value"] == 4
