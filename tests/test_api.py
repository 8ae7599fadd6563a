"""Unit tests for the info model (resource algebra, node/podgroup accounting,
snapshot packing) — the analog of the reference's pkg/scheduler/api tests."""

import numpy as np
import pytest

from kai_scheduler_tpu.api import (
    ClusterInfo, NodeInfo, PodGroupInfo, PodInfo, PodSet, PodStatus,
    QueueInfo, QueueQuota, pack, resources as rs,
)
from kai_scheduler_tpu.api.resources import ResourceRequirements


def mknode(name, cpu="8", mem="32Gi", gpu=8, **kw):
    return NodeInfo(name, rs.vec_from_spec(cpu, mem, gpu), **kw)


def mktask(uid, cpu="1", mem="1Gi", gpu=0, gpu_fraction=0.0,
           status=PodStatus.PENDING, **kw):
    return PodInfo(
        uid=uid, name=uid, status=status,
        res_req=ResourceRequirements.from_spec(cpu, mem, gpu,
                                               gpu_fraction=gpu_fraction),
        **kw)


class TestResources:
    def test_parse(self):
        assert rs.parse_cpu("500m") == 500
        assert rs.parse_cpu(2) == 2000
        assert rs.parse_memory("1Gi") == 2 ** 30
        assert rs.parse_memory("1G") == 1e9

    def test_less_equal_unlimited(self):
        a = rs.vec(100, 100, 1)
        b = rs.unlimited()
        assert rs.less_equal(a, b)
        assert not rs.less_equal(a, rs.vec(50, 200, 2))

    def test_fractional_req(self):
        r = ResourceRequirements.from_spec(cpu="1", gpu_fraction=0.5)
        assert r.is_fractional
        assert r.to_vec()[rs.RES_GPU] == 0.5
        r2 = ResourceRequirements.from_spec(gpu_memory="8Gi")
        assert r2.to_vec(node_gpu_memory=16 * 2 ** 30)[rs.RES_GPU] == 0.5
        assert r2.to_vec()[rs.RES_GPU] == 1.0  # conservative w/o node info


class TestNodeInfo:
    def test_accounting_roundtrip(self):
        node = mknode("n1")
        t = mktask("t1", gpu=2, status=PodStatus.RUNNING)
        node.add_task(t)
        assert node.used[rs.RES_GPU] == 2
        assert node.idle[rs.RES_GPU] == 6
        node.remove_task(t)
        assert node.used[rs.RES_GPU] == 0

    def test_releasing_and_pipelined(self):
        node = mknode("n1")
        rel = mktask("rel", gpu=4, status=PodStatus.RELEASING)
        node.add_task(rel)
        # Releasing tasks still occupy the node but their resources are
        # available for pipelining.
        assert node.idle[rs.RES_GPU] == 4
        assert node.releasing[rs.RES_GPU] == 4
        pend = mktask("p", gpu=6)
        assert not node.is_task_allocatable(pend)
        assert node.is_task_allocatable_on_releasing_or_idle(pend)
        pip = mktask("pip", gpu=4, status=PodStatus.PIPELINED)
        node.add_task(pip)
        assert node.releasing[rs.RES_GPU] == 0

    def test_max_pods(self):
        node = mknode("n1", max_pods=1)
        node.add_task(mktask("t1", status=PodStatus.RUNNING))
        assert not node.is_task_allocatable(mktask("t2"))

    def test_fractional_groups(self):
        node = mknode("n1", gpu=2)
        t1 = mktask("f1", gpu_fraction=0.6)

        groups = node.find_gpu_groups_for_task(t1, allow_releasing=False)
        assert groups and len(groups) == 1
        t1.gpu_group = groups[0]
        t1.status = PodStatus.RUNNING
        node.add_task(t1)
        # The whole backing device is charged, not just the fraction.
        assert node.used[rs.RES_GPU] == pytest.approx(1.0)
        # A 0.5 fraction doesn't fit the same device; gets a fresh one.
        t2 = mktask("f2", gpu_fraction=0.5)
        g2 = node.find_gpu_groups_for_task(t2, allow_releasing=False)
        assert g2 and g2[0] != groups[0]
        # A 0.4 fraction packs onto the existing shared device.
        t3 = mktask("f3", gpu_fraction=0.4)
        g3 = node.find_gpu_groups_for_task(t3, allow_releasing=False)
        assert g3 == [groups[0]]

    def test_whole_gpu_blocked_by_sharing_groups(self):
        """Two sharing groups on a 2-GPU node hold both physical devices;
        a whole-GPU task must not be admitted (review finding)."""
        node = mknode("n1", gpu=2)
        for uid, frac in (("a", 0.4), ("b", 0.6)):
            t = mktask(uid, gpu_fraction=frac)
            t.gpu_group = f"grp-{uid}"
            t.status = PodStatus.RUNNING
            node.add_task(t)
        assert node.used[rs.RES_GPU] == pytest.approx(2.0)
        assert not node.is_task_allocatable(mktask("whole", gpu=1))

    def test_pipeline_onto_releasing_group(self):
        """A fully-releasing sharing group frees its whole device for
        pipelining, and releasing fractions don't block the group budget."""
        node = mknode("n1", gpu=1)
        rel = mktask("rel", gpu_fraction=0.8, status=PodStatus.RELEASING)
        rel.gpu_group = "g1"
        node.add_task(rel)
        assert node.releasing[rs.RES_GPU] == pytest.approx(1.0)
        pend = mktask("p", gpu_fraction=0.5)
        assert not node.is_task_allocatable(pend)
        assert node.is_task_allocatable_on_releasing_or_idle(pend)
        g = node.find_gpu_groups_for_task(pend, allow_releasing=True)
        assert g == ["g1"]  # reuses the releasing device, no phantom group


def mktask_frac(uid, fraction):
    return mktask(uid, gpu_fraction=fraction)


class TestPodGroupInfo:
    def _gang(self, n_pods=4, min_available=3):
        pg = PodGroupInfo("pg1", "job1", min_available=min_available)
        for i in range(n_pods):
            pg.add_task(mktask(f"t{i}"))
        return pg

    def test_gang_satisfaction(self):
        pg = self._gang()
        assert not pg.is_gang_satisfied()
        assert pg.is_ready_for_scheduling()
        assert pg.is_elastic()
        for i, t in enumerate(list(pg.pods.values())[:3]):
            pg.update_task_status(t, PodStatus.RUNNING)
        assert pg.is_gang_satisfied()

    def test_tasks_to_allocate_gang_then_elastic(self):
        pg = self._gang(n_pods=5, min_available=3)
        sel = pg.tasks_to_allocate()
        assert len(sel) == 3  # gang chunk first
        for t in sel:
            pg.update_task_status(t, PodStatus.ALLOCATED)
        sel2 = pg.tasks_to_allocate()
        assert len(sel2) == 1  # then elastic, one at a time

    def test_staleness(self):
        pg = self._gang(n_pods=3, min_available=3)
        assert not pg.is_stale()  # nothing running
        pg.update_task_status(list(pg.pods.values())[0], PodStatus.RUNNING)
        assert pg.is_stale()  # 1 of 3 running

    def test_should_pipeline(self):
        pg = self._gang(n_pods=3, min_available=2)
        tasks = list(pg.pods.values())
        pg.update_task_status(tasks[0], PodStatus.PIPELINED)
        assert pg.should_pipeline()
        pg.update_task_status(tasks[1], PodStatus.RUNNING)
        pg.update_task_status(tasks[2], PodStatus.RUNNING)
        assert not pg.should_pipeline()

    def test_signature_dedup(self):
        a, b = self._gang(), self._gang()
        b.uid = "pg2"
        assert a.scheduling_signature() == b.scheduling_signature()
        list(b.pods.values())[0].node_selector["zone"] = "us-1"
        b._signature = None
        assert a.scheduling_signature() != b.scheduling_signature()

    def test_gang_chunks_before_elastic(self):
        """An unsatisfied podset's gang chunk must win over another podset's
        elastic growth (review finding)."""
        pg = PodGroupInfo("pg1", "job1")
        pg.set_pod_sets([PodSet("a", 1), PodSet("b", 2)])
        a_run = mktask("a0", subgroup="a", status=PodStatus.RUNNING)
        pg.add_task(a_run)
        pg.add_task(mktask("a1", subgroup="a"))  # elastic candidate
        pg.add_task(mktask("b0", subgroup="b"))
        pg.add_task(mktask("b1", subgroup="b"))
        sel = pg.tasks_to_allocate()
        assert sorted(t.uid for t in sel) == ["b0", "b1"]

    def test_multi_podset_selection(self):
        pg = PodGroupInfo("pg1", "job1")
        pg.set_pod_sets([PodSet("workers", 2), PodSet("ps", 1)])
        for i in range(3):
            pg.add_task(mktask(f"w{i}", subgroup="workers"))
        pg.add_task(mktask("ps0", subgroup="ps"))
        sel = pg.tasks_to_allocate()
        assert len(sel) == 3  # 2 workers + 1 ps
        by_sg = {}
        for t in sel:
            by_sg.setdefault(t.subgroup, []).append(t)
        assert len(by_sg["workers"]) == 2 and len(by_sg["ps"]) == 1


class TestSnapshotPack:
    def _cluster(self):
        nodes = {f"n{i}": mknode(f"n{i}", labels={"zone": f"z{i % 2}"},
                                 taints={"gpu-only"} if i == 0 else set())
                 for i in range(4)}
        pg = PodGroupInfo("pg1", "j1", queue_id="q1", min_available=2)
        pg.add_task(mktask("t0", gpu=1,
                           node_selector={"zone": "z0"},
                           tolerations={"gpu-only"}))
        pg.add_task(mktask("t1", gpu=1))
        queues = {"q1": QueueInfo("q1", quota=QueueQuota.from_spec(
            deserved=dict(cpu="16", memory="64Gi", gpu=4)))}
        return ClusterInfo(nodes, {"pg1": pg}, queues)

    def test_pack_shapes(self):
        snap = pack(self._cluster())
        assert snap.node_allocatable.shape == (4, rs.NUM_RES)
        assert snap.num_tasks == 2
        assert snap.task_job.tolist() == [0, 0]
        assert snap.job_task_count.tolist() == [2]
        assert snap.queue_deserved[0, rs.RES_GPU] == 4

    def test_pack_padding(self):
        snap = pack(self._cluster(), pad_nodes_to=16)
        assert snap.node_allocatable.shape == (16, rs.NUM_RES)
        # Padded nodes have zero capacity: nothing fits there.
        assert np.all(snap.node_idle[4:] == 0)

    def test_selector_encoding(self):
        snap = pack(self._cluster())
        # t0 constrains zone=z0; node n0/n2 have z0.
        col = 0
        sel = snap.task_selector[0, col]
        assert sel != -1
        assert snap.node_labels[0, col] == sel
        assert snap.node_labels[1, col] != sel

    def test_clone_independent(self):
        ci = self._cluster()
        ci2 = ci.clone()
        t = list(ci2.podgroups["pg1"].pods.values())[0]
        ci2.podgroups["pg1"].update_task_status(t, PodStatus.RUNNING)
        assert ci.podgroups["pg1"].num_active_used() == 0
        assert ci2.podgroups["pg1"].num_active_used() == 1

    def test_clone_rewires_node_accounting(self):
        ci = self._cluster()
        pg = ci.podgroups["pg1"]
        t = pg.pods["t0"]
        t.node_name = "n1"
        pg.update_task_status(t, PodStatus.RUNNING)
        ci.nodes["n1"].add_task(t)
        ci2 = ci.clone()
        assert ci2.nodes["n1"].used[rs.RES_GPU] == 1
        assert len(ci2.nodes["n1"].pod_infos) == 1
        # and the clone's pod ref is the cloned task, not the original
        assert ci2.nodes["n1"].pod_infos["t0"] is ci2.podgroups["pg1"].pods["t0"]


# -- queue aggregates: by count where that is exact, in turn otherwise ------

def _aggregate_cluster(seed, spoiler=None):
    """Gangs of pods sharing requirement objects over three queues, in
    every status; ``spoiler`` adds one pod the count cannot take."""
    rng = np.random.default_rng(seed)
    statuses = [PodStatus.PENDING, PodStatus.RUNNING, PodStatus.ALLOCATED,
                PodStatus.PIPELINED, PodStatus.RELEASING, PodStatus.GATED]
    nodes = {f"n{i}": mknode(f"n{i}", cpu="64", mem="512Gi") for i in range(4)}
    queues = {q: QueueInfo(q, quota=QueueQuota.from_spec())
              for q in ("qa", "qb", "qc")}
    podgroups = {}
    for g in range(int(rng.integers(3, 9))):
        pg = PodGroupInfo(f"pg{g}", f"pg{g}",
                          queue_id=("qa", "qb", "qc", "lost")[g % 4])
        shapes = [ResourceRequirements.from_spec(
            f"{int(rng.integers(100, 9000))}m",
            f"{int(rng.integers(1, 64))}Gi", int(rng.integers(0, 9)))
            for _ in range(2)]
        for k in range(int(rng.integers(1, 40))):
            pg.add_task(PodInfo(
                uid=f"pg{g}-{k}", name=f"pg{g}-{k}",
                status=statuses[int(rng.integers(len(statuses)))],
                res_req=shapes[k % 2]))
        podgroups[pg.uid] = pg
    if spoiler is not None:
        podgroups["pg0"].add_task(PodInfo(
            uid="spoiler", name="spoiler", status=PodStatus.RUNNING,
            res_req=spoiler))
    return ClusterInfo(nodes, podgroups, queues)


def _same_bits(a, b):
    assert a.keys() == b.keys()
    for q in a:
        assert a[q].dtype == b[q].dtype and a[q].tobytes() == b[q].tobytes()


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_queue_aggregates_by_count_equal_those_in_turn(seed):
    ci = _aggregate_cluster(seed)
    counted = ci._aggregates_by_count()
    assert counted is not None
    in_turn = ci._aggregates_in_turn()
    _same_bits(counted[0], in_turn[0])
    _same_bits(counted[1], in_turn[1])
    assert "lost" not in counted[0]
    assert ci.queue_aggregates()[0]["qa"].tobytes() \
        == in_turn[0]["qa"].tobytes()


@pytest.mark.parametrize("spoiler", [
    ResourceRequirements.from_spec("1", "1Gi", 0, gpu_fraction=0.3),
    ResourceRequirements.from_spec("1", "1Gi", 0, gpu_memory="4Gi"),
    ResourceRequirements.from_spec("0.0005", "1Gi", 0),
    # An odd byte count: with the queue's whole Gi beside it the total is
    # 2**53 of its unit, one byte, or more.
    ResourceRequirements(base=np.array([1000.0, 2.0 ** 53 - 1.0, 0.0])),
], ids=["fraction", "gpu_memory", "half_a_millicore", "past_2_53"])
def test_queue_aggregates_take_turns_where_a_count_is_not_exact(spoiler):
    ci = _aggregate_cluster(5, spoiler)
    assert ci._aggregates_by_count() is None
    got = ci.queue_aggregates()
    want = ci._aggregates_in_turn()
    _same_bits(got[0], want[0])
    _same_bits(got[1], want[1])


@pytest.mark.parametrize("bytes_asked", [2.0 ** 53, 2.0 ** 53 + 2.0 ** 35,
                                         2.0 ** 62],
                         ids=["2_53", "2_53_and_32gi", "2_62"])
def test_queue_aggregates_count_past_2_53_bytes_in_whole_gi(bytes_asked):
    """Every request a whole multiple of 2**30 bytes and fewer than 2**53
    of them a queue: counted, and the additions in turn to the bit."""
    ci = _aggregate_cluster(5, ResourceRequirements(
        base=np.array([1000.0, bytes_asked, 0.0])))
    counted = ci._aggregates_by_count()
    assert counted is not None
    assert counted.requested["qa"][1] >= 2.0 ** 53
    assert counted.unit["qa"][1] >= 2.0 ** 30
    in_turn = ci._aggregates_in_turn()
    _same_bits(counted[0], in_turn[0])
    _same_bits(counted[1], in_turn[1])


# -- the counts a PodGroup keeps (PodGroupInfo.queue_counts) ----------------

def _held_to_a_walk(ci):
    """What the cluster answers now, against a walk over every pod: a
    fresh ClusterInfo over ``clone()``d objects, which have nothing kept
    and one requirements object a pod, and the additions in turn."""
    got, rollup = ci.queue_aggregates(), ci.queue_rollup()
    want = ci.clone().queue_rollup()
    assert rollup is not None and want is not None
    _same_bits(got[0], rollup.allocated)
    _same_bits(got[1], rollup.requested)
    for field in ("allocated", "requested", "non_preemptible", "unit"):
        _same_bits(getattr(rollup, field), getattr(want, field))
    assert rollup.adds == want.adds
    in_turn = ci._aggregates_in_turn()
    _same_bits(got[0], in_turn[0])
    _same_bits(got[1], in_turn[1])


class _Churn:
    """A client's and a session's moves on one persistent cluster: every
    way a pod's status, a PodGroup's pods, its queue or its guarantee
    change between two reads of the queue sums."""

    QUEUES = ("qa", "qb", "qc", "lost")

    def __init__(self, seed):
        self.rng = np.random.default_rng(seed)
        nodes = {f"n{i}": mknode(f"n{i}", cpu="512", mem="4096Gi", gpu=64)
                 for i in range(6)}
        queues = {q: QueueInfo(q, quota=QueueQuota.from_spec())
                  for q in self.QUEUES[:3]}
        self.shapes = [ResourceRequirements.from_spec(
            f"{int(self.rng.integers(1, 20)) * 100}m",
            f"{int(self.rng.integers(1, 8))}Gi", int(self.rng.integers(0, 2)))
            for _ in range(3)]
        self.seq = 0
        self.ci = ClusterInfo(nodes, {}, queues)
        for _ in range(5):
            self.arrive(running=True)

    def pick(self, items):
        items = list(items)
        return items[int(self.rng.integers(len(items)))] if items else None

    def pod(self, **kw):
        self.seq += 1
        return PodInfo(uid=f"p{self.seq}", name=f"p{self.seq}",
                       res_req=self.pick(self.shapes[:2] if self.seq % 3
                                         else self.shapes), **kw)

    # -- the client's moves -------------------------------------------------
    def arrive(self, running=False):
        self.seq += 1
        pg = PodGroupInfo(f"pg{self.seq}", f"pg{self.seq}",
                          queue_id=self.pick(self.QUEUES),
                          preemptible=bool(self.rng.integers(2)))
        for _ in range(int(self.rng.integers(1, 7))):
            if running:
                task = self.pod(status=PodStatus.RUNNING,
                                node_name=self.pick(self.ci.nodes))
                self.ci.nodes[task.node_name].add_task(task)
            else:
                task = self.pod()
            pg.add_task(task)
        self.ci.podgroups[pg.uid] = pg
        self.ci.invalidate_aggregates()

    def leave(self):
        pg = self.pick(self.ci.podgroups.values())
        if pg is None:
            return
        for task in pg.pods.values():
            if task.node_name:
                self.ci.nodes[task.node_name].remove_task(task)
        del self.ci.podgroups[pg.uid]
        self.ci.invalidate_aggregates()

    def one_more_pod(self):
        pg = self.pick(self.ci.podgroups.values())
        if pg is not None:
            pg.add_task(self.pod())
            self.ci.invalidate_aggregates()

    def gate(self):
        unplaced = [(pg, t) for pg in self.ci.podgroups.values()
                    for t in pg.pods.values() if not t.node_name]
        if unplaced:
            pg, task = self.pick(unplaced)
            pg.update_task_status(task, self.pick(
                (PodStatus.PENDING, PodStatus.GATED, PodStatus.FAILED)))
            self.ci.invalidate_aggregates()

    def requeue(self):
        pg = self.pick(self.ci.podgroups.values())
        if pg is not None:
            pg.queue_id = self.pick(self.QUEUES)
            self.ci.invalidate_aggregates()

    def guarantee(self):
        pg = self.pick(self.ci.podgroups.values())
        if pg is not None:
            pg.preemptible = not pg.preemptible
            self.ci.invalidate_aggregates()

    def invalidate(self):
        self.ci.invalidate_aggregates()

    # -- a session's moves --------------------------------------------------
    def statement(self):
        """A statement's allocations, pipelines and evictions, read
        between them, then left standing, undone in part or undone."""
        from kai_scheduler_tpu.framework.conf import SchedulerConfig
        from kai_scheduler_tpu.framework.session import (InMemoryCache,
                                                         Session)
        ssn = Session(self.ci, SchedulerConfig(), InMemoryCache())
        stmt = ssn.statement()
        marks = [stmt.checkpoint()]
        for _ in range(int(self.rng.integers(1, 6))):
            tasks = [t for pg in self.ci.podgroups.values()
                     for t in pg.pods.values()]
            pending = [t for t in tasks if t.status == PodStatus.PENDING]
            placed = [t for t in tasks if t.node_name
                      and t.status in (PodStatus.RUNNING,
                                       PodStatus.ALLOCATED)]
            move = self.pick(("allocate", "pipeline", "evict"))
            if move == "evict" and placed:
                stmt.evict(self.pick(placed))
            elif move != "evict" and pending:
                getattr(stmt, move)(self.pick(pending),
                                    self.pick(self.ci.nodes))
            marks.append(stmt.checkpoint())
            _held_to_a_walk(self.ci)
        ending = self.pick(("stands", "part", "undone"))
        if ending != "stands":
            stmt.rollback(0 if ending == "undone" else self.pick(marks))


@pytest.mark.parametrize("seed", range(8))
def test_kept_queue_counts_equal_a_walk_after_any_moves(seed):
    from kai_scheduler_tpu.utils.metrics import METRICS
    churn = _Churn(seed)
    _held_to_a_walk(churn.ci)
    moves = ("arrive", "leave", "one_more_pod", "gate", "requeue",
             "guarantee", "invalidate", "statement", "statement")
    visits0 = METRICS.counters["queue_aggregate_pod_visits_total"]
    pods = 0
    for _ in range(40):
        getattr(churn, churn.pick(moves))()
        _held_to_a_walk(churn.ci)
        pods += sum(len(pg.pods) for pg in churn.ci.podgroups.values())
    # The clones are counted every time; the cluster itself far less than
    # a walk a read would have.
    visits = METRICS.counters["queue_aggregate_pod_visits_total"] - visits0
    assert visits < 2 * pods


@pytest.mark.parametrize("spoiler", [
    ResourceRequirements.from_spec("1", "1Gi", 0, gpu_fraction=0.3),
    ResourceRequirements.from_spec("1", "1Gi", 0, gpu_memory="4Gi"),
    ResourceRequirements.from_spec("0.0005", "1Gi", 0),
    ResourceRequirements(base=np.array([1000.0, 2.0 ** 53 - 1.0, 0.0])),
], ids=["fraction", "gpu_memory", "half_a_millicore", "past_2_53"])
def test_a_pod_the_count_cannot_take_ends_what_was_kept(spoiler):
    ci = _aggregate_cluster(6)
    assert ci.queue_rollup() is not None
    kept = {uid: pg._queue_counts for uid, pg in ci.podgroups.items()
            if pg.queue_id in ci.queues}
    assert kept and None not in kept.values()
    ci.podgroups["pg0"].add_task(PodInfo(
        uid="spoiler", name="spoiler", status=PodStatus.RUNNING,
        res_req=spoiler))
    ci.invalidate_aggregates()
    assert ci.queue_rollup() is None
    got, want = ci.queue_aggregates(), ci._aggregates_in_turn()
    _same_bits(got[0], want[0])
    _same_bits(got[1], want[1])
    # The spoiler gone, the others' counts are still theirs.
    ci.podgroups["pg0"].update_task_status(
        ci.podgroups["pg0"].pods["spoiler"], PodStatus.SUCCEEDED)
    ci.invalidate_aggregates()
    assert ci.queue_rollup() is not None
    assert all(ci.podgroups[uid]._queue_counts is kept[uid]
               for uid in kept if uid != "pg0")
    _held_to_a_walk(ci)


def test_queue_counts_are_per_requirements_object_and_dropped_by_the_door():
    pg = PodGroupInfo("pg", "pg")
    small, large = (ResourceRequirements.from_spec("1", "1Gi", g)
                    for g in (0, 1))
    tasks = [mktask(f"t{k}", status=s) for k, s in enumerate(
        (PodStatus.RUNNING, PodStatus.PENDING, PodStatus.PENDING,
         PodStatus.GATED, PodStatus.SUCCEEDED))]
    for k, task in enumerate(tasks):
        task.res_req = large if k == 2 else small
        pg.add_task(task)
    assert pg.queue_counts() == (small, 1, 1, large, 0, 1)
    assert pg.queue_counts() is pg.queue_counts()
    pg.update_task_status(tasks[3], PodStatus.PENDING)
    assert pg._queue_counts is None
    assert pg.queue_counts() == (small, 1, 2, large, 0, 1)
    pg.add_task(mktask("t9", status=PodStatus.BOUND))
    assert pg._queue_counts is None and len(pg.queue_counts()) == 9
    assert pg.clone()._queue_counts is None
