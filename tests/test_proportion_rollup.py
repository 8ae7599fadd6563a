"""The proportion plugin's queue roll-up, counted once a cycle.

``_build_queue_attributes`` takes each leaf's ``allocated``, ``request``
and ``allocated_non_preemptible`` from the one pod walk the cycle already
made (``ClusterInfo.queue_rollup``, counted per requirement object) and
adds them to the leaf and its ancestors.  Where the count is proven exact
that is the pod-by-pod walk to the bit, at every queue, and so is the fair
share computed from it; where it is not (a fractional or gpu-memory
request, a total past 2**53 at a leaf or an ancestor, sums a snapshot
builder pre-filled) the walk runs as before.  A columnar snapshot keeps
its own vectorised roll-up.
"""

import numpy as np
import pytest

from kai_scheduler_tpu.api import (ClusterInfo, NodeInfo, PodGroupInfo,
                                   PodInfo, PodStatus, QueueInfo)
from kai_scheduler_tpu.api import resources as rs
from kai_scheduler_tpu.api.resources import ResourceRequirements
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.framework.session import Session

ATTRS = ("allocated", "request", "allocated_non_preemptible", "fair_share")
STATUSES = (PodStatus.PENDING, PodStatus.RUNNING, PodStatus.RELEASING,
            PodStatus.GATED, PodStatus.BOUND, PodStatus.SUCCEEDED)


def _queues():
    """Two departments under one root, three leaves each; one leaf with
    no PodGroup and one queue outside the tree."""
    queues = {"root": QueueInfo("root")}
    for d in range(2):
        queues[f"d{d}"] = QueueInfo(f"d{d}", parent="root")
        for k in range(3):
            queues[f"d{d}-l{k}"] = QueueInfo(f"d{d}-l{k}", parent=f"d{d}")
    queues["alone"] = QueueInfo("alone")
    for name, q in queues.items():
        if q.parent:
            queues[q.parent].children.append(name)
    return queues


def _fleet(seed, spoiler=None, nodes=24):
    """Gangs of whole-number requests in every leaf: preemptible and not,
    pods pending, running, releasing, gated and gone, requirement objects
    shared within a gang and distinct ones besides.  ``spoiler``: one more
    pending pod with that requirement."""
    rng = np.random.default_rng(seed)
    node_objs = {f"n{i:02d}": NodeInfo(
        f"n{i:02d}", rs.vec_from_spec("64", "512Gi", 8),
        gpu_memory_per_device=16 * 2 ** 30) for i in range(nodes)}
    queues = _queues()
    leaves = [q for q in queues if "-l" in q and q != "d1-l2"] + ["alone"]
    podgroups = {}
    for g in range(12):
        shared = [ResourceRequirements.from_spec(
            str(int(rng.integers(1, 5))), f"{int(rng.integers(1, 9))}Gi",
            int(rng.integers(0, 3))) for _ in range(2)]
        pg = PodGroupInfo(f"pg{g}", f"pg{g}",
                          queue_id=leaves[int(rng.integers(len(leaves)))],
                          min_available=1, preemptible=bool(g % 3))
        for k in range(int(rng.integers(3, 9))):
            status = STATUSES[int(rng.integers(len(STATUSES)))]
            req = shared[k % 2] if k % 4 else \
                ResourceRequirements.from_spec("1500m", "3Gi", 1)
            placed = status not in (PodStatus.PENDING, PodStatus.GATED)
            pg.add_task(PodInfo(
                uid=f"pg{g}-{k}", name=f"pg{g}-{k}", res_req=req,
                status=status,
                node_name=f"n{int(rng.integers(nodes)):02d}"
                if placed else ""))
        podgroups[pg.uid] = pg
    podgroups["ghost"] = PodGroupInfo("ghost", "ghost", queue_id="no-such")
    podgroups["ghost"].add_task(PodInfo(
        uid="ghost-0", name="ghost-0",
        res_req=ResourceRequirements.from_spec("1", "1Gi", 1)))
    if spoiler is not None:
        podgroups["pg0"].add_task(PodInfo(
            uid="spoiler", name="spoiler", res_req=spoiler))
    return ClusterInfo(node_objs, podgroups, queues)


def _open(cluster, walked=False):
    """A session over ``cluster``; ``walked``: with the sums a snapshot
    builder pre-fills (taken in turn, so not handed to the plugin)."""
    ssn = Session(cluster, SchedulerConfig())
    if walked:
        cluster._queue_aggregates = cluster._aggregates_in_turn()
    return ssn.open()


def _assert_same_bits(a, b):
    assert sorted(a.queues) == sorted(b.queues)
    for qid, qa in a.queues.items():
        qb = b.queues[qid]
        for attr in ATTRS:
            va, vb = getattr(qa, attr), getattr(qb, attr)
            assert va.dtype == vb.dtype and va.tobytes() == vb.tobytes(), \
                (qid, attr, va, vb)
        assert qa.version == qb.version, qid


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_counted_rollup_is_the_walk_to_the_bit(seed):
    counted = _open(_fleet(seed)).proportion
    walked = _open(_fleet(seed), walked=True).proportion
    assert (counted.rollup, walked.rollup) == ("counted", "walked")
    _assert_same_bits(counted, walked)
    # Not vacuous: every kind of sum is there, at a leaf and above it.
    root = counted.queues["root"]
    assert root.allocated.any() and root.allocated_non_preemptible.any()
    assert (root.request >= root.allocated).all() \
        and (root.request != root.allocated).any()
    assert (root.allocated != root.allocated_non_preemptible).any()
    assert not counted.queues["d1-l2"].request.any()
    assert counted.queues["d1-l2"].version == 0
    assert root.version == sum(
        counted.queues[d].version for d in ("d0", "d1")) > 0


@pytest.mark.parametrize("spoiler", [
    ResourceRequirements.from_spec("1", "1Gi", 0, gpu_fraction=0.3),
    ResourceRequirements.from_spec("1", "1Gi", 0, gpu_memory="4Gi"),
    ResourceRequirements.from_spec("0.0005", "1Gi", 0),
    ResourceRequirements(base=np.array([1000.0, 2.0 ** 53, 0.0])),
], ids=["fraction", "gpu_memory", "half_a_millicore", "leaf_past_2_53"])
def test_requests_a_count_cannot_prove_take_the_walk(spoiler):
    plugin = _open(_fleet(7, spoiler)).proportion
    assert plugin.rollup == "walked"
    _assert_same_bits(plugin, _open(_fleet(7, spoiler), True).proportion)


def test_ancestor_total_past_2_53_takes_the_walk():
    """Each leaf's total is exact and their department's is not."""
    half = ResourceRequirements(base=np.array([1000.0, 2.0 ** 52, 0.0]))

    def fleet():
        cluster = _fleet(8)
        for k, pg in enumerate(("pg0", "pg1")):
            cluster.podgroups[pg].queue_id = f"d0-l{k}"
            cluster.podgroups[pg].add_task(PodInfo(
                uid=f"half-{k}", name=f"half-{k}", res_req=half))
        return cluster

    assert fleet().queue_rollup() is not None
    plugin = _open(fleet()).proportion
    assert plugin.rollup == "walked"
    assert plugin.queues["d0"].request[rs.RES_MEM] >= 2.0 ** 53
    _assert_same_bits(plugin, _open(fleet(), walked=True).proportion)


def _bind_by_statement(ssn, cluster):
    task = next(t for t in cluster.podgroups["pg1"].pods.values()
                if t.status == PodStatus.PENDING)
    st = ssn.statement()
    st.allocate(task, "n03")
    st.commit()


def _bind_by_client(ssn, cluster):
    task = next(t for t in cluster.podgroups["pg1"].pods.values()
                if t.status == PodStatus.PENDING)
    task.node_name = "n03"
    cluster.podgroups["pg1"].update_task_status(task, PodStatus.RUNNING)
    cluster.nodes["n03"].add_task(task)
    cluster.invalidate_aggregates()


def _arrive_by_client(ssn, cluster):
    pg = PodGroupInfo("late", "late", queue_id="d1-l2", preemptible=False)
    for k in range(3):
        pg.add_task(PodInfo(
            uid=f"late-{k}", name=f"late-{k}",
            res_req=ResourceRequirements.from_spec("2", "4Gi", 1)))
    cluster.podgroups["late"] = pg
    cluster.invalidate_aggregates()


@pytest.mark.parametrize("move", [_bind_by_statement, _bind_by_client,
                                  _arrive_by_client],
                         ids=["statement", "client_bind", "client_arrival"])
def test_invalidation_between_pack_and_open_is_honoured(move):
    """The pack memoizes the sums; what moves before ``open`` and says so
    (a Statement does, a client calls ``invalidate_aggregates``) is
    counted afresh, not read from the pack's memo."""
    def opened(walked):
        cluster = _fleet(9)
        cluster.podgroups["pg1"].add_task(PodInfo(
            uid="waiting", name="waiting",
            res_req=ResourceRequirements.from_spec("1", "1Gi", 1)))
        ssn = Session(cluster, SchedulerConfig())
        before = cluster.queue_rollup()
        assert before is not None
        move(ssn, cluster)
        assert getattr(cluster, "_queue_aggregates", None) is None
        if walked:
            cluster._queue_aggregates = cluster._aggregates_in_turn()
        plugin = ssn.open().proportion
        leaf = "d1-l2" if move is _arrive_by_client \
            else cluster.podgroups["pg1"].queue_id
        moved = "requested" if move is _arrive_by_client else "allocated"
        attr = "request" if move is _arrive_by_client else "allocated"
        assert getattr(plugin.queues[leaf], attr).tobytes() \
            != getattr(before, moved)[leaf].tobytes()
        return plugin

    counted, walked = opened(False), opened(True)
    assert (counted.rollup, walked.rollup) == ("counted", "walked")
    _assert_same_bits(counted, walked)


def test_prefilled_memo_keeps_the_shape_of_the_counted_one():
    """What a snapshot builder pre-fills (``ClusterCache``'s columnar
    build) unpacks as the counted memo does and is never handed to the
    plugin as counted."""
    cluster = _fleet(10)
    counted = cluster._aggregates()
    assert counted is cluster.queue_rollup()
    assert counted._fields == ("allocated", "requested",
                               "non_preemptible", "adds")
    in_turn = cluster._aggregates_in_turn()
    cluster._queue_aggregates = in_turn
    assert cluster.queue_rollup() is None
    allocated, requested = cluster.queue_aggregates()
    assert allocated is in_turn.allocated and requested is in_turn.requested
    for qid in cluster.queues:
        assert allocated[qid].tobytes() == counted.allocated[qid].tobytes()
        assert requested[qid].tobytes() == counted.requested[qid].tobytes()


@pytest.mark.parametrize("columnar", [True, False],
                         ids=["columnar", "object_path"])
def test_columnar_snapshot_keeps_its_own_rollup(columnar, monkeypatch):
    """A columnar ``ClusterCache`` snapshot pre-fills the sums in the
    memo's shape and the plugin rolls up from the batch; a snapshot
    parsed into objects has no batch and is counted."""
    from kai_scheduler_tpu.api.cluster_info import QueueAggregates
    from kai_scheduler_tpu.controllers import InMemoryKubeAPI
    from kai_scheduler_tpu.controllers.cache_builder import ClusterCache
    from test_incremental_cache import seed_cluster
    monkeypatch.setenv("KAI_COLUMNAR", "1" if columnar else "0")
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    cache.snapshot()
    cluster = cache.snapshot()
    path = cache.last_columnar_stats.get("path")
    batch = getattr(cluster, "columnar_batch", None)
    memo = getattr(cluster, "_queue_aggregates", None)
    ssn = Session(cluster, SchedulerConfig(), cache).open()
    if not columnar:
        assert batch is None and path != "columnar"
        assert ssn.proportion.rollup == "counted"
    else:
        assert batch is not None and path == "columnar"
        assert isinstance(memo, QueueAggregates)
        assert memo.non_preemptible is None and memo.adds is None
        assert ssn.proportion.rollup == "columnar"
    ref = Session(cluster.clone(), SchedulerConfig())
    ref.cluster._queue_aggregates = ref.cluster._aggregates_in_turn()
    _assert_same_bits(ssn.proportion, ref.open().proportion)


def test_span_says_how_the_rollup_was_taken():
    """``plugin:proportion`` carries ``rollup`` cycle by cycle: counted
    while every request is whole, walked from the cycle a fractional pod
    arrives."""
    from kai_scheduler_tpu.utils.tracing import TRACER
    from tests.test_snapshot_delta import BareLoop

    def rollup():
        (span,) = [s for s in TRACER.get_trace().spans
                   if s.name == "plugin:proportion"]
        return span.attrs["rollup"]

    loop = BareLoop()
    seen = []
    for _ in range(3):
        loop.arrive(2)
        loop.cycle()
        seen.append(rollup())
    loop.arrive(1, gpu=0, gpu_fraction=0.5)
    loop.cycle()
    seen.append(rollup())
    assert seen == ["counted", "counted", "counted", "walked"]
