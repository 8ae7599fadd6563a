"""The proportion plugin's queue roll-up, counted once a cycle.

``_build_queue_attributes`` takes each leaf's ``allocated``, ``request``
and ``allocated_non_preemptible`` from the one pod walk the cycle already
made (``ClusterInfo.queue_rollup``, counted per requirement object) and
adds them to the leaf and its ancestors.  Where the count is proven exact
that is the pod-by-pod walk to the bit, at every queue, and so is the fair
share computed from it.  The proof: every request is a non-negative whole
multiple of a power of two, its unit, taken column by column.  A sum of
such multiples is exact in float64, in any order, while it stays under
2**53 of them.  Where that fails (a fractional or gpu-memory request, a
total of 2**53 units or more at a leaf or an ancestor, sums a snapshot
builder pre-filled) the walk runs as before.  A columnar snapshot keeps
its own vectorised roll-up.
"""

import numpy as np
import pytest

from kai_scheduler_tpu.api import (ClusterInfo, NodeInfo, PodGroupInfo,
                                   PodInfo, PodStatus, QueueInfo)
from kai_scheduler_tpu.api import resources as rs
from kai_scheduler_tpu.api.cluster_info import _unit_of, sums_exact
from kai_scheduler_tpu.api.resources import ResourceRequirements
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.framework.session import Session
from kai_scheduler_tpu.utils.metrics import METRICS

WALKED = "proportion_rollup_walked_total"

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
    # An odd byte count: the leaf's unit is one byte, and with its whole Gi
    # beside this it asks more than 2**53 of them.
    ResourceRequirements(base=np.array([1000.0, 2.0 ** 53 - 1.0, 0.0])),
], ids=["fraction", "gpu_memory", "half_a_millicore", "leaf_past_2_53"])
def test_requests_a_count_cannot_prove_take_the_walk(spoiler):
    plugin = _open(_fleet(7, spoiler)).proportion
    assert plugin.rollup == "walked"
    _assert_same_bits(plugin, _open(_fleet(7, spoiler), True).proportion)


def test_ancestor_total_past_2_53_takes_the_walk():
    """Each leaf's total is exact and their department's is not: an odd
    byte count a leaf keeps the unit at one byte, and the two together
    pass 2**53 of them."""
    half = ResourceRequirements(base=np.array([1000.0, 2.0 ** 52 + 1.0, 0.0]))

    def fleet():
        cluster = _fleet(8)
        for k, pg in enumerate(("pg0", "pg1")):
            cluster.podgroups[pg].queue_id = f"d0-l{k}"
            cluster.podgroups[pg].add_task(PodInfo(
                uid=f"half-{k}", name=f"half-{k}", res_req=half))
        return cluster

    counted = fleet().queue_rollup()
    assert counted is not None
    assert all(counted.unit[leaf][rs.RES_MEM] == 1.0
               and counted.requested[leaf][rs.RES_MEM] < 2.0 ** 53
               for leaf in ("d0-l0", "d0-l1"))
    plugin = _open(fleet()).proportion
    assert plugin.rollup == "walked"
    assert plugin.queues["d0"].request[rs.RES_MEM] >= 2.0 ** 53
    _assert_same_bits(plugin, _open(fleet(), walked=True).proportion)


GI = 2.0 ** 30


def _tree(asks, leaves=3):
    """``_queues()`` (or a department of ``leaves`` leaves) with one
    PodGroup an entry of ``asks``: (leaf, pods, [milli-cores, bytes, GPUs]).
    The pods of a group share one requirement object; every third is
    pending, the others run, and every other group is not preemptible."""
    queues = _queues()
    for k in range(3, leaves):
        queues[f"d0-l{k}"] = QueueInfo(f"d0-l{k}", parent="d0")
        queues["d0"].children.append(f"d0-l{k}")
    nodes = {f"n{i}": NodeInfo(f"n{i}", rs.vec_from_spec("64", "512Gi", 8))
             for i in range(4)}
    podgroups = {}
    for g, (leaf, pods, vec) in enumerate(asks):
        req = ResourceRequirements(base=np.array(vec, float))
        pg = PodGroupInfo(f"pg{g}", f"pg{g}", queue_id=leaf,
                          min_available=1, preemptible=bool(g % 2))
        for k in range(pods):
            pg.add_task(PodInfo(
                uid=f"pg{g}-{k}", name=f"pg{g}-{k}", res_req=req,
                status=PodStatus.PENDING if k % 3 == 2
                else PodStatus.RUNNING))
        podgroups[pg.uid] = pg
    return ClusterInfo(nodes, podgroups, queues)


def _north_star_in_small():
    """``north-star-98k``'s queue tree: one occupier leaf of many small
    pods and three sibling leaves of a few large ones, 32 Gi and 256 Gi
    scaled by 2**14 so that no leaf asks 2**53 bytes and their department
    asks 2.25 times that."""
    scale = 2.0 ** 14
    return [("d0-l0", 12, [4000.0, 32 * GI * scale, 1.0])] + [
        (f"d0-l{k}", 2, [32000.0, 256 * GI * scale / 2, 8.0])
        for k in (1, 2, 3)] + [("d1-l0", 4, [4000.0, 32 * GI, 1.0])]


# id -> (the asks, what the roll-up must say, the queue whose bytes pass
# 2**53 or None, leaves in d0)
PAST_2_53 = {
    "leaf_in_gi": ([("d0-l0", 1, [1000.0, 2.0 ** 53, 0.0]),
                    ("d0-l0", 3, [2000.0, 3 * GI, 1.0])],
                   "counted", "d0-l0", 3),
    "leaf_in_32gi": ([("d0-l0", 5, [4000.0, 2.0 ** 51, 1.0]),
                      ("d0-l0", 3, [4000.0, 3 * 32 * GI, 1.0])],
                     "counted", "d0-l0", 3),
    "ancestor_in_gi": ([("d0-l0", 1, [1000.0, 2.0 ** 52 + GI, 0.0]),
                        ("d0-l1", 1, [1000.0, 2.0 ** 52 + 5 * GI, 0.0]),
                        ("d1-l0", 2, [1000.0, 7 * GI, 1.0])],
                       "counted", "d0", 3),
    "ancestor_in_32gi": ([("d0-l0", 3, [4000.0, 2.0 ** 51 + 32 * GI, 1.0]),
                          ("d0-l1", 3, [4000.0, 2.0 ** 51, 1.0]),
                          ("d0-l2", 2, [32000.0, 256 * GI, 8.0])],
                         "counted", "d0", 3),
    "north_star_in_small": (_north_star_in_small(), "counted", "d0", 4),
    "odd_bytes_beside_2_53": ([("d0-l0", 1, [1000.0, 2.0 ** 53, 0.0]),
                               ("d0-l0", 1, [1000.0, 3.0, 0.0])],
                              "walked", "d0-l0", 3),
    "odd_bytes_in_a_sibling": ([("d0-l0", 1, [1000.0, 2.0 ** 53, 0.0]),
                                ("d0-l1", 1, [1000.0, 3.0, 0.0])],
                               "walked", "d0", 3),
    "exactly_2_53_units": ([("d0-l0", 1, [1000.0, (2.0 ** 53 - 1) * GI, 0.0]),
                            ("d0-l0", 1, [1000.0, GI, 0.0])],
                           "walked", "d0-l0", 3),
    "one_unit_under_2_53": ([("d0-l0", 1, [1000.0, (2.0 ** 53 - 2) * GI, 0.0]),
                             ("d0-l0", 1, [1000.0, GI, 0.0])],
                            "counted", "d0-l0", 3),
    "exactly_2_53_units_above": ([("d0-l0", 1, [1000.0, 2.0 ** 52 * GI, 0.0]),
                                  ("d0-l1", 1, [0.0, (2.0 ** 52 - 1) * GI, 0.0]),
                                  ("d0-l2", 1, [0.0, GI, 0.0])],
                                 "walked", "d0", 3),
    "a_unit_a_column": ([("d0-l0", 3, [1001.0, 2.0 ** 52 + 2.0 ** 35, 1.0]),
                         ("d0-l1", 3, [333.0, 2.0 ** 52, 3.0])],
                        "counted", "d0", 3),
    "odd_millicores_past_2_53": ([("d0-l0", 2, [2.0 ** 52 + 1, 2.0 ** 35, 1.0]),
                                  ("d0-l1", 2, [2.0 ** 52, 2.0 ** 35, 1.0])],
                                 "walked", None, 3),
    "a_request_over_2_53": ([("d0-l0", 1, [1000.0, 2.0 ** 53 + 2, 0.0]),
                             ("d0-l1", 1, [1000.0, 6.0, 0.0])],
                            "counted", "d0-l0", 3),
    "a_column_of_zeros": ([("d0-l0", 4, [0.0, 2.0 ** 52, 0.0]),
                           ("d0-l1", 4, [0.0, 2.0 ** 52, 0.0])],
                          "counted", "d0", 3),
}


@pytest.mark.parametrize("case", PAST_2_53)
def test_totals_past_2_53_bytes_are_judged_in_the_requests_own_unit(case):
    """Every request a whole multiple of a power of two and under 2**53 of
    them at every queue: counted, and the walk to the bit at every leaf and
    ancestor; a column whose unit leaves 2**53 of them or more walks."""
    asks, rollup, past, leaves = PAST_2_53[case]
    plugin = _open(_tree(asks, leaves)).proportion
    assert plugin.rollup == rollup
    if past is not None:
        assert plugin.queues[past].request[rs.RES_MEM] >= 2.0 ** 53
    assert plugin.queues["root"].allocated.any()
    _assert_same_bits(plugin, _open(_tree(asks, leaves), True).proportion)


def test_north_star_in_small_passes_2_53_at_the_department_alone():
    counted = _tree(_north_star_in_small(), 4).queue_rollup()
    assert all(total[rs.RES_MEM] < 2.0 ** 53
               for total in counted.requested.values())
    assert counted.unit["d0-l0"][rs.RES_MEM] == 32 * GI * 2.0 ** 14
    assert counted.unit["d0-l1"][rs.RES_MEM] == 128 * GI * 2.0 ** 14
    assert counted.unit["d1-l2"][rs.RES_MEM] == 1.0     # nobody asks there


def test_unit_is_looked_for_only_past_2_53():
    """While the cluster's whole demand is under 2**53 the unit 1 of whole
    numbers proves every total and no request's own is taken."""
    counted = _fleet(1).queue_rollup()
    assert all(unit.tolist() == [1.0, 1.0, 1.0]
               for unit in counted.unit.values())
    past = _tree(PAST_2_53["a_column_of_zeros"][0]).queue_rollup()
    assert past.unit["d0-l0"].tolist() == [np.inf, 2.0 ** 52, np.inf]


@pytest.mark.parametrize("vectors, unit", [
    ([[6.0, 2.0 ** 53 + 2, 0.0], [4000.0, 3 * 2.0 ** 35, 0.0]],
     [2.0, 2.0, np.inf]),
    ([[1.0, 2.0 ** 60, 8.0]], [1.0, 2.0 ** 60, 8.0]),
    ([[1001.0, 32 * GI, 1.0], [32000.0, 256 * GI, 8.0]],
     [1.0, 32 * GI, 1.0]),
    ([[0.0, 0.0, 0.0]], [np.inf, np.inf, np.inf]),
], ids=["over_2_53", "a_power_itself", "the_98k_fleets", "zeros"])
def test_unit_of_a_column(vectors, unit):
    assert _unit_of(vectors).tolist() == unit


@pytest.mark.parametrize("seed", range(8))
def test_a_proven_sum_is_exact_in_any_order(seed):
    """The property the proof rests on, against whole-number arithmetic:
    units of 2**0 to 2**40 a column, up to 2**20 pods a requirement
    object.  Where ``sums_exact`` says yes, count times vector, the pods
    one by one and the same pods backwards all give the true sum."""
    rng = np.random.default_rng([seed, 46])
    proven = 0
    for _ in range(6):
        powers = rng.integers(0, 41, size=3)
        # Some trials stay under 2**53 units and some pass them.
        top = 2 ** int(rng.integers(20, 35))
        whole = rng.integers(1, top, size=(3, 3))
        whole[rng.integers(3)] |= 1
        vectors = whole.astype(float) * 2.0 ** powers
        counts = rng.integers(1, 2 ** 20 + 1, size=3)
        true = [sum(int(n) * int(m) << int(p) for n, m in zip(counts, col))
                for col, p in zip(whole.T, powers)]
        counted = rs.zeros()
        for n, vec in zip(counts, vectors):
            counted += int(n) * vec
        unit = _unit_of(vectors)
        assert (unit >= 2.0 ** powers).all()
        if not sums_exact(counted, unit):
            assert any(t >= 2 ** 53 * int(u) for t, u in zip(true, unit))
            continue
        proven += 1
        pods = np.repeat(vectors, counts, axis=0)
        in_turn = np.cumsum(pods, axis=0)[-1]
        backwards = np.cumsum(pods[::-1], axis=0)[-1]
        for col in range(3):
            assert int(counted[col]) == int(in_turn[col]) \
                == int(backwards[col]) == true[col]
    assert proven


@pytest.mark.parametrize("seed", range(6))
def test_random_units_count_like_the_walk(seed):
    """Random fleets on the queue tree: a unit of 2**0 to 2**40 a column
    and requests large enough that some totals pass 2**53 bytes."""
    rng = np.random.default_rng([seed, 53])
    powers = rng.integers(0, 41, size=3)
    leaves = [q for q in _queues() if "-l" in q]
    asks = [(leaves[int(rng.integers(len(leaves)))], int(rng.integers(1, 7)),
             (rng.integers(1, 2 ** 13, size=3) * 2.0 ** powers).tolist())
            for _ in range(10)]
    plugin = _open(_tree(asks)).proportion
    true = [sum(pods * int(vec[col]) for _l, pods, vec in asks)
            for col in range(3)]
    proven = all(t < 2 ** 53 * 2 ** int(p) for t, p in zip(true, powers))
    assert proven and plugin.rollup == "counted"
    assert plugin.queues["root"].request.tolist() == [float(t) for t in true]
    _assert_same_bits(plugin, _open(_tree(asks), True).proportion)


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
                               "non_preemptible", "adds", "unit")
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
    arrives.  The counter says the same to a reader that keeps no span
    attributes: there at 0 from the first session, one more a walk."""
    from kai_scheduler_tpu.utils.tracing import TRACER
    from tests.test_snapshot_delta import BareLoop

    def rollup():
        (span,) = [s for s in TRACER.get_trace().spans
                   if s.name == "plugin:proportion"]
        return span.attrs["rollup"]

    METRICS.reset()
    loop = BareLoop()
    seen, walks = [], []
    for _ in range(3):
        loop.arrive(2)
        loop.cycle()
        seen.append(rollup())
        walks.append(METRICS.counters.get(WALKED))
    for _ in range(2):
        loop.arrive(1, gpu=0, gpu_fraction=0.5)
        loop.cycle()
        seen.append(rollup())
        walks.append(METRICS.counters.get(WALKED))
    assert seen == ["counted", "counted", "counted", "walked", "walked"]
    assert walks == [0.0, 0.0, 0.0, 1.0, 2.0]


def test_counter_is_registered_before_the_first_rollup(monkeypatch):
    """A reader that takes the counter's movement over a cycle finds it at
    0 when the first session's roll-up starts, whichever way that goes."""
    from kai_scheduler_tpu.plugins.proportion import ProportionPlugin
    found = []
    for name in ("_roll_up_counted", "_roll_up_walked"):
        inner = getattr(ProportionPlugin, name)

        def spy(self, arg, inner=inner):
            found.append(METRICS.counters.get(WALKED))
            return inner(self, arg)
        monkeypatch.setattr(ProportionPlugin, name, spy)
    METRICS.reset()
    assert _open(_fleet(1)).proportion.rollup == "counted"
    assert found == [0.0] and METRICS.counters[WALKED] == 0.0
    METRICS.reset()
    assert _open(_fleet(1), walked=True).proportion.rollup == "walked"
    assert found == [0.0, 0.0, 0.0] and METRICS.counters[WALKED] == 1.0


def _metric():
    """The benchmark's entry for the counter and the file that reads it."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "proportion_rollup_walks"]
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           entry["name"] + ".json")) as fh:
        return bench, entry, json.load(fh)


def test_the_benchmarks_metric_is_this_counter():
    bench, entry, doc = _metric()
    # Asked by name: later PRs append their metrics after this one.
    assert entry in bench["per_layer"]
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert doc.pop("reader") == {"kind": "counter_delta", "counter": WALKED}
    assert doc == {k: v for k, v in entry.items() if k != "workloads"}
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("walks/cycle", "lower", "program_counter",
                                "session and actions", "cycle_ms")


@pytest.mark.parametrize("walked", [False, True], ids=["counted", "walked"])
def test_the_benchmarks_reader_finds_the_sessions_walks(walked):
    """Read as the harness reads it: the counter's movement over a session,
    0.0 where the roll-up counted and not nothing; a program without the
    counter, as the parent is, leaves the metric out."""
    from benchmark.harness import readers
    _bench, _entry, doc = _metric()

    class Rec:
        counters = {}
        spans = []

    assert readers.read_all([doc], {"records": [Rec]}) == {}
    METRICS.reset()
    before = METRICS.counters.get(WALKED, 0.0)
    _open(_fleet(2), walked=walked)
    assert WALKED in METRICS.counters
    Rec.counters = {WALKED: METRICS.counters[WALKED] - before}
    assert readers.read_all([doc], {"records": [Rec]}) == {
        doc["name"]: {"value": float(walked), "unit": "walks/cycle"}}
