"""Queue-forest fair-share parity ring (DESIGN §2b).

The fused single-dispatch forest kernel (``ops/fairshare.fair_share_forest``)
must be BIT-IDENTICAL to the per-level path (``fair_share_levels``) — which
is itself property-tested against the sequential numpy reference.  This
suite sweeps randomized forests (``KAI_FAULT_SEED`` reshuffles the
generator, so repeated chaos-matrix iterations prove genuinely different
hierarchies), the scale shape the acceptance names (10k queues, depth >= 5),
and the edge cases the dense layout introduces: zero-deserved queues,
over-limit clamps, priority bands absent at some levels, single-queue
groups, and the prep cache's reuse/invalidation discipline.
"""

import os

import numpy as np
import pytest

from kai_scheduler_tpu.ops import fairshare as fs
from kai_scheduler_tpu.utils.metrics import METRICS

pytestmark = pytest.mark.chaos

SEED_BASE = int(os.environ.get("KAI_FAULT_SEED", "0")) * 1000
R = 3


def random_forest(seed, q_lo=3, q_hi=90, attach_p=0.8):
    """A random forest: each queue attaches to a lower-index parent with
    probability ``attach_p`` (yielding mixed depths, single-child parents,
    and multiple roots)."""
    rng = np.random.default_rng(SEED_BASE + seed)
    q = int(rng.integers(q_lo, q_hi))
    parent = np.full(q, -1, np.int64)
    for i in range(1, q):
        if rng.random() < attach_p:
            parent[i] = int(rng.integers(0, i))
    priority = rng.choice([0, 0, 0, 5, 10], q)
    creation = rng.uniform(0, 100, q)
    uids = [f"q{i}" for i in range(q)]
    deserved = rng.choice([fs.UNLIMITED, 0, 5, 10, 20], (q, R))
    limit = rng.choice([fs.UNLIMITED, fs.UNLIMITED, 15, 40], (q, R))
    oqw = rng.choice([0, 1, 2, 3], (q, R)).astype(float)
    request = fs.roll_up_requests(
        parent, rng.integers(0, 60, (q, R)).astype(float))
    usage = rng.uniform(0, 0.3, (q, R))
    total = rng.integers(50, 400, R).astype(float)
    k = float(rng.choice([0.0, 0.5, 1.0]))
    return dict(parent=parent, priority=priority, creation=creation,
                uids=uids, deserved=deserved, limit=limit, oqw=oqw,
                request=request, usage=usage, total=total, k=k)


def structured_forest(seed, q=10000, roots=16, fanouts=(2, 2, 2, 2, 2, 8),
                      bands=1):
    """A multi-tenant org tree at scale: ``roots`` top-level tenants,
    breadth-first fanout per depth, depth >= len(fanouts).  The topology
    comes from bench.forest_parent_indices — the same forest
    ``bench.fairshare_microbench`` and the churn ring run."""
    import bench
    rng = np.random.default_rng(SEED_BASE + seed)
    parent = bench.forest_parent_indices(q, roots, fanouts)
    priority = rng.choice(np.arange(bands) * 50, q)
    creation = rng.uniform(0, 1e6, q)
    uids = [f"tenant-{i:05d}" for i in range(q)]
    deserved = np.where(rng.random((q, R)) < 0.5, 0.0,
                        rng.integers(1, 8, (q, R)).astype(float))
    limit = np.where(rng.random((q, R)) < 0.9, fs.UNLIMITED,
                     rng.integers(16, 64, (q, R)).astype(float))
    oqw = rng.integers(1, 4, (q, R)).astype(float)
    request = fs.roll_up_requests(
        parent, rng.integers(0, 30, (q, R)).astype(float))
    usage = rng.uniform(0, 0.2, (q, R))
    total = np.full(R, 2e5)
    return dict(parent=parent, priority=priority, creation=creation,
                uids=uids, deserved=deserved, limit=limit, oqw=oqw,
                request=request, usage=usage, total=total, k=1.0)


def run_levels(inst):
    hier = fs.QueueHierarchy.build(inst["parent"], inst["priority"],
                                   inst["creation"], inst["uids"])
    return fs.fair_share_levels(inst["total"], inst["k"], hier,
                                inst["deserved"], inst["limit"],
                                inst["oqw"], inst["request"],
                                inst["usage"])


def run_forest(inst):
    prep = fs.prepared_forest(inst["parent"], inst["priority"],
                              inst["creation"], inst["uids"],
                              inst["deserved"], inst["limit"], inst["oqw"])
    return fs.fair_share_forest(inst["total"], inst["k"], prep,
                                inst["request"], inst["usage"])


def assert_bit_identical(inst, msg=""):
    a = run_levels(inst)
    b = run_forest(inst)
    assert np.array_equal(a, b), \
        f"forest kernel diverged from per-level path {msg}: " \
        f"max |diff| = {np.abs(a - b).max()}"
    return a


class TestForestParityRandomized:
    @pytest.mark.parametrize("seed", range(8))
    def test_random_forests_bit_identical(self, seed):
        assert_bit_identical(random_forest(seed), f"(seed {seed})")

    def test_flat_wide_single_group(self):
        # One root group of ~2k siblings: the dense layout's widest row.
        inst = random_forest(100, q_lo=1500, q_hi=1501, attach_p=0.0)
        assert_bit_identical(inst, "(flat wide)")

    def test_deep_chain(self):
        # Every queue a single child of the previous: depth == Q - 1,
        # every group a single-queue group.
        q = 24
        inst = random_forest(101, q_lo=q, q_hi=q + 1, attach_p=0.0)
        inst["parent"] = np.arange(-1, q - 1, dtype=np.int64)
        inst["request"] = fs.roll_up_requests(
            inst["parent"], np.abs(inst["request"]))
        assert_bit_identical(inst, "(chain)")


@pytest.mark.slow
class TestForestParityAtScale:
    """The acceptance shape: randomized 10k-queue forests at depth >= 5.
    Slow-gated (one compile of each 10k layout costs seconds); the
    chaos matrix's --shards/--fused sweeps cover the small shapes per
    seed, and the fleet-budget gate re-measures the 10k shape in CI."""

    def test_10k_depth8_bit_identical(self):
        inst = structured_forest(1, q=10000,
                                 fanouts=(2, 2, 2, 2, 2, 8), bands=1)
        assert_bit_identical(inst, "(10k depth-8)")

    def test_10k_depth5_three_bands_bit_identical(self):
        inst = structured_forest(2, q=10000, roots=24,
                                 fanouts=(3, 3, 3, 12), bands=3)
        assert_bit_identical(inst, "(10k depth-5 3-band)")


class TestForestEdgeCases:
    def test_zero_deserved_queues(self):
        # Every queue deserved=0: the whole pool flows over-quota.
        inst = random_forest(200)
        inst["deserved"] = np.zeros_like(inst["deserved"])
        out = assert_bit_identical(inst, "(zero deserved)")
        assert np.all(out >= 0)

    def test_over_limit_clamp(self):
        # Tight limits below deserved: requestable clamps at the limit
        # and the surplus redistributes.
        inst = random_forest(201)
        inst["deserved"] = np.full_like(inst["deserved"], 50.0)
        inst["limit"] = np.full_like(inst["limit"], 5.0)
        out = assert_bit_identical(inst, "(over-limit clamp)")
        assert np.all(out <= 50.0 + 1e-6)

    def test_band_absent_at_some_levels(self):
        # High-priority band exists ONLY at the leaf level: interior
        # levels must skip it exactly (the level_bands fold).
        rng = np.random.default_rng(SEED_BASE + 202)
        q = 40
        parent = np.full(q, -1, np.int64)
        parent[8:] = rng.integers(0, 8, q - 8)
        priority = np.zeros(q, np.int64)
        priority[8:] = rng.choice([0, 100], q - 8)
        inst = random_forest(202, q_lo=q, q_hi=q + 1)
        inst["parent"], inst["priority"] = parent, priority
        inst["request"] = fs.roll_up_requests(
            parent, np.abs(inst["request"]))
        prep = fs.prepared_forest(parent, priority, inst["creation"],
                                  inst["uids"], inst["deserved"],
                                  inst["limit"], inst["oqw"])
        # Structural: the root level's band fold excludes the leaf-only
        # band; the leaf level sees both.
        assert len(prep.spec.level_bands[0]) == 1
        assert len(prep.spec.level_bands[-1]) == 2
        assert_bit_identical(inst, "(leaf-only band)")

    def test_single_queue_groups(self):
        # Parents with exactly one child each: S == 1 rows everywhere
        # below the root level.
        q = 17
        parent = np.full(q, -1, np.int64)
        parent[1:9] = np.arange(0, 8)       # 8 single-child chains
        inst = random_forest(203, q_lo=q, q_hi=q + 1)
        inst["parent"] = parent
        inst["request"] = fs.roll_up_requests(
            parent, np.abs(inst["request"]))
        assert_bit_identical(inst, "(single-queue groups)")

    def test_empty_forest(self):
        out = fs.fair_share_forest(
            np.full(R, 10.0), 1.0,
            fs.prepared_forest(np.zeros(0, np.int64), np.zeros(0),
                               np.zeros(0), [],
                               np.zeros((0, R)), np.zeros((0, R)),
                               np.zeros((0, R))),
            np.zeros((0, R)), np.zeros((0, R)))
        assert out.shape[0] == 0


class TestPrepCache:
    def test_reuse_counts_and_dispatch_is_one(self):
        fs._FOREST_CACHE.clear()
        inst = random_forest(300)
        reuse0 = METRICS.counters.get("fairshare_prep_reuse_total", 0)
        disp0 = METRICS.counters.get("fairshare_dispatch_total", 0)
        run_forest(inst)
        assert METRICS.counters.get("fairshare_prep_reuse_total",
                                    0) == reuse0  # cold build
        run_forest(inst)
        run_forest(inst)
        assert METRICS.counters.get("fairshare_prep_reuse_total",
                                    0) == reuse0 + 2
        # ONE dispatch per fair-share computation, regardless of depth.
        assert METRICS.counters.get("fairshare_dispatch_total",
                                    0) == disp0 + 3

    def test_weight_change_rebuilds(self):
        fs._FOREST_CACHE.clear()
        inst = random_forest(301)
        p1 = fs.prepared_forest(inst["parent"], inst["priority"],
                                inst["creation"], inst["uids"],
                                inst["deserved"], inst["limit"],
                                inst["oqw"])
        changed = inst["oqw"] + 1.0
        p2 = fs.prepared_forest(inst["parent"], inst["priority"],
                                inst["creation"], inst["uids"],
                                inst["deserved"], inst["limit"], changed)
        assert p1 is not p2
        # Same inputs again: both entries live in the LRU.
        assert fs.prepared_forest(
            inst["parent"], inst["priority"], inst["creation"],
            inst["uids"], inst["deserved"], inst["limit"],
            inst["oqw"]) is p1

    def test_cache_bounded(self):
        fs._FOREST_CACHE.clear()
        inst = random_forest(302)
        for i in range(fs._FOREST_CACHE_MAX + 4):
            fs.prepared_forest(inst["parent"], inst["priority"],
                               inst["creation"], inst["uids"],
                               inst["deserved"], inst["limit"],
                               inst["oqw"] + float(i))
        assert len(fs._FOREST_CACHE) == fs._FOREST_CACHE_MAX

    def test_guard_transition_drops_cache(self):
        from kai_scheduler_tpu.utils.deviceguard import device_guard
        fs._FOREST_CACHE.clear()
        inst = random_forest(303)
        p1 = fs.prepared_forest(inst["parent"], inst["priority"],
                                inst["creation"], inst["uids"],
                                inst["deserved"], inst["limit"],
                                inst["oqw"])
        # Simulate a closed-breaker CPU fallback (the arena's
        # GuardWatch hazard): the resident prep must not survive it.
        guard = device_guard()
        fs._GUARD_WATCH.resync(guard)
        guard.fallback_calls += 1
        p2 = fs.prepared_forest(inst["parent"], inst["priority"],
                                inst["creation"], inst["uids"],
                                inst["deserved"], inst["limit"],
                                inst["oqw"])
        guard.fallback_calls -= 1
        fs._GUARD_WATCH.resync(guard)
        assert p1 is not p2


class TestPluginIntegration:
    def test_forest_and_levels_modes_agree_end_to_end(self):
        """The session's fair shares (the forest dispatch) against the
        per-level reference called on the plugin's own inputs."""
        from tests.fixtures import build_session

        spec = {
            "nodes": {f"n{i}": {"gpu": 8} for i in range(4)},
            "queues": {
                "org": {"deserved": {"gpu": 24}},
                "team-a": {"parent": "org", "oqw": 2},
                "team-b": {"parent": "org"},
                "solo": {"deserved": {"gpu": 8}, "priority": 5},
            },
            "jobs": {f"j{i}": {"queue": q, "tasks": [{"gpu": 2}]}
                     for i, q in enumerate(
                         ["team-a", "team-a", "team-b", "solo"])},
        }
        ssn = build_session(spec)
        prop = ssn.proportion
        qids = sorted(prop.queues)
        index = {qid: i for i, qid in enumerate(qids)}
        col = lambda attr: np.stack(
            [getattr(prop.queues[q], attr) for q in qids])
        parent = np.array([index.get(prop.queues[q].parent, -1)
                           for q in qids], np.int64)
        hier = fs.QueueHierarchy.build(
            parent, np.array([prop.queues[q].priority for q in qids]),
            np.array([prop.queues[q].creation_ts for q in qids]), qids)
        deserved, limit, request = \
            col("deserved"), col("limit"), col("request")
        levels = fs.restore_exact(
            fs.fair_share_levels(prop.total, ssn.config.k_value, hier,
                                 deserved, limit, col("over_quota_weight"),
                                 request, col("usage")),
            deserved, limit, request)
        assert {"org", "team-a", "team-b", "solo"} <= set(qids)
        for qid, i in index.items():
            np.testing.assert_array_equal(
                prop.queues[qid].fair_share, levels[i],
                err_msg=f"queue {qid} fair share differs from the "
                        f"per-level reference")

    def test_session_open_counts_single_dispatch_and_span(self):
        from kai_scheduler_tpu.utils.tracing import TRACER
        from tests.fixtures import build_session

        spec = {
            "nodes": {"n0": {"gpu": 8}},
            "queues": {"p": {}, "c1": {"parent": "p"},
                       "c2": {"parent": "p"}},
            "jobs": {"j0": {"queue": "c1", "tasks": [{"gpu": 1}]}},
        }
        disp0 = METRICS.counters.get("fairshare_dispatch_total", 0)
        TRACER.begin_cycle(990001)
        try:
            build_session(spec)
        finally:
            trace = TRACER.end_cycle()
        assert METRICS.counters.get("fairshare_dispatch_total", 0) \
            == disp0 + 1
        spans = [s for s in trace.spans if s.kind == "fairshare"]
        assert len(spans) == 1
        assert spans[0].attrs["queues"] == 3
        assert "mode" not in spans[0].attrs
