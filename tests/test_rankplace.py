"""Rank-aware placement ring (ops/rankplace.py, DESIGN §13).

Sweeps randomized topologies and gangs proving the kernel and the host
fallback bit-identical, the assignment deterministic (same snapshot =>
same assignment), and the hierarchical-order assignment never worse —
and on scattered fills strictly better — than the rank-oblivious
baseline on the mean consecutive-rank hop metric.  ``KAI_FAULT_SEED``
reshuffles the instance generator, so ``chaos_matrix --timeaware``
sweeps genuinely different topologies per seed.
"""

import os

import numpy as np
import pytest

from kai_scheduler_tpu.controllers.cache_builder import _parse_rank
from kai_scheduler_tpu.framework import SchedulerConfig
from kai_scheduler_tpu.ops import rankplace as rp
from kai_scheduler_tpu.ops.topology import build_tree
from kai_scheduler_tpu.utils import cluster_spec as cs

pytestmark = pytest.mark.chaos

SEED_BASE = int(os.environ.get("KAI_FAULT_SEED", "0")) * 1000


def random_order(rng, n_nodes, levels=2):
    names = [f"n{i:03d}" for i in range(n_nodes)]
    keys = ["block", "rack", "host"][:levels]
    labels = {}
    for i, nm in enumerate(names):
        lab, div = {}, 1
        for k in keys:
            lab[k] = f"{k}{int(rng.integers(0, max(2, n_nodes // div)))}"
            div *= 2
        labels[nm] = lab
    tree = build_tree("dc", keys, names, labels)
    return tree, rp.build_topo_order(tree, n_nodes + int(
        rng.integers(0, 5)))


class TestKernelParity:
    def test_kernel_matches_host_on_random_instances(self):
        """The padded kernel rung (pow2 gang buckets) sliced back to
        the real gang must equal the unpadded host reference bit for
        bit — padding keys sort strictly after every real slot."""
        rng = np.random.default_rng(SEED_BASE + 1)
        for trial in range(30):
            n = int(rng.integers(4, 48))
            tree, order = random_order(rng, n, levels=int(
                rng.integers(1, 4)))
            t = int(rng.integers(2, 70))
            slots = rng.integers(0, n, t).astype(np.int32)
            p_np, h_np = rp.rank_place_np(slots, order.topo_rank,
                                          order.level_segs)
            p_k, h_k = rp.rank_place_padded(slots, order.topo_rank,
                                            order.level_segs)
            assert np.array_equal(p_np, np.asarray(p_k)), trial
            assert np.array_equal(h_np, np.asarray(h_k)), trial

    def test_padded_shapes_share_one_compilation(self):
        """Gang sizes under one pow2 bucket must not recompile the
        kernel (the hot-path shape-bucketing convention)."""
        rng = np.random.default_rng(SEED_BASE + 9)
        tree, order = random_order(rng, 24)
        shapes = set()
        for t in (2, 3, 17, 30, 32):
            t_pad = 32
            while t_pad < t:
                t_pad *= 2
            shapes.add(t_pad)
            slots = rng.integers(0, 24, t).astype(np.int32)
            rp.rank_place_padded(slots, order.topo_rank,
                                 order.level_segs)
        assert shapes == {32}  # every gang above shared one bucket

    def test_deterministic_same_input_same_assignment(self):
        rng = np.random.default_rng(SEED_BASE + 2)
        tree, order = random_order(rng, 16)
        slots = rng.integers(0, 16, 12).astype(np.int32)
        first = rp.rank_place_np(slots, order.topo_rank, order.level_segs)
        for _ in range(3):
            again = rp.rank_place_np(slots, order.topo_rank,
                                     order.level_segs)
            assert np.array_equal(first[0], again[0])

    def test_assignment_never_worse_than_identity(self):
        rng = np.random.default_rng(SEED_BASE + 3)
        for _ in range(20):
            n = int(rng.integers(4, 40))
            tree, order = random_order(rng, n)
            t = int(rng.integers(2, 25))
            slots = rng.integers(0, n, t).astype(np.int32)
            before = rp.mean_hop(slots, order)
            perm, _hops = rp.rank_place_np(slots, order.topo_rank,
                                           order.level_segs)
            after = rp.mean_hop(slots[perm], order)
            assert after <= before + 1e-12

    def test_contiguous_subtree_optimality_small(self):
        """Brute force on tiny instances: the hierarchical-order
        assignment achieves the minimum consecutive-hop sum over ALL
        slot permutations (tree-metric contiguity argument)."""
        import itertools
        rng = np.random.default_rng(SEED_BASE + 4)
        for _ in range(6):
            n = 6
            tree, order = random_order(rng, n)
            t = int(rng.integers(2, 7))
            slots = rng.integers(0, n, t).astype(np.int32)
            perm, hops = rp.rank_place_np(slots, order.topo_rank,
                                          order.level_segs)
            ours = int(hops.sum())
            best = min(
                int(rp._hops_np(slots[np.asarray(p)],
                                order.level_segs).sum())
                for p in itertools.permutations(range(t)))
            assert ours == best

    def test_hop_metric_semantics(self):
        names = ["a", "b", "c", "d"]
        labels = {"a": {"block": "b0", "rack": "r0"},
                  "b": {"block": "b0", "rack": "r0"},
                  "c": {"block": "b0", "rack": "r1"},
                  "d": {"block": "b1", "rack": "r2"}}
        tree = build_tree("dc", ["block", "rack"], names, labels)
        order = rp.build_topo_order(tree, 4)
        segs = order.level_segs
        hops = rp._hops_np(np.array([0, 0, 1, 2, 3], np.int32), segs)
        # same node, same rack, cross rack, cross block.
        assert hops.tolist() == [0, 1, 2, 3]


class TestRankParsing:
    def md(self, name="w-3", ann=None, labels=None):
        return {"name": name, "annotations": ann or {},
                "labels": labels or {}}

    def test_annotation_wins(self):
        assert _parse_rank(self.md(
            ann={"kai.scheduler/rank": "7"})) == 7

    def test_job_completion_index_annotation(self):
        assert _parse_rank(self.md(
            ann={"batch.kubernetes.io/job-completion-index": "4"})) == 4

    def test_index_labels(self):
        for key in ("apps.kubernetes.io/pod-index",
                    "training.kubeflow.org/replica-index",
                    "leaderworkerset.sigs.k8s.io/worker-index"):
            assert _parse_rank(self.md(labels={key: "2"})) == 2

    def test_name_convention_fallback(self):
        assert _parse_rank(self.md(name="mpi-worker-12")) == 12
        assert _parse_rank(self.md(name="web-5d9fbd4c9")) == -1

    def test_garbage_values_unranked(self):
        assert _parse_rank(self.md(
            name="plain", ann={"kai.scheduler/rank": "x"})) == -1
        assert _parse_rank(self.md(
            name="plain", ann={"kai.scheduler/rank": "-3"})) == -1


def _mpi_session(rank_aware: bool, interleave: bool = True,
                 gang: int = 16, ranks=None):
    labels = (lambda i: {"block": f"b{i % 2}", "rack": f"r{i % 8}"}) \
        if interleave else \
        (lambda i: {"block": f"b{i // 8}", "rack": f"r{i // 2}"})
    nodes = {f"n{i:02d}": {"gpu": 4, "cpu": "32", "mem": "256Gi",
                           "labels": labels(i)} for i in range(16)}
    if ranks is None:
        ranks = list(range(gang))
    spec = {"nodes": nodes, "queues": {"q": {}},
            "topologies": {"dc": {"levels": ["block", "rack"]}},
            "jobs": {"mpi": {"queue": "q", "min_available": gang,
                             "tasks": [{"gpu": 2, "rank": ranks[i]}
                                       for i in range(gang)]}}}
    ssn = cs.build_session(
        spec, SchedulerConfig(rank_aware_placement=rank_aware))
    cs.run_action(ssn)
    tree = build_tree("dc", ["block", "rack"], ssn.snapshot.node_names,
                      {n: nodes[n]["labels"] for n in nodes})
    order = rp.build_topo_order(tree, len(ssn.snapshot.node_names))
    pg = ssn.cluster.podgroups["mpi"]
    by_rank = sorted((t for t in pg.pods.values() if t.node_name),
                     key=lambda t: t.rank)
    idx = np.array([ssn.node_index(t.node_name) for t in by_rank],
                   np.int32)
    return ssn, idx, order


class TestEndToEnd:
    def test_rank_aware_strictly_beats_oblivious_on_interleaved(self):
        ssn_a, idx_a, order = _mpi_session(True)
        ssn_b, idx_b, _ = _mpi_session(False)
        assert len(idx_a) == len(idx_b) == 16  # identical bound counts
        # Identical node multiset: the reorder is a pure permutation.
        assert sorted(idx_a.tolist()) == sorted(idx_b.tolist())
        aware, oblivious = rp.mean_hop(idx_a, order), \
            rp.mean_hop(idx_b, order)
        assert aware < oblivious, (aware, oblivious)

    def test_config_off_is_bit_identical_to_baseline(self):
        _ssn1, idx1, _ = _mpi_session(False)
        _ssn2, idx2, _ = _mpi_session(False)
        assert np.array_equal(idx1, idx2)

    def test_unranked_gang_untouched(self):
        ssn, idx, _ = _mpi_session(True, ranks=[-1] * 16)
        base, idx_b, _ = _mpi_session(False, ranks=[-1] * 16)
        # No ranks: the rank assigner declines, placements match the
        # oblivious baseline task-for-task.
        pg_a = {t.uid: t.node_name
                for t in ssn.cluster.podgroups["mpi"].pods.values()}
        pg_b = {t.uid: t.node_name
                for t in base.cluster.podgroups["mpi"].pods.values()}
        assert pg_a == pg_b

    def test_duplicate_ranks_untouched(self):
        ranks = [0, 1] * 8
        ssn, _idx, _ = _mpi_session(True, ranks=ranks)
        base, _idx_b, _ = _mpi_session(False, ranks=ranks)
        pg_a = {t.uid: t.node_name
                for t in ssn.cluster.podgroups["mpi"].pods.values()}
        pg_b = {t.uid: t.node_name
                for t in base.cluster.podgroups["mpi"].pods.values()}
        assert pg_a == pg_b

    def test_rank_metrics_and_span_emitted(self):
        from kai_scheduler_tpu.utils.metrics import METRICS
        before = sum(v for k, v in METRICS.counters.items()
                     if str(k).startswith("rank_place_assignments_total"))
        _mpi_session(True)
        after = sum(v for k, v in METRICS.counters.items()
                    if str(k).startswith("rank_place_assignments_total"))
        assert after > before

    def test_kernel_and_host_modes_agree_end_to_end(self, monkeypatch):
        from kai_scheduler_tpu.utils.metrics import METRICS
        idx = {}
        # The 16-rank gang stands on one side of the threshold, then the
        # other: gang size is the only thing the choice reads.
        for mode, min_gang in (("kernel", 16), ("host", 17)):
            monkeypatch.setattr(rp, "_KERNEL_MIN_GANG", min_gang)
            key = f'rank_place_assignments_total{{mode="{mode}"}}'
            before = METRICS.counters.get(key, 0)
            _ssn, idx[mode], _ = _mpi_session(True)
            assert METRICS.counters.get(key, 0) > before
        assert np.array_equal(idx["kernel"], idx["host"])
