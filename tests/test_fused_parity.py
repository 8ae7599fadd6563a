"""Fused-vs-reference parity ring for the grouped allocation kernel.

Both rungs of the grouped fill (ops/allocate_grouped: Pallas row,
fused-jnp row) must be BIT-IDENTICAL in placements to the exact per-task
kernel (ops/allocate.allocate_jobs_kernel), the one reference.  This
suite sweeps randomized shapes through both rungs, plus the edges their
specializations introduce: the no-releasing fast path, empty groups,
zero feasible nodes, spread strategy routing (which must NOT take the
grouped path at all), and a breaker-open dispatch falling back mid-cycle.

``KAI_FAULT_SEED`` reshuffles the instance generator, so
``chaos_matrix --fused`` sweeps genuinely different workloads per seed.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
from kai_scheduler_tpu.ops.allocate_grouped import allocate_grouped

pytestmark = pytest.mark.chaos

SEED_BASE = int(os.environ.get("KAI_FAULT_SEED", "0")) * 1000


def make_instance(seed, n_nodes=24, n_jobs=6, max_gang=5, releasing=True,
                  gated=True):
    rng = np.random.default_rng(SEED_BASE + seed)
    alloc = np.tile([8000.0, 64e9, 8.0], (n_nodes, 1))
    idle = alloc.copy()
    idle[:, 2] -= rng.integers(0, 6, n_nodes)
    rel = np.zeros((n_nodes, 3))
    if releasing:
        rel[:, 2] = rng.integers(0, 3, n_nodes)
    labels = np.full((n_nodes, 1), -1, np.int32)
    labels[: n_nodes // 2, 0] = 0
    taints = np.full((n_nodes, 1), -1, np.int32)
    room = np.full(n_nodes, 110.0)
    reqs, jobs, sels = [], [], []
    for j in range(n_jobs):
        gang = int(rng.integers(1, max_gang + 1))
        gpu = float(rng.integers(0, 4))  # 0-GPU jobs hit the CPU axis
        s = 0 if rng.random() < 0.3 else -1
        for _ in range(gang):
            reqs.append([1000.0, 1e9, gpu])
            jobs.append(j)
            sels.append(s)
    job_allowed = np.ones(n_jobs, bool)
    if gated and n_jobs > 2:
        job_allowed[int(rng.integers(n_jobs))] = False
    nodes = tuple(map(jnp.asarray,
                      (alloc, idle, rel, labels, taints, room)))
    return (nodes, np.array(reqs), np.array(jobs, np.int32),
            np.array(sels, np.int32)[:, None],
            np.full((len(reqs), 1), -1, np.int32), job_allowed)


def exact(nodes, req, job, sel, tol, allowed, extra_scores=None,
          node_mask=None, pipeline_only=False):
    """The reference: the per-task scan over the same tasks, with the
    grouped wrapper's per-JOB rows as the exact kernel's per-job rows."""
    as_dev = lambda x: None if x is None else jnp.asarray(x)
    return allocate_jobs_kernel(
        *nodes, jnp.asarray(req), jnp.asarray(job), jnp.asarray(sel),
        jnp.asarray(tol), jnp.asarray(allowed),
        job_extra_scores=as_dev(extra_scores),
        job_node_mask=as_dev(node_mask), pipeline_only=pipeline_only)


def assert_identical(a, b, ctx=""):
    np.testing.assert_array_equal(np.asarray(a.placements),
                                  np.asarray(b.placements), err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(a.pipelined),
                                  np.asarray(b.pipelined), err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(a.job_success),
                                  np.asarray(b.job_success), err_msg=ctx)
    np.testing.assert_allclose(np.asarray(a.node_idle),
                               np.asarray(b.node_idle), err_msg=ctx)
    np.testing.assert_allclose(np.asarray(a.node_releasing),
                               np.asarray(b.node_releasing), err_msg=ctx)


class TestFusedLadderParity:
    @pytest.mark.parametrize("seed", range(5))
    @pytest.mark.parametrize("releasing", [True, False])
    def test_jnp_and_pallas_match_exact(self, seed, releasing):
        nodes, req, job, sel, tol, allowed = make_instance(
            seed, releasing=releasing)
        ref = exact(nodes, req, job, sel, tol, allowed)
        for mode in ("jnp", "pallas"):
            out = allocate_grouped(nodes, req, job, sel, tol, allowed,
                                   fused_mode=mode)
            assert_identical(out, ref,
                             f"mode={mode} seed={seed} rel={releasing}")

    @pytest.mark.parametrize("seed", range(3))
    def test_extra_and_mask_rows(self, seed):
        nodes, req, job, sel, tol, allowed = make_instance(seed)
        n_jobs, n_nodes = len(allowed), np.asarray(nodes[0]).shape[0]
        rng = np.random.default_rng(SEED_BASE + seed + 77)
        extra = np.where(rng.random((n_jobs, n_nodes)) < 0.3, 10000.0, 0.0)
        mask = rng.random((n_jobs, n_nodes)) < 0.8
        ref = exact(nodes, req, job, sel, tol, allowed,
                    extra_scores=extra, node_mask=mask)
        for mode in ("jnp", "pallas"):
            out = allocate_grouped(nodes, req, job, sel, tol, allowed,
                                   extra_scores=extra, node_mask=mask,
                                   fused_mode=mode)
            assert_identical(out, ref, f"mode={mode} seed={seed}")

    @pytest.mark.parametrize("mode", ["jnp", "pallas"])
    def test_pipeline_only(self, mode):
        nodes, req, job, sel, tol, allowed = make_instance(2)
        ref = exact(nodes, req, job, sel, tol, allowed, pipeline_only=True)
        out = allocate_grouped(nodes, req, job, sel, tol, allowed,
                               pipeline_only=True, fused_mode=mode)
        assert_identical(out, ref, f"pipeline_only mode={mode}")

    def test_merged_independent_singles(self):
        n_jobs = 40
        alloc = np.tile([8000.0, 64e9, 8.0], (16, 1))
        nodes = tuple(map(jnp.asarray, (
            alloc, alloc.copy(), np.zeros((16, 3)),
            np.full((16, 1), -1, np.int32), np.full((16, 1), -1, np.int32),
            np.full(16, 110.0))))
        req = np.tile([1000.0, 1e9, 1.0], (n_jobs, 1))
        job = np.arange(n_jobs, dtype=np.int32)
        sel = np.full((n_jobs, 1), -1, np.int32)
        tol = np.full((n_jobs, 1), -1, np.int32)
        allowed = np.ones(n_jobs, bool)
        allowed[7] = False
        indep = np.ones(n_jobs, bool)
        # One-task jobs are independent in the exact kernel by nature:
        # each is its own gang.
        ref = exact(nodes, req, job, sel, tol, allowed)
        for mode in ("jnp", "pallas"):
            out = allocate_grouped(nodes, req, job, sel, tol, allowed,
                                   independent_jobs=indep, fused_mode=mode)
            assert_identical(out, ref, f"merged mode={mode}")


class TestFusedEdges:
    def test_empty_task_set(self):
        nodes, _, _, _, _, allowed = make_instance(0)
        empty_req = np.zeros((0, 3))
        empty_i = np.zeros(0, np.int32)
        empty_col = np.zeros((0, 1), np.int32)
        # Not sayable in the exact kernel's terms: its scan step gathers
        # row t of a [0, R] task table, which JAX refuses at trace time
        # (the session never dispatches an empty chunk).  The grouped
        # wrapper pads to one zero-count group, so the rungs are held to
        # the plain answer and to each other.
        outs = {mode: allocate_grouped(nodes, empty_req, empty_i, empty_col,
                                       empty_col, allowed, fused_mode=mode)
                for mode in ("jnp", "pallas")}
        for out in outs.values():
            assert np.asarray(out.placements).shape == (0,)
            assert not np.asarray(out.job_success).any()
            np.testing.assert_array_equal(np.asarray(out.node_idle),
                                          np.asarray(nodes[1]))
        assert_identical(outs["pallas"], outs["jnp"], "empty")

    def test_zero_feasible_nodes(self):
        """Every node excluded (selector no node carries): gangs fail
        identically on both rungs, state untouched."""
        nodes, req, job, sel, tol, allowed = make_instance(1, gated=False)
        sel = np.full_like(sel, 3)  # label id no node carries
        ref = exact(nodes, req, job, sel, tol, allowed)
        assert not np.asarray(ref.job_success).any()
        assert (np.asarray(ref.placements) == -1).all()
        for mode in ("jnp", "pallas"):
            out = allocate_grouped(nodes, req, job, sel, tol, allowed,
                                   fused_mode=mode)
            assert_identical(out, ref, f"zero-feasible mode={mode}")

    def test_gang_larger_than_cluster(self):
        """Demand over total capacity: rollback leaves no trace, both
        rungs agree with the exact kernel."""
        nodes, _, _, _, _, _ = make_instance(3, n_nodes=4)
        t = 200  # 4 nodes x 8 GPUs = 32 slots
        req = np.tile([1000.0, 1e9, 1.0], (t, 1))
        job = np.zeros(t, np.int32)
        sel = np.full((t, 1), -1, np.int32)
        tol = np.full((t, 1), -1, np.int32)
        allowed = np.ones(1, bool)
        ref = exact(nodes, req, job, sel, tol, allowed)
        assert not bool(ref.job_success[0])
        for mode in ("jnp", "pallas"):
            out = allocate_grouped(nodes, req, job, sel, tol, allowed,
                                   fused_mode=mode)
            assert_identical(out, ref, f"overflow mode={mode}")


class TestNoPathPins:
    """Which device program runs is the code's choice from what it can
    see (backend, node bucket, gang size): no environment name moves it,
    and a rung that does not exist is refused."""

    @staticmethod
    def _observe(monkeypatch):
        """(rung, key width, fill placements, rank mode, rank
        assignment) of one grouped fill and one 32-rank gang."""
        import kai_scheduler_tpu.ops.allocate_grouped as ag
        from kai_scheduler_tpu.utils.metrics import METRICS
        from tests.test_rankplace import _mpi_session
        seen = {}
        packed = ag._allocate_groups_packed

        def spy(*args, **kw):
            seen.update(rung=kw["fused_mode"], f32_keys=kw["f32_keys"])
            return packed(*args, **kw)

        with monkeypatch.context() as m:
            m.setattr(ag, "_allocate_groups_packed", spy)
            out = ag.allocate_grouped(*make_instance(0))
        kernel = 'rank_place_assignments_total{mode="kernel"}'
        before = METRICS.counters.get(kernel, 0)
        _ssn, idx, _ = _mpi_session(True, gang=32)
        rank_mode = "kernel" if METRICS.counters.get(kernel, 0) > before \
            else "host"
        return (seen["rung"], seen["f32_keys"],
                np.asarray(out.placements).tolist(), rank_mode,
                idx.tolist())

    @pytest.mark.parametrize("name,value", [("KAI_FUSED_ALLOC", "legacy"),
                                            ("KAI_F32_SCORE_KEYS", "1"),
                                            ("KAI_RANKPLACE", "host")])
    def test_environment_name_pins_nothing(self, name, value, monkeypatch):
        monkeypatch.delenv(name, raising=False)
        unset = self._observe(monkeypatch)
        assert unset[0] == "jnp" and unset[1] is False \
            and unset[3] == "kernel"
        monkeypatch.setenv(name, value)
        assert self._observe(monkeypatch) == unset

    def test_legacy_rung_is_refused(self):
        with pytest.raises(ValueError, match="fused_mode"):
            allocate_grouped(*make_instance(0), fused_mode="legacy")


class TestRoutingAndFallback:
    def _session(self):
        from kai_scheduler_tpu.utils.cluster_spec import build_session
        spec = {"nodes": {f"n{i}": {"gpu": 8} for i in range(6)},
                "queues": {"q": {}},
                "jobs": {"j1": {"queue": "q", "min_available": 4,
                                "tasks": [{"cpu": "1", "mem": "1Gi",
                                           "gpu": 2}] * 4}}}
        ssn = build_session(spec)
        tasks = list(ssn.cluster.podgroups["j1"].pods.values())
        return ssn, tasks

    def test_spread_strategy_falls_back_to_exact_kernel(self, monkeypatch):
        """SPREAD round-robins as nodes fill — the grouped fill plan
        cannot model it, so the session must route spread chunks to the
        exact per-task kernel (the grouped path is never consulted)."""
        from kai_scheduler_tpu.ops.scoring import SPREAD
        ssn, tasks = self._session()
        ssn.gpu_strategy = SPREAD
        calls = []
        import kai_scheduler_tpu.ops.allocate_grouped as ag
        orig = ag.allocate_grouped
        monkeypatch.setattr(
            "kai_scheduler_tpu.ops.allocate_grouped.allocate_grouped",
            lambda *a, **k: calls.append(k) or orig(*a, **k))
        prop = ssn.propose_placements(tasks)
        assert prop.success
        assert calls == []

    def test_breaker_open_falls_back_and_stays_correct(self):
        """With the circuit breaker OPEN, the grouped dispatch runs via
        the guard's CPU fallback — the fused kernel must produce the
        same placements it produces under a healthy dispatch, and the
        fused-taken counter still counts the call."""
        from kai_scheduler_tpu.utils.deviceguard import (OPEN, device_guard,
                                                         reset_device_guard)
        from kai_scheduler_tpu.utils.metrics import METRICS
        ssn, tasks = self._session()
        healthy = ssn.propose_placements(tasks)
        assert healthy.success
        reset_device_guard()
        guard = device_guard()
        try:
            guard.breaker.state = OPEN
            guard.breaker.opened_at = guard.breaker.clock()

            def fused_taken():
                return sum(v for k, v in METRICS.counters.items()
                           if str(k).startswith(
                               "allocate_fused_taken_total"))

            before = fused_taken()
            degraded = ssn.propose_placements(tasks)
            assert degraded.success
            assert [p[1] for p in degraded.placements] == \
                [p[1] for p in healthy.placements]
            assert fused_taken() > before
        finally:
            reset_device_guard()
