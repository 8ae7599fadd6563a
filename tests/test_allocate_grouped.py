"""Grouped-allocation kernel: parity with the exact per-task kernel on
bin-pack configs (identical-task gangs are the hot path)."""

import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
from kai_scheduler_tpu.ops.allocate_grouped import allocate_grouped


def make_instance(seed, n_nodes=24, n_jobs=6, max_gang=5, releasing=True):
    rng = np.random.default_rng(seed)
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
        gpu = float(rng.integers(1, 4))
        sel = 0 if rng.random() < 0.3 else -1
        for _ in range(gang):
            reqs.append([1000.0, 1e9, gpu])
            jobs.append(j)
            sels.append(sel)
    req = np.array(reqs)
    task_job = np.array(jobs, np.int32)
    sel = np.array(sels, np.int32)[:, None]
    tol = np.full((len(reqs), 1), -1, np.int32)
    job_allowed = np.ones(n_jobs, bool)
    if n_jobs > 2:
        job_allowed[int(rng.integers(n_jobs))] = False
    nodes = (jnp.asarray(alloc), jnp.asarray(idle), jnp.asarray(rel),
             jnp.asarray(labels), jnp.asarray(taints), jnp.asarray(room))
    tasks = (jnp.asarray(req), jnp.asarray(task_job), jnp.asarray(sel),
             jnp.asarray(tol))
    return nodes, tasks, jnp.asarray(job_allowed)


@pytest.mark.parametrize("seed", range(6))
def test_parity_with_exact_kernel(seed):
    nodes, tasks, job_allowed = make_instance(seed)
    exact = allocate_jobs_kernel(*nodes, *tasks, job_allowed)
    grouped = allocate_grouped(nodes, *tasks, job_allowed)
    np.testing.assert_array_equal(np.asarray(exact.job_success),
                                  np.asarray(grouped.job_success))
    np.testing.assert_array_equal(np.asarray(exact.placements),
                                  np.asarray(grouped.placements))
    np.testing.assert_array_equal(np.asarray(exact.pipelined),
                                  np.asarray(grouped.pipelined))
    np.testing.assert_allclose(np.asarray(exact.node_idle),
                               np.asarray(grouped.node_idle))


def test_large_gang_fills_in_binpack_order():
    nodes, _, _ = make_instance(0, n_nodes=4, n_jobs=1)
    alloc, _, _, labels, taints, room = nodes
    idle = jnp.asarray(np.tile([8000.0, 64e9, 8.0], (4, 1)))
    rel = jnp.zeros((4, 3))
    req = np.tile([100.0, 1e8, 2.0], (16, 1))
    task_job = np.zeros(16, np.int32)
    sel = np.full((16, 1), -1, np.int32)
    tol = np.full((16, 1), -1, np.int32)
    out = allocate_grouped(
        (alloc, idle, rel, labels, taints, room),
        jnp.asarray(req), jnp.asarray(task_job), jnp.asarray(sel),
        jnp.asarray(tol), jnp.asarray(np.ones(1, bool)))
    assert bool(out.job_success[0])
    counts = np.bincount(np.asarray(out.placements), minlength=4)
    assert counts.tolist() == [4, 4, 4, 4]
    assert float(out.node_idle[:, 2].sum()) == 0.0


def test_pipeline_phase_marks_tasks():
    """Gang larger than idle capacity pipelines the overflow onto
    releasing resources, in the same fill order."""
    alloc = jnp.asarray(np.tile([8000.0, 64e9, 8.0], (2, 1)))
    idle = jnp.asarray(np.array([[8000.0, 64e9, 4.0],
                                 [8000.0, 64e9, 0.0]]))
    rel = jnp.asarray(np.array([[0.0, 0.0, 0.0], [0.0, 0.0, 8.0]]))
    labels = jnp.full((2, 1), -1, jnp.int32)
    taints = jnp.full((2, 1), -1, jnp.int32)
    room = jnp.full(2, 110.0)
    req = np.tile([100.0, 1e8, 2.0], (5, 1))
    out = allocate_grouped(
        (alloc, idle, rel, labels, taints, room),
        jnp.asarray(req), jnp.asarray(np.zeros(5, np.int32)),
        jnp.asarray(np.full((5, 1), -1, np.int32)),
        jnp.asarray(np.full((5, 1), -1, np.int32)),
        jnp.asarray(np.ones(1, bool)))
    assert bool(out.job_success[0])
    p = np.asarray(out.placements)
    piped = np.asarray(out.pipelined)
    assert (p[:2] == 0).all() and not piped[:2].any()  # idle capacity first
    assert (p[2:] == 1).all() and piped[2:].all()      # overflow pipelines


class TestMergedIndependentSingles:
    def _instance(self, n_jobs, n_nodes=16, gpu=1):
        import numpy as np
        alloc = np.tile([8000.0, 64e9, 8.0], (n_nodes, 1))
        idle = alloc.copy()
        rel = np.zeros((n_nodes, 3))
        labels = np.full((n_nodes, 1), -1, np.int32)
        taints = np.full((n_nodes, 1), -1, np.int32)
        room = np.full(n_nodes, 110.0)
        req = np.tile([1000.0, 1e9, float(gpu)], (n_jobs, 1))
        job = np.arange(n_jobs, dtype=np.int32)
        sel = np.full((n_jobs, 1), -1, np.int32)
        tol = np.full((n_jobs, 1), -1, np.int32)
        nodes = tuple(map(jnp.asarray,
                          (alloc, idle, rel, labels, taints, room)))
        return nodes, req, job, sel, tol

    def test_merged_matches_unmerged(self):
        """A burst of identical single-task jobs must place identically
        whether merged into one scan step or not."""
        import numpy as np
        nodes, req, job, sel, tol = self._instance(40)
        allowed = np.ones(40, bool)
        allowed[7] = False  # one gated job mid-run splits the merge
        merged = allocate_grouped(nodes, req, job, sel, tol, allowed,
                                  independent_jobs=np.ones(40, bool))
        plain = allocate_grouped(nodes, req, job, sel, tol, allowed)
        np.testing.assert_array_equal(np.asarray(merged.placements),
                                      np.asarray(plain.placements))
        np.testing.assert_array_equal(np.asarray(merged.job_success),
                                      np.asarray(plain.job_success))
        np.testing.assert_allclose(np.asarray(merged.node_idle),
                                   np.asarray(plain.node_idle))

    def test_merged_partial_placement(self):
        """Demand beyond capacity: the first jobs of the merged run place,
        the tail fails individually (no all-or-nothing across the run)."""
        import numpy as np
        # 16 nodes x 8 GPUs = 128 slots; 200 one-GPU jobs.
        nodes, req, job, sel, tol = self._instance(200)
        allowed = np.ones(200, bool)
        out = allocate_grouped(nodes, req, job, sel, tol, allowed,
                               independent_jobs=np.ones(200, bool))
        placed = np.asarray(out.placements)
        success = np.asarray(out.job_success)
        assert (placed >= 0).sum() == 128
        # Sequential semantics: the first 128 jobs succeed.
        np.testing.assert_array_equal(success[:128], True)
        np.testing.assert_array_equal(success[128:], False)

    def test_mixed_gangs_and_singles(self):
        """Real gangs interleaved with mergeable singles keep their
        all-or-nothing semantics."""
        import numpy as np
        n_nodes = 4  # 32 GPU slots
        alloc = np.tile([8000.0, 64e9, 8.0], (n_nodes, 1))
        nodes = tuple(map(jnp.asarray, (
            alloc, alloc.copy(), np.zeros((n_nodes, 3)),
            np.full((n_nodes, 1), -1, np.int32),
            np.full((n_nodes, 1), -1, np.int32),
            np.full(n_nodes, 110.0))))
        # jobs: 10 singles (1 GPU), one too-big gang (40 GPUs), 5 singles.
        req_rows = [[1000.0, 1e9, 1.0]] * 10 \
            + [[1000.0, 1e9, 1.0]] * 40 + [[1000.0, 1e9, 1.0]] * 5
        job_ids = list(range(10)) + [10] * 40 + list(range(11, 16))
        req = np.array(req_rows)
        job = np.array(job_ids, np.int32)
        sel = np.full((len(job), 1), -1, np.int32)
        tol = np.full((len(job), 1), -1, np.int32)
        allowed = np.ones(16, bool)
        indep = np.array([True] * 10 + [False] + [True] * 5)
        out = allocate_grouped(nodes, req, job, sel, tol, allowed,
                               independent_jobs=indep)
        success = np.asarray(out.job_success)
        placed = np.asarray(out.placements)
        # Gang of 40 cannot fit 32 slots: fails atomically.
        assert not success[10]
        assert (placed[10:50] >= 0).sum() == 0
        # All 15 singles fit.
        assert success[:10].all() and success[11:].all()


class TestExtraScoresAndMasks:
    """Per-job extra score rows (tier constants) and hard masks through
    the grouped fill plan: parity with the exact kernel, which receives
    the same terms as [T,N] arrays."""

    def _expand(self, rows, task_job):
        return np.asarray(rows)[np.asarray(task_job)]

    @pytest.mark.parametrize("seed", range(4))
    def test_extra_parity_with_exact_kernel(self, seed):
        nodes, tasks, job_allowed = make_instance(seed)
        n_jobs = len(np.asarray(job_allowed))
        n_nodes = np.asarray(nodes[0]).shape[0]
        rng = np.random.default_rng(seed + 100)
        # Tier-constant boosts (multiples of 10, like topology=10000 and
        # nominated=1e6): a random subset of nodes boosted per job.
        extra = np.where(rng.random((n_jobs, n_nodes)) < 0.3,
                         10000.0, 0.0)
        exact = allocate_jobs_kernel(
            *nodes, *tasks, job_allowed,
            jnp.asarray(self._expand(extra, tasks[1])))
        grouped = allocate_grouped(nodes, *tasks, job_allowed,
                                   extra_scores=extra)
        np.testing.assert_array_equal(np.asarray(exact.job_success),
                                      np.asarray(grouped.job_success))
        np.testing.assert_array_equal(np.asarray(exact.placements),
                                      np.asarray(grouped.placements))
        np.testing.assert_array_equal(np.asarray(exact.pipelined),
                                      np.asarray(grouped.pipelined))
        np.testing.assert_allclose(np.asarray(exact.node_idle),
                                   np.asarray(grouped.node_idle))

    @pytest.mark.parametrize("seed", range(4))
    def test_mask_parity_with_exact_kernel(self, seed):
        nodes, tasks, job_allowed = make_instance(seed)
        n_jobs = len(np.asarray(job_allowed))
        n_nodes = np.asarray(nodes[0]).shape[0]
        rng = np.random.default_rng(seed + 200)
        mask = rng.random((n_jobs, n_nodes)) < 0.7
        exact = allocate_jobs_kernel(
            *nodes, *tasks, job_allowed,
            task_node_mask=jnp.asarray(self._expand(mask, tasks[1])))
        grouped = allocate_grouped(nodes, *tasks, job_allowed,
                                   node_mask=mask)
        np.testing.assert_array_equal(np.asarray(exact.job_success),
                                      np.asarray(grouped.job_success))
        np.testing.assert_array_equal(np.asarray(exact.placements),
                                      np.asarray(grouped.placements))
        np.testing.assert_allclose(np.asarray(exact.node_idle),
                                   np.asarray(grouped.node_idle))

    def test_extra_and_mask_together(self):
        nodes, tasks, job_allowed = make_instance(3)
        n_jobs = len(np.asarray(job_allowed))
        n_nodes = np.asarray(nodes[0]).shape[0]
        rng = np.random.default_rng(42)
        extra = np.where(rng.random((n_jobs, n_nodes)) < 0.3, 100.0, 0.0)
        mask = rng.random((n_jobs, n_nodes)) < 0.8
        exact = allocate_jobs_kernel(
            *nodes, *tasks, job_allowed,
            jnp.asarray(self._expand(extra, tasks[1])),
            task_node_mask=jnp.asarray(self._expand(mask, tasks[1])))
        grouped = allocate_grouped(nodes, *tasks, job_allowed,
                                   extra_scores=extra, node_mask=mask)
        np.testing.assert_array_equal(np.asarray(exact.placements),
                                      np.asarray(grouped.placements))
        np.testing.assert_array_equal(np.asarray(exact.job_success),
                                      np.asarray(grouped.job_success))


class TestSessionFastPathRouting:
    """propose_placements routing: which chunks take the grouped
    fill-plan kernel vs the exact per-task scan (framework/session.py).
    A regression that routes non-uniform or non-tier terms through the
    fill plan would silently change placements."""

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

    def _spy(self, monkeypatch):
        import kai_scheduler_tpu.ops.allocate_grouped as ag
        calls = []
        orig = ag.allocate_grouped

        def spy(*a, **k):
            calls.append(k)
            return orig(*a, **k)

        # The session imports inside the function body, so patch the
        # module attribute it resolves at call time.
        monkeypatch.setattr(
            "kai_scheduler_tpu.ops.allocate_grouped.allocate_grouped",
            spy, raising=True)
        return calls

    def test_plain_homogeneous_routes_grouped(self, monkeypatch):
        ssn, tasks = self._session()
        calls = self._spy(monkeypatch)
        prop = ssn.propose_placements(tasks)
        assert prop.success and len(prop.placements) == 4
        assert len(calls) == 1

    # An extra-score fn may return one [N] row for the chunk or [T,N];
    # a uniform term routes the same in either form.
    FORMS = {"row": lambda boost, ts: boost,
             "tiled": lambda boost, ts: np.tile(boost, (len(ts), 1))}

    @pytest.mark.parametrize("form", FORMS)
    def test_uniform_tier_extra_routes_grouped(self, monkeypatch, form):
        ssn, tasks = self._session()
        n = ssn.node_idle.shape[0]
        boost = np.zeros(n)
        boost[3] = 10000.0
        ssn.extra_score_fns.append(
            lambda ts: self.FORMS[form](boost, ts))
        calls = self._spy(monkeypatch)
        prop = ssn.propose_placements(tasks)
        assert prop.success
        assert len(calls) == 1
        assert calls[0]["extra_scores"].shape == (1, n)
        # The boost decides the placement: everything lands on n3.
        assert {p[1] for p in prop.placements} == {"n3"}

    @pytest.mark.parametrize("form", FORMS)
    def test_non_tier_extra_falls_back_to_exact(self, monkeypatch, form):
        ssn, tasks = self._session()
        n = ssn.node_idle.shape[0]
        boost = np.zeros(n)
        boost[3] = 5.0  # not a multiple of 10: fill-plan parity unsafe
        ssn.extra_score_fns.append(
            lambda ts: self.FORMS[form](boost, ts))
        calls = self._spy(monkeypatch)
        prop = ssn.propose_placements(tasks)
        assert prop.success
        assert calls == []
        # The exact kernel still reads the boost, in either form.
        assert prop.placements[0][1] == "n3"

    @pytest.mark.parametrize("form", FORMS)
    def test_all_zero_extra_rides_no_operand(self, monkeypatch, form):
        ssn, tasks = self._session()
        boost = np.zeros(ssn.node_idle.shape[0])
        ssn.extra_score_fns.append(
            lambda ts: self.FORMS[form](boost, ts))
        calls = self._spy(monkeypatch)
        assert ssn.propose_placements(tasks).success
        assert len(calls) == 1 and calls[0]["extra_scores"] is None

    def test_per_task_varying_extra_falls_back(self, monkeypatch):
        ssn, tasks = self._session()
        n = ssn.node_idle.shape[0]

        def varying(ts):
            extra = np.zeros((len(ts), n))
            extra[0, 2] = 10000.0  # only the first task boosted
            return extra

        ssn.extra_score_fns.append(varying)
        calls = self._spy(monkeypatch)
        prop = ssn.propose_placements(tasks)
        assert prop.success
        assert calls == []

    def test_node_subset_becomes_mask_row(self, monkeypatch):
        ssn, tasks = self._session()
        n = ssn.node_idle.shape[0]
        subset = np.zeros(n, bool)
        subset[4:] = True
        calls = self._spy(monkeypatch)
        prop = ssn.propose_placements(tasks, node_subset=subset)
        assert prop.success
        assert len(calls) == 1
        assert calls[0].get("node_mask") is not None
        assert {p[1] for p in prop.placements} <= {"n4", "n5"}

    def test_node_subset_ands_with_a_uniform_task_mask(self, monkeypatch):
        """A subset (the job's row) beside a per-task mask whose rows are
        alike: still the grouped kernel, on the AND of the two."""
        ssn, tasks = self._session()
        n = ssn.node_idle.shape[0]
        subset = np.zeros(n, bool)
        subset[2:] = True
        allowed = np.ones(n, bool)
        allowed[4:] = False
        ssn.hard_node_mask_fns.append(
            lambda ts: np.tile(allowed, (len(ts), 1)))
        calls = self._spy(monkeypatch)
        prop = ssn.propose_placements(tasks, node_subset=subset)
        assert prop.success
        assert len(calls) == 1
        assert calls[0]["node_mask"].tolist() == [
            [False, False, True, True, False, False]]
        assert {p[1] for p in prop.placements} <= {"n2", "n3"}

    def test_node_subset_ands_with_a_varying_task_mask(self, monkeypatch):
        """The exact kernel ANDs the job's row with the per-task mask."""
        ssn, tasks = self._session()
        n = ssn.node_idle.shape[0]
        subset = np.zeros(n, bool)
        subset[2:] = True

        def varying_mask(ts):
            mask = np.ones((len(ts), n), bool)
            mask[0, :5] = False     # the first task: n5 only
            return mask

        ssn.hard_node_mask_fns.append(varying_mask)
        calls = self._spy(monkeypatch)
        prop = ssn.propose_placements(tasks, node_subset=subset)
        assert prop.success
        assert calls == []
        nodes = [p[1] for p in prop.placements]
        assert nodes[0] == "n5"
        assert set(nodes) <= {"n2", "n3", "n4", "n5"}

    def test_per_task_varying_mask_falls_back(self, monkeypatch):
        ssn, tasks = self._session()
        n = ssn.node_idle.shape[0]

        def varying_mask(ts):
            mask = np.ones((len(ts), n), bool)
            mask[0, :3] = False
            return mask

        ssn.hard_node_mask_fns.append(varying_mask)
        calls = self._spy(monkeypatch)
        prop = ssn.propose_placements(tasks)
        assert prop.success
        assert calls == []
