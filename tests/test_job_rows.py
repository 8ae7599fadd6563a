"""One [N] row per job, not [T,N]: the exact kernel's per-job score and
hard-mask operands, and the session that hands them over.

Kernel half: ``allocate_jobs_kernel`` given ``job_extra_scores`` /
``job_node_mask`` rows places exactly as it does given the same values
tiled to [T,N].  Session half counts, never times: which form the
operands took (``propose:operands``' ``extras`` / ``mask``) and how many
bytes crossed the seam (``seam:stage``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
from kai_scheduler_tpu.ops.scenario_batch import batch_prefix_feasibility
from kai_scheduler_tpu.scheduler import Scheduler
from kai_scheduler_tpu.utils.cluster_spec import build_cluster, build_session
from kai_scheduler_tpu.utils.metrics import METRICS, _key
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.test_allocate_grouped import make_instance


def assert_same_result(rows, tiled):
    for field in ("placements", "pipelined", "job_success", "node_idle",
                  "node_releasing", "packed"):
        np.testing.assert_array_equal(np.asarray(getattr(rows, field)),
                                      np.asarray(getattr(tiled, field)),
                                      err_msg=field)


def padded(tasks, job_allowed):
    """Task rows padded to a power of two the way the session pads them:
    the padding tasks belong to one more, gated-out job."""
    req, task_job, sel, tol = (np.asarray(a) for a in tasks)
    t, n_jobs = req.shape[0], np.asarray(job_allowed).shape[0]
    t_pad = 1 << t.bit_length()

    def pad(a, fill):
        out = np.full((t_pad,) + a.shape[1:], fill, a.dtype)
        out[:t] = a
        return jnp.asarray(out)
    allowed = np.append(np.asarray(job_allowed), False)
    return ((pad(req, 0.0), pad(task_job, n_jobs), pad(sel, -1),
             pad(tol, -1)), jnp.asarray(allowed))


CASES = {
    # name: (instance kwargs, pad with a gated-out job, a per-task mask
    #        beside the rows, kernel statics)
    "one_job": (dict(n_jobs=1, max_gang=12), False, False, {}),
    "several_jobs": (dict(), False, False, {}),
    "padding_job": (dict(), True, False, {}),
    "row_plus_task_mask": (dict(), True, True, {}),
    "pipeline_only": (dict(), False, False, dict(pipeline_only=True)),
    "no_pipeline": (dict(), False, False, dict(allow_pipeline=False)),
}


class TestKernelJobRows:
    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("case", CASES)
    def test_rows_place_as_the_same_values_tiled(self, case, seed):
        kwargs, pad, task_mask, statics = CASES[case]
        nodes, tasks, job_allowed = make_instance(seed, **kwargs)
        if pad:
            tasks, job_allowed = padded(tasks, job_allowed)
        task_job = np.asarray(tasks[1])
        t, n_jobs = task_job.shape[0], np.asarray(job_allowed).shape[0]
        n = np.asarray(nodes[0]).shape[0]
        rng = np.random.default_rng(100 + seed)
        # Boosts large enough to decide placements, not multiples of
        # anything: the row is added to the same score either way.
        rows = rng.random((n_jobs, n)) * 50.0 * (rng.random((n_jobs, n))
                                                 < 0.4)
        masks = rng.random((n_jobs, n)) < 0.8
        per_task = (rng.random((t, n)) < 0.9) if task_mask else None
        tiled_mask = masks[task_job] if per_task is None \
            else masks[task_job] & per_task

        by_rows = allocate_jobs_kernel(
            *nodes, *tasks, job_allowed,
            task_node_mask=None if per_task is None
            else jnp.asarray(per_task),
            job_extra_scores=jnp.asarray(rows),
            job_node_mask=jnp.asarray(masks), **statics)
        tiled = allocate_jobs_kernel(
            *nodes, *tasks, job_allowed, jnp.asarray(rows[task_job]),
            task_node_mask=jnp.asarray(tiled_mask), **statics)
        assert_same_result(by_rows, tiled)
        # The operands decide something: without them the result differs
        # (else the case proves nothing).
        plain = allocate_jobs_kernel(*nodes, *tasks, job_allowed, **statics)
        assert not np.array_equal(np.asarray(plain.placements),
                                  np.asarray(tiled.placements))

    @pytest.mark.parametrize("seed", range(3))
    def test_a_job_row_adds_to_a_per_task_row(self, seed):
        """Both score operands at once: whole numbers, so the order of
        the two additions cannot show."""
        nodes, tasks, job_allowed = make_instance(seed)
        task_job = np.asarray(tasks[1])
        n = np.asarray(nodes[0]).shape[0]
        rng = np.random.default_rng(200 + seed)
        rows = np.floor(rng.random((len(np.asarray(job_allowed)), n)) * 40)
        per_task = np.floor(rng.random((task_job.shape[0], n)) * 40)
        both = allocate_jobs_kernel(
            *nodes, *tasks, job_allowed, jnp.asarray(per_task),
            job_extra_scores=jnp.asarray(rows))
        summed = allocate_jobs_kernel(
            *nodes, *tasks, job_allowed,
            jnp.asarray(per_task + rows[task_job]))
        assert_same_result(both, summed)

    @pytest.mark.parametrize("seed", range(3))
    def test_an_absent_operand_is_the_neutral_operand(self, seed):
        """None leaves the term out of the step; zeros / ones / no-domain
        rows must give the same answer."""
        nodes, tasks, job_allowed = make_instance(seed)
        t = np.asarray(tasks[1]).shape[0]
        n = np.asarray(nodes[0]).shape[0]
        absent = allocate_jobs_kernel(*nodes, *tasks, job_allowed)
        neutral = allocate_jobs_kernel(
            *nodes, *tasks, job_allowed, jnp.zeros((t, n)),
            task_node_mask=jnp.ones((t, n), bool),
            task_anti_domain=(jnp.full((t, n), -1, jnp.int32),
                              jnp.zeros(t, bool), jnp.zeros(t, bool)),
            task_aff_domain=(jnp.full((t, n), -1, jnp.int32),
                             jnp.zeros(t, bool), jnp.zeros(t, bool),
                             jnp.ones((t, n), bool), jnp.zeros(t, bool)))
        assert_same_result(absent, neutral)

    @pytest.mark.parametrize("seed", range(3))
    def test_prefix_feasibility_still_takes_a_per_task_mask(self, seed):
        """``batch_prefix_feasibility`` vmaps the kernel with a [T,N]
        mask: each prefix must read as the kernel alone reads it."""
        nodes, tasks, _allowed = make_instance(seed, n_jobs=1, max_gang=6)
        alloc, idle, rel, labels, taints, room = nodes
        (req, task_job, sel, tol), allowed = padded(
            tasks, np.ones(1, bool))
        n, k = np.asarray(alloc).shape[0], 4
        rng = np.random.default_rng(300 + seed)
        mask = rng.random((np.asarray(req).shape[0], n)) < 0.5
        # Prefix p releases 3 GPUs on node p (and keeps earlier ones).
        step = np.arange(k, dtype=np.int32)
        vec = np.tile([0.0, 0.0, 3.0], (k, 1))
        got = np.asarray(batch_prefix_feasibility(
            alloc, idle, rel, labels, taints, room, jnp.asarray(step),
            jnp.asarray(step), jnp.asarray(vec), req, task_job, sel, tol,
            num_prefixes=k, task_node_mask=jnp.asarray(mask)))
        want = []
        for p in range(k):
            pool = np.asarray(rel).copy()
            pool[:p + 1, 2] += 3.0
            want.append(bool(allocate_jobs_kernel(
                alloc, idle, jnp.asarray(pool), labels, taints, room, req,
                task_job, sel, tol, allowed,
                task_node_mask=jnp.asarray(mask),
                pipeline_only=True).job_success[0]))
        assert got.tolist() == want


# ---------------------------------------------------------------------------
# the session


def rack_cluster(n_nodes=64, workers=63, topology=True):
    """Racks of 16 nodes and one gang, a master beside its workers (so
    the chunk is non-homogeneous and takes the exact kernel)."""
    nodes = {f"n{i:02d}": {"gpu": 8, "labels": {"rack": f"r{i // 16}"}}
             for i in range(n_nodes)}
    job = {"queue": "q", "min_available": workers + 1,
           "tasks": [{"cpu": "2", "mem": "1Gi", "gpu": 1}]
           + [{"cpu": "1", "mem": "1Gi", "gpu": 1}] * workers}
    spec = {"nodes": nodes, "queues": {"q": {}}, "jobs": {"gang": job}}
    if topology:
        spec["topologies"] = {"topo": {"levels": ["rack"]}}
        job.update(topology="topo", preferred_topology_level="rack")
    return spec


def spans_of_a_cycle(spec):
    TRACER.reset()
    ssn = Scheduler(lambda: build_cluster(spec), SchedulerConfig()).run_once()
    spans = {}
    for sp in TRACER.get_trace().spans:
        spans.setdefault(sp.name, []).append(sp)
    return ssn, spans


def form_count(extras, mask):
    return METRICS.counters.get(
        _key("propose_operand_form_total",
             {"extras": extras, "mask": mask}), 0.0)


class TestSessionOperandForms:
    def test_a_topology_gang_stages_rows_not_a_matrix(self):
        before = form_count("row", "row")
        ssn, spans = spans_of_a_cycle(rack_cluster())
        assert len(ssn.cluster.bind_requests) == 64
        (operands,) = spans["propose:operands"]
        assert operands.attrs["path"] == "exact"
        assert (operands.attrs["extras"], operands.attrs["mask"]) \
            == ("row", "row")
        assert form_count("row", "row") == before + 1
        # Fewer bytes than ONE BYTE a cell of [T,N]: boosts f64 and the
        # subset bool for the gang's job and the padding job, and the
        # task rows.
        t, n = operands.attrs["t_pad"], operands.attrs["nodes"]
        (stage,) = spans["seam:stage"]
        assert (t, n) == (64, 64)
        assert stage.attrs["bytes_host"] < t * n
        assert stage.attrs["operands"] == 7
        # One row from the plugin, too.
        assert spans["extra_scores:topology"][0].attrs["bytes"] == n * 8
        # And the gang sits in one rack of 16 nodes.
        assert len({int(br.node_name[1:]) // 16
                    for br in ssn.cluster.bind_requests}) == 1

    def test_a_gang_without_extras_stages_no_score_operand(self):
        before = form_count("none", "none")
        ssn, spans = spans_of_a_cycle(rack_cluster(topology=False))
        assert len(ssn.cluster.bind_requests) == 64
        (operands,) = spans["propose:operands"]
        assert operands.attrs["path"] == "exact"
        assert (operands.attrs["extras"], operands.attrs["mask"]) \
            == ("none", "none")
        assert form_count("none", "none") == before + 1
        # Task requests, job index, selectors, tolerations, job gate:
        # nothing with a node axis.
        (stage,) = spans["seam:stage"]
        assert stage.attrs["operands"] == 5
        assert stage.attrs["bytes_host"] < 64 * 64

    def _gang(self, ssn):
        job = ssn.cluster.podgroups["gang"]
        tasks = list(job.pods.values())
        (subset,) = ssn.subset_nodes(job, tasks)[:1]
        return tasks, subset

    def test_a_per_task_fn_beside_the_row_makes_it_dense(self):
        """A [T,N] fn beside topology's row: one dense operand whose
        values are the parent's (every fn [T,N], summed in order)."""
        def nominated(tasks):
            out = np.zeros((len(tasks), 16))
            out[0, 9] = 123456.0    # the master wants n09
            out[3, 2] = 7.25
            return out

        def propose(tile_rows):
            ssn = build_session(rack_cluster(n_nodes=16, workers=5))
            if tile_rows:   # the parent's contract: every fn [T,N]
                ssn.extra_score_fns[:] = [
                    (lambda ts, fn=fn: None if (c := fn(ts)) is None else
                     np.broadcast_to(c, (len(ts), 16)).copy())
                    for fn in ssn.extra_score_fns]
            ssn.extra_score_fns.append(nominated)
            tasks, subset = self._gang(ssn)
            TRACER.begin_cycle(1)
            prop = ssn.propose_placements(tasks, node_subset=subset)
            trace = TRACER.end_cycle()
            (operands,) = [sp for sp in trace.spans
                           if sp.name == "propose:operands"]
            assert prop.success
            return ([(t.uid, node, piped) for t, node, piped
                     in prop.placements], operands.attrs)

        mixed, attrs = propose(tile_rows=False)
        tiled, tiled_attrs = propose(tile_rows=True)
        assert (attrs["extras"], attrs["mask"]) == ("dense", "row")
        assert tiled_attrs["extras"] == "dense"
        assert mixed == tiled
        assert mixed[0][1] == "n09"

    def test_multi_gives_each_topology_job_its_own_row(self):
        """Two jobs in one call, each boosted toward another node: on
        the parent the first job's boosts were tiled over both."""
        spec = rack_cluster(n_nodes=16, workers=1)
        spec["jobs"]["other"] = dict(spec["jobs"]["gang"])
        ssn = build_session(spec)
        topo = next(p for p in ssn.plugins if p.name == "topology")._topo
        chunks = []
        for name, node in (("gang", 5), ("other", 11)):
            job = ssn.cluster.podgroups[name]
            row = np.zeros(16)
            row[node] = 10000.0
            topo._job_node_scores[job.uid] = row
            chunks.append((job, list(job.pods.values())))
        before = form_count("row", "none")
        out = ssn.propose_placements_multi(chunks, pipeline_only=False)
        assert form_count("row", "none") == before + 1
        for prop, node in zip(out, ("n05", "n11")):
            assert prop.success
            assert {n for _t, n, _p in prop.placements} == {node}

    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("hard_mask", (False, True))
    @pytest.mark.parametrize("form", ("none", "row", "dense"))
    def test_the_single_call_is_the_one_chunk_multi_call(
            self, form, hard_mask, seed, monkeypatch):
        """One path behind both names: the same operands cross the seam,
        array for array, and the same placements come back."""
        import jax

        from kai_scheduler_tpu.framework import propose
        n, workers = 16, 5
        rng = np.random.default_rng(400 + seed)
        ssn = build_session(rack_cluster(n_nodes=n, workers=workers,
                                         topology=False))
        job = ssn.cluster.podgroups["gang"]
        tasks = list(job.pods.values())
        # Scores that decide placements (no multiples of anything) and a
        # mask that leaves every task room.
        scores = {"none": None, "row": rng.random(n) * 50.0,
                  "dense": rng.random((len(tasks), n)) * 50.0}[form]
        ssn.extra_score_fns[:] = [lambda ts: scores]
        if hard_mask:
            mask = rng.random((len(tasks), n)) < 0.7
            ssn.hard_node_mask_fns.append(lambda ts: mask)

        staged = []
        real_stage = propose._stage

        def recording_stage(*operands):
            staged.append(operands)
            return real_stage(*operands)
        monkeypatch.setattr(propose, "_stage", recording_stage)

        before = form_count(form, "dense" if hard_mask else "none")
        single = ssn.propose_placements(tasks)
        multi = ssn.propose_placements_multi([(job, tasks)],
                                             pipeline_only=False)
        assert form_count(form, "dense" if hard_mask else "none") \
            == before + 2
        assert single.success and multi == [single]
        if form != "none" or hard_mask:
            ssn.extra_score_fns[:] = []
            ssn.hard_node_mask_fns[:] = []
            assert ssn.propose_placements(tasks) != single, \
                "the operands decide nothing: the case proves nothing"
            del staged[2:]

        by_single, by_multi = staged
        assert jax.tree_util.tree_structure(by_single) \
            == jax.tree_util.tree_structure(by_multi)
        leaves = jax.tree_util.tree_leaves(by_single)
        assert len(leaves) == 5 + (form != "none") + hard_mask
        for a, b in zip(leaves, jax.tree_util.tree_leaves(by_multi)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)

    def test_multi_mixes_a_row_job_and_a_per_task_job(self):
        spec = rack_cluster(n_nodes=16, workers=1)
        spec["jobs"]["other"] = dict(spec["jobs"]["gang"])
        ssn = build_session(spec)
        gang = ssn.cluster.podgroups["gang"]
        other = ssn.cluster.podgroups["other"]

        def fn(tasks):
            if tasks[0].job_id == gang.uid:
                row = np.zeros(16)
                row[4] = 10000.0
                return row
            out = np.zeros((len(tasks), 16))
            out[0, 7] = out[1, 8] = 10000.0
            return out
        ssn.extra_score_fns[:] = [fn]
        out = ssn.propose_placements_multi(
            [(gang, list(gang.pods.values())),
             (other, list(other.pods.values()))], pipeline_only=False)
        assert [n for _t, n, _p in out[0].placements] == ["n04"] * 2
        assert [n for _t, n, _p in out[1].placements] \
            == ["n07", "n08"]

    @pytest.mark.parametrize("form", ("row", "per_task"))
    def test_score_nodes_for_task_adds_the_whole_row(self, form):
        ssn = build_session(rack_cluster(n_nodes=16, workers=1,
                                         topology=False))
        task = next(iter(ssn.cluster.podgroups["gang"].pods.values()))
        ssn.extra_score_fns[:] = []
        base = ssn.score_nodes_for_task(task)
        row = np.arange(16) * 10.0
        ssn.extra_score_fns.append(
            lambda ts: row if form == "row" else row[None, :].copy())
        np.testing.assert_array_equal(
            ssn.score_nodes_for_task(task) - base, row)
