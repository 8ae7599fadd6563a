"""What a scenario places again of a victim (PR 53).

``try_replace_victims`` placed a victim's next chunk again and nothing
more: its gang chunk where the scenario left it below its minimum, one pod
where it did not.  An elastic victim of a prefix that runs on past what the
pending job needs (a gang held to one rack, whose prefix runs through
eight) lost its surplus for good whatever room there was.  Now a second
pass places what else the scenario took of every victim whose first chunk
stands again, a pod a chunk (``actions/solvers.py`` ``_place_the_rest``:
ONE more multi-job call on the batched path, and none where nothing is
left; ``_attempt_the_rest``: attempt by attempt on the sequential one),
the two statements op for op alike.  Beside it: the one ``[N]`` row that holds the
call's first job to its domain (``first_job_node_mask``), and the extra
scores of a victim's chunks read in one call of the fns.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.framework import propose
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.fixtures import build_session, run_action


def config(**settings) -> SchedulerConfig:
    cfg = SchedulerConfig()
    for key, value in settings.items():
        setattr(cfg, key, value)
    return cfg


def pod(node=None, gpu=1) -> dict:
    out = {"gpu": gpu, "cpu": "4", "mem": "32Gi"}
    if node is not None:
        out.update(status="RUNNING", node=node)
    return out


def full_node_spec(claimer_pods: int, victim_pods: int = 4,
                   minimum: int = 2, nodes: int = 1) -> dict:
    """``nodes`` nodes of four GPUs, each under one elastic victim of
    queue ``b`` (newest on the last node); the claimer of queue ``a`` asks
    for ``claimer_pods`` one-GPU pods, all or nothing."""
    spec = {"nodes": {}, "jobs": {}, "queues": {
        "a": {"deserved": {"gpu": 4 * nodes}},
        "b": {"deserved": {"gpu": 0}}}}
    for i in range(nodes):
        name = f"n{i}"
        spec["nodes"][name] = {"gpu": 4, "cpu": "64", "mem": "512Gi"}
        spec["jobs"][f"victim-{i}"] = {
            "queue": "b", "min_available": minimum,
            "creation_ts": 100.0 + i,
            "tasks": [pod(name) for _ in range(victim_pods)]}
    spec["jobs"]["claimer"] = {
        "queue": "a", "min_available": claimer_pods, "preemptible": False,
        "tasks": [pod() for _ in range(claimer_pods)]}
    return spec


def reclaim(spec: dict, **settings):
    ssn = build_session(spec, config(**settings))
    TRACER.begin_cycle(1)
    run_action(ssn, "reclaim")
    trace = TRACER.end_cycle()
    (solve,) = [s for s in trace.spans if s.name == "solve:job"]
    ops = [(op.kind, op.task.name, op.node_name)
           for stmt in ssn.statements if stmt.committed for op in stmt.ops]
    return ssn, solve, ops


CASES = {
    # pending pods, nodes -> (evicted, placed again)
    # The surplus of two frees two GPUs, the claimer takes one: one pod
    # of the surplus stands again, the other finds no room and stays
    # evicted alone.
    "surplus-part": (1, 1, (2, 1)),
    # Three GPUs take the whole victim; one is left, the gang chunk of
    # two does not fit, and NOTHING of the job follows it: a lone
    # surplus pod of a job below its minimum is not placed.
    "gang-fails": (3, 1, (4, 0)),
    # Five GPUs on two nodes: the newer victim whole and the older one's
    # surplus, six GPUs, one left.  The newer victim's gang chunk of two
    # does not fit and nothing of it follows, so the one GPU is the older
    # victim's, which stands at its minimum: one pod of its surplus.
    "gang-and-one": (5, 2, (6, 1)),
}


@pytest.mark.parametrize("batched", (True, False),
                         ids=("batched", "sequential"))
@pytest.mark.parametrize("case", CASES)
def test_a_victim_is_placed_again_as_far_as_there_is_room(case, batched):
    pods, nodes, (evicted, replaced) = CASES[case]
    ssn, solve, ops = reclaim(full_node_spec(pods, nodes=nodes),
                              batched_scenario_confirm=batched)
    assert solve.attrs["solved"]
    assert sum(1 for kind, _t, _n in ops if kind == "evict") == evicted
    assert solve.attrs["replaced"] == replaced
    claimer = ssn.cluster.podgroups["claimer"]
    assert all(t.node_name for t in claimer.pods.values())
    # No job stands below its minimum with a pod placed again.
    for name, pg in ssn.cluster.podgroups.items():
        if name.startswith("victim"):
            alive = sum(1 for t in pg.pods.values()
                        if t.is_active_allocated())
            assert alive == 0 or alive >= 2


@pytest.mark.parametrize("case", CASES)
def test_the_batched_statement_is_the_sequential_ones(case):
    pods, nodes, _want = CASES[case]
    spec = full_node_spec(pods, nodes=nodes)
    _ssn, _solve, batched = reclaim(spec, batched_scenario_confirm=True)
    _ssn, _solve, plain = reclaim(spec, batched_scenario_confirm=False)
    assert batched == plain and batched


def test_a_victim_that_is_not_elastic_is_one_chunk_as_it_was():
    """Four pods, minimum four: the gang chunk is the job, nothing is
    left for a second pass."""
    spec = full_node_spec(1, minimum=4)
    ssn, solve, ops = reclaim(spec)
    assert solve.attrs["solved"] and solve.attrs["replaced"] == 0
    assert sum(1 for kind, _t, _n in ops if kind == "evict") == 4


# -- the first job's one row --------------------------------------------------
def kernel_call(first_mask=None, job_mask=None):
    """Two jobs of two one-GPU pods on four idle nodes of two GPUs."""
    n, r = 4, 3
    alloc = np.tile([64000.0, 512.0, 2.0], (n, 1))
    zeros = np.zeros((n, r))
    named = {}
    if first_mask is not None:
        named["first_job_node_mask"] = jnp.asarray(first_mask)
    if job_mask is not None:
        named["job_node_mask"] = jnp.asarray(job_mask)
    out = allocate_jobs_kernel(
        jnp.asarray(alloc), jnp.asarray(alloc), jnp.asarray(zeros),
        jnp.full((n, 1), -1, jnp.int32), jnp.full((n, 1), -1, jnp.int32),
        jnp.full(n, 110.0), jnp.tile(jnp.asarray([4000.0, 32.0, 1.0]),
                                     (4, 1)),
        jnp.asarray([0, 0, 1, 1], jnp.int32),
        jnp.full((4, 1), -1, jnp.int32), jnp.full((4, 1), -1, jnp.int32),
        jnp.asarray([True, True]), **named)
    return np.asarray(out.placements).tolist(), \
        np.asarray(out.job_success).tolist()


def test_the_first_jobs_row_holds_job_0_alone():
    only_3 = np.array([False, False, False, True])
    placed, ok = kernel_call(first_mask=only_3)
    assert ok == [True, True]
    assert placed[:2] == [3, 3]              # held to node 3
    assert 3 not in placed[2:]               # the second job goes anywhere
    # The same call with a [J,N] mask whose second row says nothing.
    dense = np.stack([only_3, np.ones(4, bool)])
    assert kernel_call(job_mask=dense) == (placed, ok)
    # A row that admits too little fails the first job, not the second.
    none = np.zeros(4, bool)
    placed, ok = kernel_call(first_mask=none)
    assert ok == [False, True] and placed[:2] == [-1, -1]


def test_a_chunk_that_follows_a_failed_one_takes_no_room():
    """Three jobs on ONE node of two GPUs: a gang of three (fails), its
    next chunk of one pod, and another job's two pods.  Unchained, the
    lone pod takes a GPU and the third job fails; chained, it is not
    tried and the third job has the node."""
    alloc = np.array([[64000.0, 512.0, 2.0]])
    req = jnp.tile(jnp.asarray([4000.0, 32.0, 1.0]), (6, 1))
    none = jnp.full((1, 1), -1, jnp.int32)
    rows = jnp.full((6, 1), -1, jnp.int32)

    def call(**named):
        out = allocate_jobs_kernel(
            jnp.asarray(alloc), jnp.asarray(alloc), jnp.zeros((1, 3)),
            none, none, jnp.full(1, 110.0), req,
            jnp.asarray([0, 0, 0, 1, 2, 2], jnp.int32), rows, rows,
            jnp.asarray([True, True, True]), **named)
        return np.asarray(out.job_success).tolist()
    assert call() == [False, True, False]
    assert call(job_follows=jnp.asarray([False, True, False])) \
        == [False, False, True]
    # A chain whose head succeeds goes on.
    assert call(job_follows=jnp.asarray([False, False, True])) \
        == [False, True, False]


def test_a_multi_call_sends_one_row_and_no_job_mask(monkeypatch):
    """``propose(kind="multi", node_subset=...)``: the subset is the first
    chunk's, goes to the kernel as ``first_job_node_mask`` [N], and no
    ``[J,N]`` mask is built."""
    ssn = build_session(full_node_spec(1, nodes=2))
    sent = {}
    stage = propose._stage

    def spy(*operands):
        sent.update(operands[-1])
        return stage(*operands)
    monkeypatch.setattr(propose, "_stage", spy)
    jobs = ssn.cluster.podgroups
    stmt = ssn.statement()
    for name in ("victim-0", "victim-1"):
        for task in list(jobs[name].pods.values()):
            stmt.evict(task)
    chunks = [(jobs["claimer"], list(jobs["claimer"].pods.values())),
              (jobs["victim-1"], list(jobs["victim-1"].pods.values())[:2])]
    subset = np.zeros(ssn.node_idle.shape[0], bool)
    subset[ssn.node_index("n0")] = True
    out = propose.propose(ssn, chunks, "multi", pipeline_only=True,
                          node_subset=subset)
    assert sent["job_node_mask"] is None
    assert np.asarray(sent["first_job_node_mask"]).tolist() \
        == subset.tolist()
    assert [p.success for p in out] == [True, True]
    assert [node for _t, node, _p in out[0].placements] == ["n0"]
    # The victim is not held to the subset: binpack puts it beside the
    # claimer or on the other node, and either way it is placed.
    assert len(out[1].placements) == 2
    stmt.discard()


# -- a victim's chunks are scored in one call ---------------------------------
@pytest.mark.parametrize("form", ("none", "row", "dense"))
def test_the_chunks_of_one_job_are_scored_in_one_call(form):
    ssn = build_session(full_node_spec(1, nodes=2))
    n = ssn.node_idle.shape[0]
    calls = []

    def fn(tasks):
        calls.append(len(tasks))
        if form == "none":
            return None
        if form == "row":
            return np.arange(n, dtype=float)
        return np.array([[float(ord(t.name[-1])) + i for i in range(n)]
                         for t in tasks])
    ssn.extra_score_fns[:] = [fn]
    jobs = ssn.cluster.podgroups
    claimer = list(jobs["claimer"].pods.values())
    victim = sorted(jobs["victim-1"].pods.values(), key=lambda t: t.name)
    chunks = [(jobs["claimer"], claimer), (jobs["victim-1"], victim[:2]),
              (jobs["victim-1"], victim[2:3]),
              (jobs["victim-1"], victim[3:]),
              (jobs["victim-0"], list(jobs["victim-0"].pods.values()))]
    grouped = propose._chunk_extras(ssn, chunks)
    assert calls == [1, 4, 4]                # one call a job
    calls.clear()
    alone = [ssn._sum_extra_scores(tasks) for _job, tasks in chunks]
    assert calls == [1, 2, 1, 1, 4]
    for got, want in zip(grouped, alone):
        assert (got is None) == (want is None)
        if want is not None:
            assert np.asarray(got).tolist() == np.asarray(want).tolist()
