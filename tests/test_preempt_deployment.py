"""In-queue preemption at fleet shape, on the CPU (PR 50).

The deployment ``preempt-98k`` at 64 and 256 nodes: the benchmark's own
client (``benchmark/generators/preempt_replicas.py``, which is
``reclaim_gangs``' fleet with the arrivals in the occupier's own queue)
drives ``Scheduler.run_once``; every cycle a step of a LeaderWorkerSet's
replica groups arrives (each a gang of one leader and three workers, every
pod a whole node), the preempt action solves them one after another, each
taking four nodes of the queue's own training jobs, and the allocate action
binds them a cycle later; the plain reference the chip's ``correct`` uses
(``benchmark/reference/inqueue_eviction.py``, loaded by path, no import of
the program) finds all fifteen counts 0, where the controls of
``benchmark/tests/control_preempt.py`` each move their own.  Beside it:
the reference's functions on numbers made by hand, the span and the two
counter families this PR adds, and the preempt action's own filters.
"""

import math
import os
import sys
import types

import numpy as np
import pytest

from kai_scheduler_tpu.actions import preempt
from kai_scheduler_tpu.actions.preempt import survey_preempt_victims
from kai_scheduler_tpu.utils.metrics import METRICS, _key
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.fixtures import build_session, run_action

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "preempt98k-lws-32x4"
SOLVED = _key("preemptors_solved_total", {"result": "solved"})
UNSOLVED = _key("preemptors_solved_total", {"result": "unsolved"})
CALLS = "scenario_prescreen_calls_total"
COUNTED = "scenario_prescreen_counted_total"
COUNTERS = (SOLVED, UNSOLVED, CALLS, COUNTED,
            "scenario_prescreen_scan_steps_total", "device_kernel_calls",
            "scenarios_skipped_by_prescreen_total")
DEPLOY_SEEDS = (3, 11, 3000000019)
# 64 nodes: two departments of one leaf, the team's half under its jobs,
# two replicas a cycle (three steps in flight and the next step's victims
# are its 32 nodes).  256: the generator's own trial.
CUTS = {
    64: dict(nodes=64, replicas=2, whole=4, victims=32, share=0.5,
             departments=2, leaves=1, limit_factor=1.0),
    256: dict(nodes=256, replicas=4, whole=16, victims=128, departments=2,
              leaves=2, limit_factor=1.0),
}


def the_cell():
    from benchmark.harness import spec
    return spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)


def small_cell(nodes: int):
    """The cell as ``BENCHMARK.json`` names it (its generator and its
    reference loaded by path, as a chip run loads them) with the fleet,
    the step and the solver's caps cut to ``nodes``."""
    cell = the_cell()
    assert cell.reference.__file__ == os.path.join(
        BENCH, "reference", "inqueue_eviction.py")
    assert cell.generator.__file__ == os.path.join(
        BENCH, "generators", "preempt_replicas.py")
    return cell.generator.cut_cell(cell, **CUTS[nodes])


@pytest.fixture(scope="module")
def ref():
    return the_cell().reference


# -- (a) the reference on numbers made by hand ---------------------------------
NODE = [64000.0, 512.0, 8.0]
POD = np.array([4000.0, 32.0, 1.0])
LEADER = [36000.0, 288.0, 8.0]
WORKER = [32000.0, 256.0, 8.0]
REPLICA = np.array([LEADER] + [WORKER] * 3)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(BENCH, "reference", "inqueue_eviction.py")
    tree = ast.parse(open(path).read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "math", "numpy"}


@pytest.mark.parametrize("victim, taken", [
    (("team", True, 50), True),
    (("team", True, 124), True),
    (("team", True, 125), False),        # not STRICTLY lower
    (("team", True, 200), False),
    (("team", False, 50), False),        # not preemptible
    (("other", True, 50), False),        # another queue
], ids=("lower", "just-lower", "equal", "higher", "fixed", "other-queue"))
def test_what_may_be_taken(ref, victim, taken):
    assert ref.may_be_taken(*victim, "team", 125) is taken


@pytest.mark.parametrize("victims, preemptor, counts", [
    ([("team", True, 50)] * 3, ("team", 125), (0, 0, 0)),
    ([("team", False, 50), ("team", True, 50)], ("team", 125), (1, 0, 0)),
    ([("other", True, 50)] * 2 + [("team", True, 50)], ("team", 125),
     (0, 2, 0)),
    ([("team", True, 125), ("team", True, 130), ("team", True, 50)],
     ("team", 125), (0, 0, 2)),
    ([("other", False, 125)], ("team", 125), (1, 1, 1)),
    # No preemptor seated: only preemptibility can be judged here.
    ([("other", False, 125), ("team", True, 50)], None, (1, 0, 0)),
], ids=("legal", "fixed", "other-queue", "not-lower", "all-three",
        "no-preemptor"))
def test_a_commits_victims_are_judged_one_by_one(ref, victims, preemptor,
                                                 counts):
    out = ref.victim_faults(victims, preemptor)
    assert (out["victims_not_preemptible"], out["victims_from_other_queue"],
            out["victims_not_lower_priority"]) == counts


def test_upstreams_order_is_lowest_priority_then_newest(ref):
    jobs = [("old", 50, 1.0), ("new", 50, 3.0), ("mid", 50, 2.0),
            ("low-old", 10, 0.5), ("high-new", 100, 9.0)]
    assert ref.victim_order(jobs) == ["low-old", "new", "mid", "old",
                                      "high-new"]


@pytest.mark.parametrize("pods, minimum, steps", [
    ({"j-0": 1, "j-1": 1, "j-2": 1, "j-3": 1}, 2,
     [["j-2", "j-3"], ["j-0", "j-1"]]),
    ({"j-0": 1, "j-1": 1}, 2, [["j-0", "j-1"]]),
    ({"j-0": 1}, 2, [["j-0"]]),
    ({"j-1": 4, "j-0": 4, "j-2": 4}, 1, [["j-1", "j-2"], ["j-0"]]),
], ids=("surplus-first", "at-minimum", "below", "by-name"))
def test_a_job_sheds_its_surplus_before_its_core_gang(ref, pods, minimum,
                                                      steps):
    assert ref.victim_steps(pods, minimum) == steps


def full_fleet(nodes: int = 6):
    """``nodes`` nodes, each under two training jobs of four one-GPU pods:
    (capacity, used, pods, candidates newest first, node 0's the
    newest)."""
    capacity = np.tile(NODE, (nodes, 1))
    used = np.tile(8 * POD, (nodes, 1))
    pods = np.full(nodes, 8)
    candidates = []
    for node in range(nodes):
        for half in (1, 0):
            uid = f"n{node}-{half}"
            candidates.append((uid, 2, {f"{uid}-{k}": node
                                        for k in range(4)}, POD))
    return capacity, used, pods, candidates


def test_the_reference_takes_the_newest_prefix_that_seats_the_replica(ref):
    capacity, used, pods, candidates = full_fleet()
    taken = ref.reference_victims(capacity, used, pods, 110, REPLICA,
                                  candidates)
    # Four whole nodes: the eight newest jobs, all their pods.
    assert taken == {name for _u, _m, job, _r in candidates[:8]
                     for name in job}
    assert len(taken) == 32
    assert ref.fewest_evictions(
        capacity, used, pods, 110, REPLICA,
        *ref.victims_by_node(6, np.repeat(np.arange(6), 8),
                             np.tile(POD, (48, 1)))) == 32


def test_an_idle_node_is_used_before_anything_is_taken(ref):
    capacity, used, pods, candidates = full_fleet()
    used[5] = 0.0
    pods[5] = 0
    taken = ref.reference_victims(capacity, used, pods, 110, REPLICA,
                                  candidates[:-2])
    assert len(taken) == 24
    count, most = ref.victims_by_node(6, np.repeat(np.arange(5), 8),
                                      np.tile(POD, (40, 1)))
    assert count.tolist() == [8, 8, 8, 8, 8, 0]
    assert ref.fewest_evictions(capacity, used, pods, 110, REPLICA, count,
                                most) == 24
    # With four idle nodes nothing is taken at all.
    used[2:] = 0.0
    pods[2:] = 0
    assert ref.reference_victims(capacity, used, pods, 110, REPLICA,
                                 candidates[:4]) == set()
    assert ref.fewest_evictions(capacity, used, pods, 110, REPLICA,
                                count, most) == 0


def test_a_replica_that_can_get_three_nodes_gets_none(ref):
    capacity, used, pods, candidates = full_fleet(3)
    assert ref.reference_victims(capacity, used, pods, 110, REPLICA,
                                 candidates) is None
    assert ref.fewest_evictions(
        capacity, used, pods, 110, REPLICA,
        *ref.victims_by_node(3, np.repeat(np.arange(3), 8),
                             np.tile(POD, (24, 1)))) == math.inf
    assert ref.seats(capacity - used, 110 - pods, REPLICA) is False
    assert ref.seats(capacity.copy(), np.full(3, 110), REPLICA[:3]) is True


def test_the_walk_stops_inside_a_job_where_its_surplus_is_enough(ref):
    """A one-pod preemptor of two GPUs: the newest job's surplus of two
    pods seats it, and its core gang stays."""
    capacity, used, pods, candidates = full_fleet(2)
    two = np.array([[8000.0, 64.0, 2.0]])
    taken = ref.reference_victims(capacity, used, pods, 110, two,
                                  candidates)
    assert taken == {"n0-1-2", "n0-1-3"}
    assert ref.fewest_evictions(
        capacity, used, pods, 110, two,
        *ref.victims_by_node(2, np.repeat(np.arange(2), 8),
                             np.tile(POD, (16, 1)))) == 2


@pytest.mark.parametrize("running, gone, faults", [
    ({"a": 4}, {"a": 2}, 0),             # down to its minimum
    ({"a": 4}, {"a": 3}, 1),             # one left of a gang of two
    ({"a": 4}, {"a": 4}, 0),             # gone whole
    ({"a": 4, "b": 2}, {"a": 3, "b": 1}, 2),
], ids=("at-minimum", "below", "whole", "two-jobs"))
def test_a_victim_gang_left_below_its_minimum_is_a_fault(ref, running, gone,
                                                         faults):
    assert ref.gangs_left_below_minimum(
        running, gone, {job: 2 for job in running}) == faults


@pytest.mark.parametrize("count, fault", [(0, 0), (1, 1), (3, 1), (4, 0)])
def test_a_gang_is_whole_or_not_at_all(ref, count, fault):
    assert ref.gang_faults(count, 4) == fault


def test_nodes_and_queues_past_their_bounds(ref):
    capacity = np.tile(NODE, (3, 1))
    used = np.array([NODE, [64000.0, 512.0, 9.0], [0.0, 0.0, 0.0]])
    assert ref.nodes_over_capacity(capacity, used, np.array([8, 8, 0]),
                                   110) == 1
    assert ref.nodes_over_capacity(capacity, used, np.array([8, 8, 111]),
                                   110) == 2
    bound = {"a": np.array([10.0, 10.0, 8.0]), "b": np.array([1.0, 1.0, 1.0])}
    assert ref.queues_over({"a": [10.0, 10.0, 8.0]}, bound) == 0
    assert ref.queues_over({"a": [10.0, 10.0, 8.5], "b": [0, 0, 0]},
                           bound) == 1
    total = np.array([64.0, 64.0, 64.0])
    assert ref.deserved_share(total, 4, 4, leaf=True).tolist() == [4.0] * 3
    assert ref.deserved_share(total, 4, 4, leaf=False).tolist() == [16.0] * 3


# -- (b) the preempt action's own filters, span and counters -------------------
def team_spec(peers: int = 1, strangers: int = 1, trainers: int = 3) -> dict:
    """Nodes of two GPUs, each under a two-pod job of queue ``team``:
    ``trainers`` at priority 50, ``peers`` at the preemptor's 125; and
    ``strangers`` of queue ``other`` at 50.  A pending ``vip`` of ``team``
    asks two GPUs."""
    jobs = {}
    kinds = ([("peer", "team", 125)] * peers
             + [("stranger", "other", 50)] * strangers
             + [("trainer", "team", 50)] * trainers)
    for i, (kind, queue, priority) in enumerate(kinds):
        jobs[f"{kind}{i}"] = {
            "queue": queue, "priority": priority, "min_available": 2,
            # The decoys are the newest, so an order blind to queues or
            # priorities would reach them first.
            "creation_ts": 100.0 - i,
            "tasks": [{"gpu": 1, "cpu": "1", "status": "RUNNING",
                       "node": f"n{i}"}] * 2}
    jobs["vip"] = {"queue": "team", "priority": 125, "preemptible": False,
                   "min_available": 1, "creation_ts": 200.0,
                   "tasks": [{"gpu": 2, "cpu": "1"}]}
    return {"nodes": {f"n{i}": {"gpu": 2} for i in range(len(kinds))},
            "queues": {"team": {"deserved": {"gpu": 64}},
                       "other": {"deserved": {"gpu": 64}}},
            "jobs": jobs}


def test_the_survey_lists_a_queues_jobs_lowest_priority_then_newest():
    ssn = build_session(team_spec())
    survey = survey_preempt_victims(ssn)
    assert [pg.uid for pg in survey["team"]] == [
        "trainer2", "trainer3", "trainer4", "peer0"]
    assert [pg.uid for pg in survey["other"]] == ["stranger1"]


def preempt_cycle(ssn):
    TRACER.begin_cycle(1)
    before = {c: METRICS.counters.get(c, 0.0) for c in COUNTERS}
    run_action(ssn, "preempt")
    trace = TRACER.end_cycle()
    moved = {c: METRICS.counters.get(c, 0.0) - before[c] for c in COUNTERS}
    return trace, moved


@pytest.mark.parametrize("counted", [True, False],
                         ids=["counted", "uncounted"])
def test_the_preemptor_passes_over_its_peers_and_other_queues(counted):
    ssn = build_session(team_spec())
    if not counted:
        for pg in ssn.cluster.podgroups.values():
            pg.invalidate_caches()
    trace, moved = preempt_cycle(ssn)
    evicted = {uid.rsplit("-", 1)[0] for uid in ssn.cache.evicted}
    assert evicted == {"trainer2"}           # the newest of lower priority
    assert moved[SOLVED] == 1 and moved[UNSOLVED] == 0
    (survey,) = [s for s in trace.spans if s.name == "preempt:survey"]
    assert survey.kind == "preempt"
    # Six PodGroups asked, and none read off its pods where the
    # session's opening counted them for the queue sums: a PodGroup keeps
    # what its pods add up to (``tests/test_pod_census.py``).  Where
    # nobody has, the five victims' two pods each are read, and the same
    # victims come of it.
    assert survey.attrs == {"queues": 2, "victims": 5, "podgroups": 6,
                            "pod_visits": 0 if counted else 10}


def test_a_preemptor_with_peers_alone_takes_nothing():
    ssn = build_session(team_spec(peers=2, strangers=2, trainers=0))
    trace, moved = preempt_cycle(ssn)
    assert ssn.cache.evicted == []
    # Filtered to nothing before the solver: no preemptor was solved for.
    assert moved[SOLVED] == 0 and moved[UNSOLVED] == 0
    assert [s.name for s in trace.spans if s.name == "solve:job"] == []
    assert len([s for s in trace.spans if s.name == "preempt:survey"]) == 1


def test_an_unsolved_preemptor_is_counted_as_such():
    """One trainer holds one GPU of two-GPU nodes beside a fixed pod: its
    leaving seats nothing, the solver runs and fails."""
    spec = team_spec(peers=0, strangers=0, trainers=1)
    spec["jobs"]["trainer0"]["tasks"] = [
        {"gpu": 1, "cpu": "1", "status": "RUNNING", "node": "n0"}]
    spec["jobs"]["trainer0"]["min_available"] = 1
    spec["jobs"]["pin"] = {"queue": "team", "priority": 125,
                           "preemptible": False, "min_available": 1,
                           "tasks": [{"gpu": 1, "cpu": "1",
                                      "status": "RUNNING", "node": "n0"}]}
    ssn = build_session(spec)
    _trace, moved = preempt_cycle(ssn)
    assert ssn.cache.evicted == []
    assert moved[SOLVED] == 0 and moved[UNSOLVED] == 1


def test_no_pending_job_no_survey():
    spec = team_spec()
    del spec["jobs"]["vip"]
    trace, moved = preempt_cycle(build_session(spec))
    assert [s.name for s in trace.spans if s.name == "preempt:survey"] == []
    assert moved[SOLVED] == moved[UNSOLVED] == 0


def deep_spec(gang: list) -> dict:
    """Eight two-GPU nodes, each under a rigid two-pod trainer; a pending
    preemptor of ``gang`` (GPUs a pod) whose fit needs four of them, so
    that the prescreen is asked (``scenario_prescreen_after`` 1)."""
    jobs = {f"trainer{i}": {
        "queue": "team", "priority": 50, "min_available": 2,
        "creation_ts": 100.0 - i,
        "tasks": [{"gpu": 1, "cpu": "1", "status": "RUNNING",
                   "node": f"n{i}"}] * 2} for i in range(8)}
    jobs["vip"] = {"queue": "team", "priority": 125, "preemptible": False,
                   "min_available": len(gang), "creation_ts": 200.0,
                   "tasks": [{"gpu": 2, "cpu": cpu} for cpu in gang]}
    return {"nodes": {f"n{i}": {"gpu": 2} for i in range(8)},
            "queues": {"team": {"deserved": {"gpu": 64}}}, "jobs": jobs}


@pytest.mark.parametrize("gang, form, counted, steps", [
    (["1"] * 4, "counted", 1, 0),
    (["2", "1", "1", "1"], "grouped", 0, 2),
], ids=("alike", "leader-and-workers"))
def test_every_dispatched_prescreen_counts_whatever_its_form(gang, form,
                                                             counted, steps):
    from kai_scheduler_tpu.framework.conf import SchedulerConfig
    ssn = build_session(deep_spec(gang),
                        SchedulerConfig(scenario_prescreen_after=1))
    trace, moved = preempt_cycle(ssn)
    (span,) = [s for s in trace.spans if s.name == "solve:prescreen"]
    assert span.attrs["form"] == form and "declined" not in span.attrs
    assert moved[CALLS] == 1
    assert moved[COUNTED] == counted
    assert moved["scenario_prescreen_scan_steps_total"] == steps
    assert moved[SOLVED] == 1
    assert len(ssn.cache.evicted) == 8


def test_a_declined_prescreen_counts_no_call():
    from kai_scheduler_tpu.framework.conf import SchedulerConfig
    ssn = build_session(deep_spec(["1"] * 4), SchedulerConfig(
        scenario_prescreen_after=1, scenario_prescreen_max=0))
    trace, moved = preempt_cycle(ssn)
    asked = [s for s in trace.spans if s.name == "solve:prescreen"]
    # Asked again after every scenario that fails, and declined each time.
    assert asked and all(s.attrs["declined"] == "disabled" for s in asked)
    assert moved[CALLS] == 0 and moved[SOLVED] == 1


# -- (c) the deployment through the benchmark's own loop -----------------------
@pytest.fixture(scope="module", params=[
    (nodes, seed) for nodes in CUTS for seed in DEPLOY_SEEDS],
    ids=lambda p: f"{p[0]}n-seed{p[1]}")
def driven(request):
    """Five cycles of the deployment through ``Scheduler.run_once``, with
    both kinds of decoy planted, and the last cycle's trace."""
    nodes, seed = request.param
    cell = small_cell(nodes)
    TRACER.reset()
    client = cell.generator.Client(cell, seed, counters=COUNTERS)
    decoys = client.plant_decoys()
    for _ in range(5):
        client.cycle()
    return types.SimpleNamespace(
        cell=cell, client=client, nodes=nodes, decoys=decoys,
        replicas=CUTS[nodes]["replicas"], trace=TRACER.get_trace(),
        verdict=cell.generator.compare(client.records[1:], client.ledger,
                                       cell))


def test_every_count_is_zero(driven):
    out, cell = driven.verdict, driven.cell
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 15
    assert out["correct"], out["compared"]
    assert all(v == [0, 0] for v in out["compared"].values())
    r = driven.replicas
    # Every cycle: a step preempted for and the step before it bound.
    assert out["failed"] == 0 and out["attempted"] == 3 * r
    assert out["run"]["evictions_per_cycle"] == [32 * r]
    assert out["run"]["binds_per_cycle"] == [4 * r]
    assert out["run"]["commits_per_cycle"] == [r]
    assert out["run"]["solves_per_cycle"] == [r]
    assert out["run"]["prescreens_per_cycle"] == [r]
    assert out["run"]["bind_cycles_after_arrival"] == [1]
    assert out["bound_pods"] == 4 * 4 * r


def test_every_commit_seats_one_replica_on_four_emptied_nodes(driven):
    """By the client's own book: a commit nominates the four pods of one
    replica and evicts the 32 pods of the eight jobs on those very nodes,
    every one a training job of the team's queue."""
    client = driven.client
    for rec in client.records:
        assert len(rec.commits) == driven.replicas
        seated = set()
        for commit in rec.commits:
            assert commit.unknown_evictions == 0
            pods = [pod for pod, _node in commit.nominated]
            assert len(pods) == 4
            (replica,) = {pod.rsplit("-", 1)[0] for pod in pods}
            assert replica not in seated
            seated.add(replica)
            nodes = sorted(node for _pod, node in commit.nominated)
            assert len(set(nodes)) == 4
            assert sorted({v.node for v in commit.evicted}) == nodes
            assert len(commit.evicted) == 32
            assert {(v.queue, v.preemptible, v.priority)
                    for v in commit.evicted} == {(client.team, True, 50.0)}
            assert len({v.job for v in commit.evicted}) == 8
        assert seated == {g.uid for g in rec.arrived}


def test_the_decoys_are_passed_over_and_still_run(driven):
    """The four newest jobs of the book, which a preemptor blind to queues
    or to priorities would have taken in the first cycle."""
    client = driven.client
    taken = {v.job for rec in client.records for v in rec.evicted}
    decoys = driven.decoys["queue"] + driven.decoys["priority"]
    assert len(decoys) == 4 and not taken & set(decoys)
    for uid in driven.decoys["queue"]:
        assert client.jobs[uid].queue != client.team
        assert client.cluster.podgroups[uid].queue_id != client.team
        assert len(client.jobs[uid].pods) == 4
    for uid in driven.decoys["priority"]:
        assert client.cluster.podgroups[uid].priority == 125
        assert len(client.jobs[uid].pods) == 4
    # What was taken in the first cycle: the newest jobs that are left.
    first = {v.job for v in client.records[0].evicted}
    newest = sorted((j for j in first), reverse=True)
    assert newest[0] < min(decoys)


def test_the_fleet_stands_still_over_the_cycles(driven):
    """Three steps in flight once warm: the team's nodes under training
    jobs, nominated, bound or running replicas add up to its share."""
    client = driven.client
    team_nodes = int(round(driven.nodes * driven.cell.config["occupancy"][
        "preemptible_nodes_share"]))
    for rec in client.records[2:]:
        free = np.flatnonzero(rec.used_after[:, 2] == 0)
        assert len(free) == 4 * driven.replicas       # nominated, not bound
        assert len(rec.refilled) == 8 * driven.replicas \
            or rec.index < 3
    held = {n for j in client.jobs.values()
            if j.queue == client.team or j.uid in driven.decoys["queue"]
            for n in j.pods.values()}
    running = {n for gang, _pg, _ran in client.running
               for n in gang.bound.values()}
    assert len(held | running) + 4 * driven.replicas == team_nodes


def children(trace, span):
    return [s for s in trace.spans if s.parent_id == span.span_id]


def test_the_span_tree_of_a_cycle_of_preemptors(driven):
    trace, r = driven.trace, driven.replicas
    (action,) = [s for s in trace.spans if s.name == "action:preempt"]
    inside = children(trace, action)
    assert [s.name for s in inside] == ["preempt:survey"] + ["solve:job"] * r
    survey = inside[0]
    assert survey.attrs["queues"] >= 1
    for solve in inside[1:]:
        assert solve.attrs["action"] == "preempt"
        assert solve.attrs["tasks"] == 4 and solve.attrs["solved"] is True
        # The first step fails at the queue's limit before any dispatch;
        # the prescreen then names the sixteenth (four nodes, two jobs a
        # node, two steps a job) and the fourteen between are skipped.
        assert (solve.attrs["tried"], solve.attrs["skipped"]) == (2, 14)
        names = [s.name for s in children(trace, solve)]
        assert names == ["solve:precheck", "solve:scenario",
                         "solve:prescreen", "solve:scenario",
                         "statement:commit"]
        prescreen = children(trace, solve)[2]
        assert prescreen.attrs["form"] == "grouped"
        assert prescreen.attrs["runs"] == 2
        assert prescreen.attrs["mask"] == "none"
        assert prescreen.attrs["first_feasible"] == 14
        commit = children(trace, solve)[4]
        assert commit.attrs == {"binds": 0, "evictions": 32}
        first, second = (children(trace, solve)[i] for i in (1, 3))
        assert not [s for s in children(trace, first)
                    if s.name.startswith("dispatch:")]
        assert [s.name for s in children(trace, second)
                if s.name.startswith("dispatch:")] == [
            "dispatch:allocate_jobs_multi",
            "dispatch:allocate_jobs_multi_fetch"]


def test_the_counters_of_a_cycle_of_preemptors(driven):
    r = driven.replicas
    for rec in driven.client.records:
        assert rec.counters[SOLVED] == r
        assert rec.counters[UNSOLVED] == 0
        assert rec.counters[CALLS] == r
        assert rec.counters[COUNTED] == 0
        # The leader's run and the workers', a call.
        assert rec.counters["scenario_prescreen_scan_steps_total"] == 2 * r
        assert rec.counters["scenarios_skipped_by_prescreen_total"] == 14 * r


def test_the_span_and_the_counters_feed_the_cells_three_metrics(driven):
    from benchmark.harness import readers
    wanted = {"preempt_host_ms", "preemptors_solved", "prescreen_calls"}
    metrics = [m for m in the_cell().per_layer if m["name"] in wanted]
    assert {m["name"] for m in metrics} == wanted
    assert set(readers.counters_wanted(metrics)) == {SOLVED, CALLS}
    out = readers.read_all(metrics, {"records": driven.client.records})
    assert out["preemptors_solved"] == {"value": float(driven.replicas),
                                        "unit": "jobs/cycle"}
    assert out["prescreen_calls"] == {"value": float(driven.replicas),
                                      "unit": "calls/cycle"}
    assert out["preempt_host_ms"]["value"] > 0
    # The action's host time leaves its dispatches out.
    rec = driven.client.records[-1]
    (action,) = [s for s in rec.spans if s[0] == "action:preempt"]
    assert out["preempt_host_ms"]["value"] < 1e3 * max(
        s[5] for r in driven.client.records for s in r.spans
        if s[0] == "action:preempt")
    assert action[5] > 0


def test_reclaim_and_consolidation_find_nothing_to_do(driven):
    names = [s.name for s in driven.trace.spans]
    assert "reclaim:job" not in names and "reclaim:survey" not in names
    assert "consolidation:job" not in names


# -- (d) a replica for which only three nodes can be freed ---------------------
def test_nothing_is_evicted_for_a_replica_that_can_get_three_nodes():
    """One replica a cycle on a fleet whose team holds five nodes; two of
    them are half under a training job and half under a fixed four-GPU pod
    of another queue, so the victims' GPUs add up to the replica's 32 (the
    solver's budget passes) and only three whole nodes can be emptied."""
    cell = small_cell(64)
    cell.config["occupancy"]["preemptible_nodes_share"] = 5 / 64
    cell.traffic["replicas_per_cycle"] = 1
    client = cell.generator.Client(cell, 3, counters=COUNTERS)
    gen = sys.modules[type(client).__module__]
    by_node = {}
    for job in client.jobs.values():
        if job.preemptible:
            by_node.setdefault(next(iter(job.pods.values())),
                               []).append(job)
    assert len(by_node) == 5
    other = next(q for q in client.ledger.queue_parent
                 if q != client.team
                 and client.ledger.queue_parent[q] is not None)
    half = np.array([16000.0, 128.0, 4.0])
    for node in sorted(by_node)[:2]:
        gone = by_node[node][0]
        client._remove([gen.Victim(
            pod, gone.uid, gone.queue, True, gone.priority, gone.created,
            gone.min_available, node, gone.req) for pod in list(gone.pods)])
        pin = gen.base.Job(f"pin-{node}", other, False, 1, half,
                           {f"pin-{node}-0": node},
                           gen.base._requirements(
                               {"cpu": "16", "memory": "128Gi", "gpu": 4}))
        client._book(pin)
        client.ledger.charge(other, np.array([node]), half[None, :])
    client.cluster.invalidate_aggregates()
    TRACER.reset()
    for _ in range(2):
        client.cycle()
    trace = TRACER.get_trace()
    (solve,) = [s for s in trace.spans if s.name == "solve:job"]
    # The budget passed (32 GPUs of victims), a scenario was simulated,
    # the prescreen found no prefix that seats four pods, and every
    # remaining step was passed over.
    assert solve.attrs["victims"] == 8 and solve.attrs["solved"] is False
    (prescreen,) = [s for s in trace.spans if s.name == "solve:prescreen"]
    assert prescreen.attrs["feasible"] == 0
    for rec in client.records:
        assert rec.evicted == [] and rec.commits == [] and rec.bound == {}
        assert rec.counters[SOLVED] == 0 and rec.counters[UNSOLVED] == 1
    out = cell.generator.compare(client.records, client.ledger, cell)
    moved = {k for k, (v, lim) in out["compared"].items() if v > lim}
    assert moved == {"replicas_not_bound"}
    assert out["compared"]["replicas_not_bound"][0] == 1


# -- (e) the controls in the program's place -----------------------------------
def controls():
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import control_preempt
    finally:
        sys.path.pop(0)
    return control_preempt


@pytest.mark.parametrize("nodes", CUTS)
@pytest.mark.parametrize("kind", ("queue_blind", "priority_blind",
                                  "one_more", "partial_gang", "sound"))
def test_a_control_in_the_programs_place_moves_its_own_counts(kind, nodes):
    ctl = controls()
    out = ctl.run_control(CELL, 7, kind, cut=CUTS[nodes])
    assert out["correct"] == (kind == "sound")
    assert ctl.as_said(out), out["compared"]
    moved = {k: v[0] for k, v in out["compared"].items() if v[0]}
    assert moved == {
        "queue_blind": {"victims_from_other_queue": 8},
        "priority_blind": {"victims_not_lower_priority": 8},
        "one_more": {"evictions_beyond_need": 4,
                     "evictions_not_reference": 4},
        "partial_gang": {"gangs_partly_bound": 1}, "sound": {}}[kind]


# -- (f) the trial before the fleet ---------------------------------------------
def test_the_trial_passes_on_this_program():
    cell = the_cell()
    out = cell.generator.try_inqueue_preemption(cell, 3)
    assert out["nodes"] == 256 and out["replicas"] == 4
    assert out["evictions_per_cycle"] == [128]


@pytest.mark.parametrize("blind, count", [
    ("queue", "victims_from_other_queue"),
    ("priority", "victims_not_lower_priority"),
])
def test_the_trial_stops_a_program_whose_preemptor_is_blind(monkeypatch,
                                                            blind, count):
    """A preempt action that surveys every queue's jobs as one list, or
    that takes its equals: the decoys are the newest, it takes them, and
    the trial stops with status 1 before the fleet is built."""
    cell = the_cell()
    survey = preempt.survey_preempt_victims

    def one_list(ssn):
        out = survey(ssn)
        merged = sorted((pg for jobs in out.values() for pg in jobs),
                        key=lambda pg: (pg.priority, -pg.creation_ts))
        return {queue: merged for queue in ssn.cluster.queues}

    def equals_too(ssn):
        class Lower(int):
            def __lt__(self, other):
                return True
        out = survey(ssn)
        for jobs in out.values():
            jobs.sort(key=lambda pg: -pg.creation_ts)
            for pg in jobs:
                pg.priority = Lower(pg.priority)
        return out

    monkeypatch.setattr(preempt, "survey_preempt_victims",
                        one_list if blind == "queue" else equals_too)
    with pytest.raises(SystemExit) as stop:
        cell.generator.try_inqueue_preemption(cell, 3)
    assert "cannot run the configuration preempt-98k" in str(stop.value)
    assert count in str(stop.value)
