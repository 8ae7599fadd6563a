"""The deployment ``defrag-98k`` at 64 and 256 nodes, on the CPU: the
benchmark's own client (``benchmark/generators/consolidation_gangs.py``)
drives ``Scheduler.run_once`` over a fleet with idle GPUs on every node and
no node empty, a gang of whole-node pods a cycle is seated by moving
fragment jobs that stand alone on their nodes and bound a cycle later with
what was moved, and the plain reference the chip's ``correct`` uses
(``benchmark/reference/relocation.py``, loaded by path, no import of the
program) finds all eleven numbers 0.  And what the action says of itself
under ``action:consolidation``: the span tree, its attributes and the
counters (docs/OBSERVABILITY.md "Span model")."""

import os
import types

import pytest

from kai_scheduler_tpu.utils.metrics import _key
from kai_scheduler_tpu.utils.tracing import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "defrag98k-pytorchjob-1k"
SEEDS = (3, 11, 3000000019)
# nodes, share of them fragmented, queue tree, the fragments' queues,
# whole-node gang, the arriving gang's workers, victims the solver considers.
WIDTHS = {
    64: {"share": 0.75, "departments": 2, "leaves": 2, "fragment_queues": 2,
         "whole": 4, "workers": 3, "victims": 16},
    256: {"share": 0.25, "departments": 4, "leaves": 2,
          "fragment_queues": 1, "whole": 8, "workers": 7, "victims": 32},
}
COUNTERS = ("scenario_prescreen_prefixes_total",
            "scenario_prescreen_feasible_total",
            "scenarios_skipped_by_prescreen_total",
            "scenario_prescreen_counted_total",
            "scenario_prescreen_scan_steps_total",
            _key("solver_evictions_total", {"action": "consolidation"}),
            _key("solver_victims_replaced_total",
                 {"action": "consolidation"}))


def small_cell(nodes: int):
    """The cell as ``BENCHMARK.json`` names it (its generator and its
    reference loaded by path, as a chip run loads them) with the fleet,
    the gang and the solver's caps cut to ``nodes``; every shape (node,
    pods, queue levels) as the files have it."""
    from benchmark.harness import spec
    from benchmark.tests.control_relocation import cut_cell
    cell = spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)
    assert cell.reference.__file__ == os.path.join(
        BENCH, "reference", "relocation.py")
    assert cell.generator.__file__ == os.path.join(
        BENCH, "generators", "consolidation_gangs.py")
    return cut_cell(cell, nodes=nodes, **WIDTHS[nodes])


@pytest.fixture(scope="module", params=[
    (nodes, seed) for nodes in WIDTHS for seed in SEEDS],
    ids=lambda p: f"{p[0]}n-seed{p[1]}")
def driven(request):
    """Six cycles of the deployment, and the last cycle's trace."""
    nodes, seed = request.param
    cell = small_cell(nodes)
    TRACER.reset()
    client = cell.generator.build(cell, seed, counters=COUNTERS)
    idle0 = client.ledger.capacity - client.ledger.used
    for _ in range(6):
        client.cycle()
    return types.SimpleNamespace(cell=cell, client=client, nodes=nodes,
                                 gang=1 + WIDTHS[nodes]["workers"],
                                 idle0=idle0, trace=TRACER.get_trace())


def test_the_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(BENCH, "reference", "relocation.py")
    tree = ast.parse(open(path).read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "numpy"}


def test_every_number_is_zero(driven):
    cell, client, gang = driven.cell, driven.client, driven.gang
    out = cell.generator.compare(client.records[1:], client.ledger, cell)
    assert out["correct"], out["compared"]
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 11
    assert all(v == [0, 0] for v in out["compared"].values())
    # One consolidation in every cycle, and from the second on the bind of
    # the gang seated a cycle before with the pods moved for it: two pods
    # moved for each node the gang lacked, a place pipelined for each and
    # for each pod of the gang, one prescreen, of the grouped form (counted
    # prescreens 0: the master and its workers are two runs).
    assert out["failed"] == 0 and out["attempted"] == 4
    assert out["run"]["evictions_per_cycle"] == [2 * gang]
    assert out["run"]["places_per_cycle"] == [3 * gang]
    assert out["run"]["binds_per_cycle"] == [3 * gang]
    assert out["run"]["prescreens_per_cycle"] == [1]
    assert out["run"]["counted_prescreens_per_cycle"] == [0.0]
    assert out["run"]["bind_cycles_after_arrival"] == [1]
    assert out["bound_pods"] == 5 * 3 * gang
    # Every moved pod's replacement was bound in the next cycle.
    for before, after in zip(client.records, client.records[1:]):
        assert {m.replaced_by for m in before.moved} == after.rebound


def test_no_node_is_empty_and_none_has_a_whole_nodes_room(driven):
    client, ledger, gang = driven.client, driven.client.ledger, driven.gang
    idle = driven.idle0
    assert (ledger.capacity[:, 2] - idle[:, 2] > 0).all()
    assert idle[:, 2].max() == 6 < 8
    # The idle GPUs would hold the gang several times over.
    assert idle[:, 2].sum() >= 4 * 8 * gang
    # The fleet stands still from the third cycle on, the first that both
    # binds a gang and moves for the next (the first gang completes before
    # the fourth, and as many fragment jobs with it as fill its nodes):
    # as many pods, as many GPUs idle, and still no node with a whole
    # node's room when the next gang arrives.
    pods = [rec.pods_after.sum() for rec in client.records[2:]]
    assert len(set(pods)) == 1
    used = [rec.used_after[:, 2].sum() for rec in client.records[2:]]
    assert len(set(used)) == 1
    whole = [j for j in client.jobs.values() if not j.preemptible]
    assert not {j.queue for j in whole} & (
        set(client.fragment_queues) | {client.gang_queue})
    assert all(j.queue in client.fragment_queues
               for j in client.jobs.values() if j.preemptible)
    for rec in client.records:
        assert rec.consolidator.queue == client.gang_queue
        assert {client.jobs[m.job].queue for m in rec.moved
                if m.job in client.jobs} <= set(client.fragment_queues)


def children(trace, span):
    return [s for s in trace.spans if s.parent_id == span.span_id]


def only(spans, name):
    found = [s for s in spans if s.name == name]
    assert len(found) == 1, (name, [s.name for s in spans])
    return found[0]


def test_the_span_tree_under_the_consolidation_action(driven):
    trace, cut, gang = driven.trace, WIDTHS[driven.nodes], driven.gang
    action = only(trace.spans, "action:consolidation")
    under = children(trace, action)
    order = only(under, "consolidation:order")
    assert order.kind == "consolidation" and order.attrs["jobs"] == 1
    job = only(under, "consolidation:job")
    assert job.kind == "consolidation" and job.attrs["success"] is True
    assert job.attrs["queue"] == driven.client.gang_queue
    victims = only(children(trace, job), "consolidation:victims")
    # Every fragment job of the fleet, of which the solver takes its cap.
    frag = sum(1 for j in driven.client.jobs.values() if j.preemptible)
    # And what the pass walked to find them (PR 55): every PodGroup asked
    # (the book's jobs and the gangs alive), the two pods of each fragment
    # job read.
    client = driven.client
    asked = len(client.jobs) + len(client.pending) + len(client.running)
    assert victims.attrs == {"victims": frag, "pod_visits": 2 * frag,
                             "podgroups": asked}
    assert asked == len(client.cluster.podgroups) > frag
    assert job.attrs["victims"] == frag > cut["victims"]
    solve = only(children(trace, job), "solve:job")
    assert solve.kind == "solver"
    assert solve.attrs == {
        "job": job.attrs["job"], "action": "consolidation", "tasks": gang,
        "victims": cut["victims"], "steps": cut["victims"],
        "tried": 2, "skipped": gang - 2, "solved": True,
        "replaced": 2 * gang}
    inside = children(trace, solve)
    assert [s.name for s in inside] == [
        "solve:precheck", "solve:scenario", "solve:prescreen",
        "solve:scenario", "statement:commit"]
    first, last = (s for s in inside if s.name == "solve:scenario")
    assert first.attrs == {"prefix": 1, "evicted": 2, "fits": False}
    assert last.attrs == {"prefix": gang, "evicted": 2 * gang - 2,
                          "fits": True}
    prescreen = only(inside, "solve:prescreen")
    scored = cut["victims"] - 1
    assert prescreen.attrs == {
        "prefixes": cut["victims"], "steps": scored,
        "rows": 2 * cut["victims"],
        "t_pad": gang, "form": "grouped", "mask": "none",
        "strategy": "binpack", "runs": 2, "level": "none", "domains": 0,
        "feasible": scored - (gang - 2), "first_feasible": gang - 2}
    dispatch = only(children(trace, prescreen),
                    "dispatch:scenario_prescreen")
    assert dispatch.kind == "kernel"
    # Each confirm is one exact scan over the gang and the victim jobs.
    for scenario in (first, last):
        only(children(trace, scenario), "dispatch:allocate_jobs_multi")
    commit = only(inside, "statement:commit")
    assert commit.kind == "commit"
    assert commit.attrs == {"binds": 0, "evictions": 2 * gang}
    # The action's spans are few whatever the victims: none per task.
    assert sum(s.kind in ("solver", "consolidation")
               for s in trace.spans) == 8
    # Reclaim and preempt found nothing to do.
    for name in ("action:reclaim", "action:preempt"):
        assert not children(trace, only(trace.spans, name))


def test_the_counters_move_with_the_spans(driven):
    cut, gang = WIDTHS[driven.nodes], driven.gang
    scored = cut["victims"] - 1
    for rec in driven.client.records:
        assert rec.counters == {
            "scenario_prescreen_prefixes_total": scored,
            "scenario_prescreen_feasible_total": scored - (gang - 2),
            "scenarios_skipped_by_prescreen_total": gang - 2,
            "scenario_prescreen_counted_total": 0,
            # A master and its workers: two steps over the pools, not one
            # a pod.
            "scenario_prescreen_scan_steps_total": 2,
            _key("solver_evictions_total", {"action": "consolidation"}):
            2 * gang,
            _key("solver_victims_replaced_total",
                 {"action": "consolidation"}): 2 * gang}


def test_where_no_fragment_can_land_again_nothing_is_moved():
    """The fragments' pods take three GPUs each, so every fragmented node
    has two idle: the idle total holds the gang four times over, a node's
    two pods leaving would empty it, and no moved pod finds three GPUs
    anywhere else.  Every scenario the solver simulates seats the gang and
    strands its victims, so none is committed: nothing is evicted, by this
    action or by the two after it (the fragments' queues stand within their
    deserved shares), and the gang stays pending."""
    from benchmark.harness import spec
    from benchmark.tests.control_relocation import cut_cell
    cell = cut_cell(spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT),
                    nodes=64, share=0.625, departments=2, leaves=4,
                    fragment_queues=4, whole=4, workers=1, victims=16)
    frag = cell.config["occupancy"]["fragment"]
    frag["pod"]["gpu"] = 3
    cell.config["scheduler"]["max_scenarios_per_job"] = 6
    TRACER.reset()
    client = cell.generator.build(cell, 5, counters=COUNTERS)
    ledger = client.ledger
    idle = ledger.capacity - ledger.used
    assert idle[:, 2].max() == 2 and idle[:, 2].sum() >= 4 * 16
    for queue in client.fragment_queues:
        assert (ledger.queue_used[queue]
                <= ledger.queue_limit[queue] / 4.0).all()
    for _ in range(2):
        rec = client.cycle()
        assert not rec.moved and not rec.placed and not rec.bound
        assert not any(value for name, value in rec.counters.items()
                       if name.startswith("solver_"))
    spans = TRACER.get_trace().spans
    job = only(spans, "consolidation:job")
    # Every fragment job of the fleet: no gang has bound, so none has
    # completed and no new fragment has come.
    assert job.attrs["success"] is False and job.attrs["victims"] == 40
    solves = [s for s in spans if s.name == "solve:job"]
    solve = solves[0]
    assert solve.attrs["action"] == "consolidation"
    assert solve.attrs["solved"] is False and solve.attrs["replaced"] == 0
    assert solve.attrs["tried"] == 6
    scenarios = [s for s in spans if s.name == "solve:scenario"
                 and s.parent_id == solve.span_id]
    assert [s.attrs["fits"] for s in scenarios] == 6 * [False]
    assert not [s for s in spans if s.name == "statement:commit"]
    assert len(client.pending) == 2
    out = cell.generator.compare(client.records, client.ledger, cell)
    assert out["compared"]["gangs_not_bound"] == [1, 0]
    assert {k for k, v in out["compared"].items() if v[0]} == {
        "gangs_not_bound"}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("nodes", WIDTHS)
def test_a_program_that_moves_the_newest_first_is_stopped_at_once(
        nodes, seed, monkeypatch):
    """The generator tries the deployment's own guarantee on 64 nodes
    before it builds the fleet: with the old order the trial's cycle moves
    the newest job, which shares its node, and two that stand alone, six
    pods where four seat the gang, on every seed; the program as it stands
    moves four and the run goes on (``driven`` builds through the trial)."""
    from benchmark.tests.control_relocation import newest_first
    from kai_scheduler_tpu.actions import consolidation
    cell = small_cell(nodes)
    cell.generator.try_fewest_moves(cell, seed)
    monkeypatch.setattr(consolidation, "collect_consolidation_victims",
                        newest_first)
    with pytest.raises(SystemExit) as stop:
        cell.generator.build(cell, seed)
    assert "cannot run the configuration" in str(stop.value)
    assert "moved 6 pods" in str(stop.value)
    assert "2 beyond the fewest" in str(stop.value)
