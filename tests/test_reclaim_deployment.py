"""The deployment ``north-star-98k`` at 64 and 256 nodes, on the CPU: the
benchmark's own client (``benchmark/generators/reclaim_gangs.py``) drives
``Scheduler.run_once`` over a fleet that one queue's jobs fill, a gang a
cycle reclaims and binds a cycle later, and the plain reference the chip's
``correct`` uses (``benchmark/reference/eviction.py``, loaded by path, no
import of the program) finds all eleven numbers 0.  And what the solver
says of itself under ``action:reclaim``: the span tree, its attributes and
the four counters (docs/OBSERVABILITY.md "Span model")."""

import os
import types

import pytest

from kai_scheduler_tpu.utils.metrics import _key
from kai_scheduler_tpu.utils.tracing import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEEDS = (3, 11, 3000000019)
# nodes, share of them under the occupier's preemptible jobs, queue tree,
# whole-node gang, the reclaimer's gang, victims the solver considers.
WIDTHS = {
    64: {"share": 1.0, "departments": 2, "leaves": 2, "whole": 4,
         "gang": 24, "victims": 32},
    256: {"share": 0.25, "departments": 4, "leaves": 4, "whole": 8,
          "gang": 32, "victims": 64},
}
COUNTERS = ("scenario_prescreen_prefixes_total",
            "scenario_prescreen_feasible_total",
            "scenarios_skipped_by_prescreen_total",
            _key("solver_evictions_total", {"action": "reclaim"}),
            "device_kernel_calls")


def small_cell(nodes: int):
    """The cell as ``BENCHMARK.json`` names it (its generator and its
    reference loaded by path, as a chip run loads them) with the fleet,
    the gang and the solver's caps cut to ``nodes``; every shape (node,
    pods, queue levels) as the files have it."""
    from benchmark.harness import spec
    from benchmark.tests.control_reclaim import cut_cell
    cell = spec.Cell(spec.load_benchmark(ROOT), "ns98k-reclaim-wide", ROOT)
    assert cell.reference.__file__ == os.path.join(
        BENCH, "reference", "eviction.py")
    assert cell.generator.__file__ == os.path.join(
        BENCH, "generators", "reclaim_gangs.py")
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
    for _ in range(6):
        client.cycle()
    return types.SimpleNamespace(cell=cell, client=client, nodes=nodes,
                                 trace=TRACER.get_trace())


def test_the_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(BENCH, "reference", "eviction.py")
    tree = ast.parse(open(path).read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "math", "numpy"}


def test_every_number_is_zero(driven):
    cell, client = driven.cell, driven.client
    out = cell.generator.compare(client.records[1:], client.ledger, cell)
    assert out["correct"], out["compared"]
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 11
    assert all(v == [0, 0] for v in out["compared"].values())
    gang = WIDTHS[driven.nodes]["gang"]
    # One reclaim and one bind in every cycle once the first gang waits:
    # the gang's pods bound a cycle after they arrived, as many victims
    # gone for the next one, one prescreen dispatched.
    assert out["failed"] == 0 and out["attempted"] == 4
    assert out["run"]["evictions_per_cycle"] == [gang]
    assert out["run"]["binds_per_cycle"] == [gang]
    assert out["run"]["prescreens_per_cycle"] == [1]
    assert out["run"]["bind_cycles_after_arrival"] == [1]
    assert out["bound_pods"] == 5 * gang


def test_the_fleet_is_full_and_the_victims_are_the_occupiers(driven):
    client, ledger = driven.client, driven.client.ledger
    first = client.records[0]
    assert (first.used_before[:, 2] == ledger.capacity[:, 2]).all()
    for rec in client.records:
        assert {v.queue for v in rec.evicted} == {client.occupier}
        assert rec.reclaimer.queue == client.reclaimer
    parent = ledger.queue_parent
    assert parent[client.occupier] != parent[client.reclaimer]
    whole = [j for j in client.jobs.values() if not j.preemptible]
    share = WIDTHS[driven.nodes]["share"]
    assert sum(len(j.pods) for j in whole) == round(
        driven.nodes * (1 - share))
    # The occupier holds preemptible jobs alone, the reclaimer's queue
    # nothing, and no queue more whole-node pods than its deserved share.
    assert not {client.occupier, client.reclaimer} & {j.queue for j in whole}
    for queue in {j.queue for j in whole}:
        held = sum(len(j.pods) for j in whole if j.queue == queue)
        assert held <= driven.nodes // (len(parent) - len(
            {p for p in parent.values() if p}))


def children(trace, span):
    return [s for s in trace.spans if s.parent_id == span.span_id]


def only(spans, name):
    found = [s for s in spans if s.name == name]
    assert len(found) == 1, (name, [s.name for s in spans])
    return found[0]


def test_the_span_tree_under_the_reclaim_action(driven):
    trace, cut = driven.trace, WIDTHS[driven.nodes]
    gang = cut["gang"]
    action = only(trace.spans, "action:reclaim")
    under = children(trace, action)
    order = only(under, "reclaim:order")
    assert order.kind == "reclaim" and order.attrs["jobs"] == 1
    job = only(under, "reclaim:job")
    assert job.kind == "reclaim" and job.attrs["success"] is True
    assert job.attrs["queue"] == driven.client.reclaimer
    survey = only(children(trace, job), "reclaim:survey")
    # The survey holds every victim, the reclaimer's own running gangs
    # too; the reclaimer read its stream as far as the solver reads.
    assert survey.attrs["victims"] > job.attrs["victims"] == cut["victims"]
    assert job.attrs["filtered"] == 0
    solve = only(children(trace, job), "solve:job")
    assert solve.kind == "solver"
    steps = gang // 2                  # two pods a step, one GPU a pod
    assert solve.attrs == {
        "job": job.attrs["job"], "action": "reclaim", "tasks": gang,
        "victims": cut["victims"], "steps": 2 * cut["victims"],
        "tried": 2, "skipped": steps - 2, "solved": True,
        # No victim lands again: the fleet is full.
        "replaced": 0}
    inside = children(trace, solve)
    assert [s.name for s in inside] == [
        "solve:precheck", "solve:scenario", "solve:prescreen",
        "solve:scenario", "statement:commit"]
    first, last = (s for s in inside if s.name == "solve:scenario")
    assert first.attrs == {"prefix": 1, "evicted": 2, "fits": False}
    assert last.attrs == {"prefix": steps, "evicted": gang - 2,
                          "fits": True}
    prescreen = only(inside, "solve:prescreen")
    scored = min(cut["victims"], 2 * cut["victims"] - 1)
    assert prescreen.attrs == {
        "prefixes": scored, "steps": scored, "rows": prescreen.attrs["rows"],
        "t_pad": prescreen.attrs["t_pad"], "form": "counted",
        "mask": "none", "strategy": "binpack", "level": "none",
        "domains": 0,
        "feasible": scored - (steps - 2), "first_feasible": steps - 2}
    assert prescreen.attrs["rows"] >= 2 * scored
    assert prescreen.attrs["t_pad"] >= gang
    dispatch = only(children(trace, prescreen),
                    "dispatch:scenario_prescreen")
    assert dispatch.kind == "kernel"
    commit = only(inside, "statement:commit")
    assert commit.kind == "commit"
    assert commit.attrs == {"binds": 0, "evictions": gang}
    # The solver's spans are few whatever the victims: none per task.
    assert sum(s.kind in ("solver", "reclaim") for s in trace.spans) == 8


def test_the_four_counters_move_with_the_spans(driven):
    cut = WIDTHS[driven.nodes]
    steps = cut["gang"] // 2
    scored = min(cut["victims"], 2 * cut["victims"] - 1)
    for rec in driven.client.records[1:]:
        assert rec.counters == {
            "scenario_prescreen_prefixes_total": scored,
            "scenario_prescreen_feasible_total": scored - (steps - 2),
            "scenarios_skipped_by_prescreen_total": steps - 2,
            _key("solver_evictions_total", {"action": "reclaim"}):
            cut["gang"],
            # The fill that binds, the fill that finds the fleet full, two
            # confirms and the prescreen.
            "device_kernel_calls": 5}


def test_a_prescreen_that_declines_says_why():
    """With ``scenario_prescreen_max`` 0 the solver simulates scenario by
    scenario; its span says the batch was not asked, and no counter of
    the prescreen moves."""
    cell = small_cell(64)
    cell.config["scheduler"].update(scenario_prescreen_max=0,
                                    max_scenarios_per_job=16)
    cell.traffic["gang"]["roles"][0]["count"] = 8
    TRACER.reset()
    client = cell.generator.build(cell, 5, counters=COUNTERS)
    rec = client.cycle()
    assert len(rec.evicted) == 8
    spans = TRACER.get_trace().spans
    # Asked after each of the three scenarios that failed.
    asked = [s for s in spans if s.name == "solve:prescreen"]
    assert [s.attrs for s in asked] == 3 * [{"declined": "disabled"}]
    assert not [s for s in spans if s.name == "dispatch:scenario_prescreen"]
    assert rec.counters.get("scenario_prescreen_prefixes_total", 0) == 0
    assert rec.counters.get("scenarios_skipped_by_prescreen_total", 0) == 0
    assert only(spans, "solve:job").attrs["tried"] == 4
