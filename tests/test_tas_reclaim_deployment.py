"""Rack-local gangs reclaim on a full fleet, at fleet shape, on the CPU
(PR 53).

The deployment ``tas-reclaim-98k`` at 1,024 nodes (16 racks of 64): the
benchmark's own client (``benchmark/generators/domain_reclaim_gangs.py``,
which is ``reclaim_gangs``' loop over racks) drives ``Scheduler.run_once``;
every cycle gangs that REQUIRE one rack arrive in starved queues, the
reclaim action solves them one after another on one ``VictimStream``, each
at the smallest prefix of the striped victim order that frees its GPUs
inside one rack, and the allocate action binds them there a cycle later;
the plain reference the chip's ``correct`` uses
(``benchmark/reference/domain_eviction.py``, loaded by path, no import of
the program) finds all thirteen counts 0, where the controls of
``benchmark/tests/control_domain.py`` each move their own.  Beside it: the
reference's functions on numbers made by hand.
"""

import os
import sys

import numpy as np
import pytest

from kai_scheduler_tpu.utils.metrics import METRICS
from kai_scheduler_tpu.utils.tracing import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "tasreclaim98k-pytorchjob-8x256"
DEPLOY_SEEDS = (3, 11, 3000000019)
CYCLES = 4
# Four of 16 racks the occupier's, in stripes of two.  ``pair``: two gangs
# of 128 pods a cycle, one to each queue (two waves of a rack each: a
# cycle's two take the newest four waves between them and leave nothing
# placed again behind).  ``single``: the generator's own trial, one gang of
# 160 pods a cycle, whose prefix ends inside a wave.
CUTS = {
    "pair": dict(nodes=1024, gang=128, gangs=2, whole=64, victims=512,
                 stripe=2),
    "single": dict(nodes=1024, gang=160, gangs=1, whole=64, victims=512,
                   stripe=2),
}
COUNTERS = ("scenario_prescreen_calls_total",
            "scenario_prescreen_domain_calls_total",
            "scenario_prescreen_domain_pruned_total",
            "scenario_prescreen_pool_cells_total",
            "scenario_prescreen_scan_steps_total",
            'solver_victims_replaced_total{action="reclaim"}',
            'solver_evictions_total{action="reclaim"}',
            "scenarios_skipped_by_prescreen_total")


def the_cell():
    from benchmark.harness import spec
    return spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)


def small_cell(cut: str):
    cell = the_cell()
    return cell.generator.cut_cell(cell, **CUTS[cut])


@pytest.fixture(scope="module")
def ref():
    return the_cell().reference


@pytest.fixture(scope="module", params=[
    (cut, seed) for cut in CUTS for seed in DEPLOY_SEEDS],
    ids=lambda p: f"{p[0]}-seed{p[1]}")
def driven(request):
    """The client driven for four cycles: (cell, client, verdict, the
    traces of the cycles)."""
    cut, seed = request.param
    cell = small_cell(cut)
    client = cell.generator.Client(cell, seed, COUNTERS)
    # As ``build`` does: a confirm opens a span a registered fn for every
    # job of its calls, more than the recorder keeps by default.
    TRACER.max_spans_per_trace = max(TRACER.max_spans_per_trace, 8192)
    traces = []
    for _ in range(CYCLES):
        client.cycle()
        traces.append(TRACER.get_trace())
    verdict = cell.generator.compare(client.records, client.ledger, cell)
    return cell, client, verdict, traces


# -- the reference on numbers made by hand ------------------------------------
def test_the_reference_imports_nothing_of_the_program(ref):
    imports = [ln for ln in open(ref.__file__).read().splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import numpy as np"]


def hand_fleet():
    cap = np.tile([64000.0, 512.0, 8.0], (8, 1))     # two domains of four
    seg = np.array([0, 0, 0, 0, 1, 1, 1, 1])
    one = np.array([[4000.0, 32.0, 1.0]])
    steps = [(np.array([n, n]), np.tile(one, (2, 1)))
             for n in (0, 4, 5, 1, 6)]
    return cap, seg, one, steps


@pytest.mark.parametrize("seg_of, allowed, steps_to, want", [
    ("racks", None, 5, (3, 1)),          # the second rack fills up first
    ("fleet", None, 5, (2, 0)),          # four GPUs are free somewhere
    ("racks", None, 2, (None, None)),    # the victims run out
    ("racks", {0}, 5, (4, 0)),           # held to the first rack
], ids=("racks", "fleet", "too-few", "allowed"))
def test_the_first_prefix_that_seats_the_gang_inside_one_domain(
        ref, seg_of, allowed, steps_to, want):
    cap, seg, one, steps = hand_fleet()
    if seg_of == "fleet":
        seg = np.zeros(8, int)
    got = ref.first_seating_prefix(cap, cap.copy(), np.full(8, 8), 110, seg,
                                   steps[:steps_to], np.tile(one, (4, 1)),
                                   allowed)
    assert got == want


def test_an_idle_domain_needs_no_victim(ref):
    cap, seg, one, steps = hand_fleet()
    used = cap.copy()
    used[6] = 0
    assert ref.first_seating_prefix(cap, used, np.full(8, 8), 110, seg,
                                    steps, np.tile(one, (4, 1))) == (0, 1)


def test_a_master_that_no_node_of_the_domain_holds_seats_nowhere(ref):
    cap, seg, one, steps = hand_fleet()
    gang = np.vstack([[[40000.0, 32.0, 1.0]], np.tile(one, (3, 1))])
    used = cap.copy()
    used[:, 0] = 50000.0                 # 14 cpu free a node at the most
    assert ref.first_seating_prefix(cap, used, np.full(8, 8), 110, seg,
                                    steps, gang) == (None, None)


@pytest.mark.parametrize("priority, created, want", [
    ([0, 0, 0], [1.0, 3.0, 2.0], [1, 2, 0]),
    ([5, 0, 0], [9.0, 1.0, 2.0], [2, 1, 0]),
], ids=("newest-first", "lowest-priority-first"))
def test_upstreams_order_is_lowest_priority_then_newest(ref, priority,
                                                        created, want):
    assert ref.victim_order(priority, created).tolist() == want


@pytest.mark.parametrize("nodes, want", [([0, 1, 3], 0), ([0, 4], 1),
                                         ([], 0)])
def test_domains_apart(ref, nodes, want):
    assert ref.domains_apart(nodes, hand_fleet()[1]) == want


@pytest.mark.parametrize("running, gone, fault", [(4, 4, 0), (4, 2, 1),
                                                  (4, 0, 0)])
def test_a_victim_gang_left_below_its_minimum_is_a_fault(ref, running, gone,
                                                         fault):
    assert ref.gangs_left_below_minimum({"j": running}, {"j": gone},
                                        {"j": 4}) == fault


def test_the_quotas(ref):
    parent = {"leaf": "dep", "dep": None, "occ": "dep"}
    deserved = {q: np.array([8.0, 8.0, 8.0]) for q in parent}
    limit = {q: np.array([16.0, 16.0, 16.0]) for q in parent}
    used = {"leaf": np.zeros(3), "dep": np.full(3, 12.0),
            "occ": np.full(3, 12.0)}
    took = {"occ": [np.ones(3)] * 2}
    asks = np.full(3, 2.0)
    assert ref.quota_faults(deserved, limit, used, took, "leaf", asks,
                            parent) == 0
    # The victims' queue stands under its share once one pod has gone.
    used["occ"] = np.full(3, 9.0)
    assert ref.quota_faults(deserved, limit, used, took, "leaf", asks,
                            parent) == 1
    used["occ"] = np.full(3, 12.0)
    # The reclaimer over its deserved share; its department past its limit.
    assert ref.quota_faults(deserved, limit, used, took, "leaf",
                            np.full(3, 9.0), parent) == 2


# -- the deployment, driven ----------------------------------------------------
def test_every_count_is_zero(driven):
    cell, _client, verdict, _traces = driven
    assert verdict["correct"], verdict["compared"]
    assert list(verdict["compared"]) == list(cell.generator.LIMITS)
    assert len(verdict["compared"]) == 13
    assert all(v == [0, 0] for v in verdict["compared"].values())
    assert verdict["failed"] == 0 and verdict["attempted"] >= 2


def test_every_commit_seats_one_gang_inside_one_rack(driven):
    cell, client, verdict, _traces = driven
    seg = client.ledger.levels["rack"]
    gangs = int(cell.traffic["gangs_per_cycle"])
    size = sum(int(r["count"]) for r in cell.traffic["gang"]["roles"])
    for rec in client.records:
        assert len(rec.commits) == gangs
        for commit in rec.commits:
            assert len(commit.nominated) == size
            assert len({int(seg[n]) for _p, n in commit.nominated}) == 1
            # As many pods stay evicted as the gang has, less what stood
            # idle in its rack; the rest of the prefix is placed again.
            assert len(commit.evicted) - len(commit.replaced) <= size
        for bound in rec.bound.values():
            assert len(bound) == size
            assert len({int(seg[n]) for n in bound.values()}) == 1
    assert verdict["run"]["bind_cycles_after_arrival"] == [1]
    assert verdict["run"]["prescreens_per_cycle"] == [gangs]


def test_the_pair_takes_the_newest_waves_and_leaves_nothing_behind(driven):
    cell, client, verdict, _traces = driven
    line = verdict["run"]
    if int(cell.traffic["gangs_per_cycle"]) != 2:
        # The single gang of 160: 72 jobs the first time (more are
        # touched later, where placed-again victims lie a pod or two to a
        # job); what stands placed again at a cycle's end is moved, not
        # lost, so only the gang's pods are deleted.
        assert 72 in line["prefix_jobs_per_commit"]
        assert line["pods_deleted"] == line["pods_that_stay_evicted"]
        return
    # Three waves for the first gang (two of its rack, one of the other's,
    # placed again), two for the second: the newest four, all deleted.
    # (jobs a commit touched: the second gang's 128 pods are two waves'
    # worth, some of them the first commit's victims where they were
    # placed again, a pod or two of a job)
    assert line["prefix_jobs_per_commit"][-1] == 48
    assert line["evictions_written"] == CYCLES * (48 + 32) * 4
    assert line["pods_that_stay_evicted"] == line["pods_deleted"] \
        == CYCLES * 256
    assert line["pods_placed_again_on_own_node"] \
        + line["pods_placed_again_elsewhere"] == CYCLES * 64
    for rec in client.records:
        assert [(len(c.evicted), len(c.replaced)) for c in rec.commits] \
            == [(192, 64), (128, 0)]


def test_the_fleet_stands_still_over_the_cycles(driven):
    cell, client, _verdict, _traces = driven
    ledger = client.ledger
    pair = int(cell.traffic["gangs_per_cycle"]) == 2
    # Every GPU is held or promised at the start of every cycle after the
    # first bind: what a gang leaves is refilled, in the striped order,
    # and a victim that stands placed again at a cycle's end runs at its
    # new place (the single gang's prefix ends inside a wave: the part of
    # it that was placed again is moved, not lost).
    for rec in (client.records[1], client.records[-1]):
        held = rec.used_before[:, 2].sum()
        promised = sum(g.req[:, 2].sum() for g in rec.pending
                       if g not in rec.arrived)
        idle = ledger.capacity[:, 2].sum() - held - promised
        assert idle == 0
    assert np.all(client.records[-1].used_after <= ledger.capacity + 1e-9)


def test_the_span_tree_of_a_cycle_of_rack_bound_reclaimers(driven):
    cell, _client, _verdict, traces = driven
    gangs = int(cell.traffic["gangs_per_cycle"])
    spans = traces[-1].spans
    solves = [s for s in spans if s.name == "solve:job"]
    screens = [s for s in spans if s.name == "solve:prescreen"]
    assert len(solves) == len(screens) == gangs
    assert sum(1 for s in spans if s.name == "reclaim:survey") == 1
    for solve, screen in zip(solves, screens):
        assert solve.attrs["action"] == "reclaim" and solve.attrs["solved"]
        assert solve.attrs["tried"] == 2
        assert screen.attrs["level"] == "rack"
        assert screen.attrs["domains"] == 16
        assert screen.attrs["form"] == "grouped"
        assert screen.attrs["runs"] == 2 and "declined" not in screen.attrs
    # A confirm is one multi-job call, the gang under its rack's subset
    # and each victim's next chunk, and one more where a victim that
    # stands again has pods left to place.
    confirms = [s for s in spans if s.name == "dispatch:allocate_jobs_multi"]
    assert gangs <= len(confirms) <= 2 * gangs
    # ``subset_nodes``: a refused arrival and a bind a bound gang in the
    # allocate action, the failed first scenario and the confirm a solve.
    subsets = [s for s in spans if s.name == "topology:subset_nodes"]
    assert len(subsets) >= 2 * gangs + gangs + 1


def test_the_counters_of_a_cycle(driven):
    cell, client, _verdict, _traces = driven
    gangs = int(cell.traffic["gangs_per_cycle"])
    for rec in client.records:
        c = rec.counters
        assert c["scenario_prescreen_calls_total"] == gangs
        assert c["scenario_prescreen_domain_calls_total"] == gangs
        assert c["scenario_prescreen_pool_cells_total"] \
            == gangs * 512 * 1024
        assert c["scenario_prescreen_scan_steps_total"] == 2 * gangs
        placed = sum(len(k.replaced) for k in rec.commits)
        evicted = sum(len(k.evicted) for k in rec.commits)
        assert c['solver_victims_replaced_total{action="reclaim"}'] \
            == placed
        assert c['solver_evictions_total{action="reclaim"}'] == evicted
        assert c["scenario_prescreen_domain_pruned_total"] >= 0
    first = client.records[0].counters
    assert first["scenario_prescreen_domain_pruned_total"] >= 16


def test_the_byte_counts_are_fed_by_what_the_cycle_dispatched(driven):
    cell, client, _verdict, _traces = driven
    gen = cell.generator
    client.primed = gen.file_shape(cell)
    shapes = gen.kernel_shapes(client)
    rec = client.records[1]              # the window's first cycle
    gangs = int(cell.traffic["gangs_per_cycle"])
    assert shapes["prefix_feasibility_bytes"] == {
        "cells": float(gangs * 512 * 1024), "runs_a_call": 2.0,
        "resources": 3}
    assert shapes["exact_scan_bytes"]["steps"] == sum(
        len(c.nominated) + len(c.evicted) for c in rec.commits) \
        + sum(len(b) for b in rec.bound.values())


def test_the_trial_passes_on_this_program():
    cell = the_cell()
    trial = cell.generator.try_rack_reclaim(cell, 3)
    assert trial["nodes"] == 1024 and trial["gang"] == 160
    # 72 jobs where 40 free as many GPUs somewhere.
    assert 72 in trial["prefix_jobs"]


def test_the_trial_stops_a_program_whose_prescreen_is_blind_to_racks(
        monkeypatch):
    """The parent's program by a test double: no ``required_domains``, so
    the verdict is the fleet's, the solver spends its scenarios on
    prefixes that free the GPUs somewhere, and the gang is never bound."""
    from kai_scheduler_tpu.ops import topology
    monkeypatch.setattr(topology.TopologySession, "required_domains",
                        lambda self, job: None)
    cell = the_cell()
    with pytest.raises(SystemExit) as stop:
        cell.generator.try_rack_reclaim(cell, 3)
    assert "cannot run the configuration tas-reclaim-98k" in str(stop.value)
    assert "gangs_not_bound" in str(stop.value)


@pytest.mark.parametrize("kind", ("rack_blind", "oldest_first", "one_more",
                                  "keep_none", "sound"))
def test_a_control_in_the_programs_place_moves_its_own_counts(kind):
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        from control_domain import as_said, run_control
    finally:
        sys.path.pop(0)
    out = run_control(CELL, 7, kind, cut=CUTS["pair"])
    assert out["correct"] == (kind == "sound")
    assert as_said(out), out["compared"]


def test_every_family_reads_zero_before_any_rack_bound_solve():
    from tests.fixtures import build_session
    for name in COUNTERS[1:4]:
        METRICS.counters.pop(name, None)
    build_session({"nodes": {}, "jobs": {}})
    assert all(METRICS.counters[name] == 0 for name in COUNTERS[1:4])
