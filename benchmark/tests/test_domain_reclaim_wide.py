"""The cell ``tasreclaim98k-pytorchjob-8x256`` as files: its shapes and
bytes from the files alone, what its entries add to ``BENCHMARK.json``
(asked by NAME, never by position: a later cell is appended after this
one), its generator through the same ``run_cell`` at a width the CPU
holds, against the plain reference ``reference/domain_eviction.py``, the
trial's exit on a solver whose prescreen has no domain axis, and the
controls of its reference (``control_domain.py``), each of which has to
come out not correct by its own counts alone."""

import json
import os
import types

import numpy as np
import pytest

from conftest import ROOT

from benchmark.harness import readers, spec

CELL = "tasreclaim98k-pytorchjob-8x256"
CONFIG = "tas-reclaim-98k"
MIX = "reclaim-pytorchjob-8x256-rack"
# The accepted metrics that read what the cell runs, and list it.
LISTED = (
    "allocate_jobs_kernel_ms", "allocate_jobs_kernel_roofline",
    "topology_ms", "scenario_prescreen_ms", "scenario_prescreen_kernel_ms",
    "scenario_prescreen_roofline", "reclaim_host_ms",
    "prescreen_operands_ms", "scenarios_skipped", "prescreen_counted",
    "prescreen_scan_steps", "prescreen_masked", "prescreen_calls",
    "reclaim_victims_examined", "gc_full_collections", "gc_full_pause_s",
    "gc_young_pause_s", "gc_middle_pause_s", "operands_net_ms",
    "statement_net_ms", "affinity_pod_walks", "proportion_rollup_walks",
    # Eight binds and their confirms go through the dispatch, and the
    # grouped prescreen runs under a strategy.
    "dispatch_ms", "strategy_declines")
NEW = {
    "prescreen_domain_pruned": {
        "kind": "counter_delta",
        "counter": "scenario_prescreen_domain_pruned_total"},
    "reclaim_victims_replaced": {
        "kind": "counter_delta",
        "counter": 'solver_victims_replaced_total{action="reclaim"}'},
    "solve_scenario_ms": {"kind": "span_sum", "match": ["solve:scenario"]},
    "prescreen_pool_cells": {
        "kind": "counter_delta",
        "counter": "scenario_prescreen_pool_cells_total"}}
# 1,024 nodes, 16 racks, four of them the occupier's in stripes of two;
# two gangs of 128 pods a cycle, one to each queue (three in flight stay
# inside a leaf's 512 GPUs): two waves of one rack each, and a cycle's two
# gangs take the newest four waves between them.
SMALL = dict(nodes=1024, gang=128, gangs=2, whole=64, victims=512, stripe=2)


@pytest.fixture(scope="module")
def cell():
    return spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)


def test_the_files_give_the_cycles_shapes(cell):
    shape = cell.generator.file_shape(cell)
    assert (shape["prefixes"], shape["rows"], shape["t"], shape["t_pad"],
            shape["nodes"], shape["resources"], shape["runs"]) == (
        2048, 4096, 256, 256, 98304, 3, 2)
    # 1,536 racks of 64: the table of domains is the fleet, no padding.
    assert (shape["domains"], shape["d_pad"], shape["slots"]) == (
        1536, 1536, 98304)
    # A confirm is two calls: the gang and each victim's gang chunk of
    # two (400 jobs: 1,056 tasks of 401 jobs), then the surplus of those
    # that stand again, a pod a job (336 jobs' 672 pods).
    assert [2048, 512] in shape["confirms"]
    assert [1024, 1024] in shape["rests"] and [1, 2] in shape["rests"]
    assert all(t >= 256 for t, _j in shape["confirms"])
    assert cell.chips == 1 and cell.entry["config"] == CONFIG
    assert cell.entry["traffic"] == MIX
    assert cell.generator.__file__ == os.path.join(
        ROOT, "benchmark", "generators", "domain_reclaim_gangs.py")
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "domain_eviction.py")
    other = spec.Cell(spec.load_benchmark(ROOT), "ns98k-reclaim-wide", ROOT)
    assert cell.generator.base.__file__ == other.generator.__file__
    assert issubclass(cell.generator.Client, cell.generator.base.Client)


def test_the_traffic_is_the_issues(cell):
    traffic = cell.traffic
    assert {k: traffic[k] for k in (
        "gangs_per_cycle", "reclaimer_queues", "lifetime_cycles",
        "pending_cycles_max", "warm_cycles")} == {
        "gangs_per_cycle": 9, "reclaimer_queues": 3, "lifetime_cycles": 1,
        "pending_cycles_max": 2, "warm_cycles": 1}
    assert 4 <= traffic["gangs_per_cycle"] <= 16
    assert traffic["gangs_per_cycle"] % traffic["reclaimer_queues"] == 0
    gang = traffic["gang"]
    assert gang["topology"] == {"name": "mesh", "required": "rack"}
    assert [(r["name"], r["count"], r["cpu"], r["memory"], r["gpu"])
            for r in gang["roles"]] == [("master", 1, "8", "64Gi", 1),
                                        ("worker", 255, "4", "32Gi", 1)]
    assert traffic["generator"] == "domain_reclaim_gangs"


def test_the_configuration_is_tas65ks_mesh_at_north_stars_width(cell):
    config = cell.config
    bench = spec.load_benchmark(ROOT)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["file"] == "benchmark/configs/tas-reclaim-98k.json"
    assert entry["reduced"] == config["reduced"] == ["backlog", "occupancy"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell.entry["why"]) <= 200
    configs = os.path.join(ROOT, "benchmark", "configs")
    ns = spec.load_json(os.path.join(configs, "north-star-98k.json"))
    tas = spec.load_json(os.path.join(configs, "tas-65k.json"))
    assert config["nodes"] == {**ns["nodes"],
                               "labels": tas["nodes"]["labels"]}
    assert config["topologies"] == tas["topologies"]
    assert config["queues"] == ns["queues"]
    occ = config["occupancy"]
    for key in ("preemptible_nodes_share", "job_pods", "preemptible", "pod",
                "whole_node"):
        assert occ[key] == ns["occupancy"][key]
    # The victims are north-star-98k's, elastic: two steps a job.
    assert occ["min_available"] == ns["occupancy"]["min_available"] == 2
    assert (occ["stripe_racks"], occ["wave_jobs"]) == (8, 16)
    assert config["scheduler"] == {"max_victims_considered": 1024,
                                   "scenario_prescreen_max": 2048,
                                   "scenario_prescreen_after": 1}
    assert config["reference"] == "domain_eviction"
    assert len(config["guarantees"]) == 8
    assert config["backlog"]["pending_jobs"] == 9


def test_the_entries_are_there_by_name_and_nothing_else_moved():
    bench = spec.load_benchmark(ROOT)
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert CONFIG in [c["name"] for c in bench["configs"]]
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == {"name": CELL, "config": CONFIG, "traffic": MIX,
                     "chips": 1, "why": entry["why"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name]["workloads"]
    for name in ("gc_full_ms", "preempt_host_ms", "consolidation_host_ms",
                 "victims_filtered"):
        assert CELL not in by_name[name]["workloads"]
    for name, reader in NEW.items():
        entry = by_name[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "cycle_ms" and len(entry["unit"]) <= 16
        doc = spec.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert {k: doc[k] for k in ("name", "unit", "better", "source",
                                    "layer", "moves")} == {
            k: entry[k] for k in ("name", "unit", "better", "source",
                                  "layer", "moves")}
        assert doc["reader"] == reader
        assert doc["reader"]["kind"] in readers.KINDS
    assert bench["run_seconds"] == 51
    assert [m["name"] for m in bench["end_to_end"]] == [
        "cycle_ms", "pods_bound_per_s", "setup_s"]


def test_the_byte_counts_follow_what_was_dispatched(cell):
    gen = cell.generator
    one = 2048 * 98304 * 3 * 4
    assert gen.reckon(cell)["program_bytes"] == 7 * one
    # Eight calls of two runs each: a pool written and read twice, a call.
    assert gen.prefix_feasibility_bytes(
        cells=8.0 * 2048 * 98304, runs_a_call=2.0, resources=3) \
        == 8 * 3 * one
    assert gen.exact_scan_bytes(steps=100, nodes=98304, resources=3,
                                label_cols=1, taint_cols=1) \
        == 100 * 49 * 98304
    # Fed by the traced cycle's record, not by the files.
    rec = types.SimpleNamespace(
        counters={gen.CALLS: 8.0, gen.CELLS: 8.0 * 2048 * 98304,
                  gen.RUNS: 16.0},
        commits=[types.SimpleNamespace(nominated=[0] * 256,
                                       evicted=[0] * 1600)],
        bound={"g": {i: 0 for i in range(256)}})
    client = types.SimpleNamespace(
        primed=gen.file_shape(cell), traffic=cell.traffic,
        records=[None, rec])
    shapes = gen.kernel_shapes(client)
    assert shapes["prefix_feasibility_bytes"] == {
        "cells": 8.0 * 2048 * 98304, "runs_a_call": 2.0, "resources": 3}
    assert shapes["exact_scan_bytes"]["steps"] == 256 + 1600 + 256
    # A program without the counters: no prescreen shape, no share.
    rec.counters = {}
    assert "prefix_feasibility_bytes" not in gen.kernel_shapes(client)


# -- the reference, alone ------------------------------------------------------
def test_the_reference_imports_nothing_of_the_program(cell):
    source = open(cell.reference.__file__).read()
    assert "import numpy as np" in source
    imports = [ln for ln in source.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import numpy as np"]


def test_the_reference_finds_the_first_prefix_that_seats_inside_a_domain(
        cell):
    ref = cell.reference
    cap = np.tile([64000.0, 512.0, 8.0], (8, 1))     # two domains of four
    used = cap.copy()
    pods = np.full(8, 8)
    seg = np.array([0, 0, 0, 0, 1, 1, 1, 1])
    one = np.array([[4000.0, 32.0, 1.0]])
    gang = np.tile(one, (4, 1))
    # Victims alternate between the domains, two pods a step.
    steps = [(np.array([n, n]), np.tile(one, (2, 1)))
             for n in (0, 4, 5, 1, 6)]
    assert ref.first_seating_prefix(cap, used, pods, 110, seg, steps,
                                    gang) == (3, 1)
    # Four GPUs are free somewhere after two steps.
    assert ref.first_seating_prefix(cap, used, pods, 110, np.zeros(8, int),
                                    steps, gang) == (2, 0)
    assert ref.first_seating_prefix(cap, used, pods, 110, seg, steps[:2],
                                    gang) == (None, None)
    assert ref.first_seating_prefix(cap, used, pods, 110, seg, steps, gang,
                                    allowed={0}) == (4, 0)
    idle = used.copy()
    idle[2] = 0
    assert ref.first_seating_prefix(cap, idle, pods, 110, seg, steps,
                                    gang) == (0, 0)
    assert ref.victim_order([0, 0, -1], [1.0, 2.0, 0.5]).tolist() \
        == [2, 1, 0]
    assert ref.domains_apart([0, 1, 2], seg) == 0
    assert ref.domains_apart([0, 5], seg) == 1
    assert ref.seats_inside(cap[:1] - used[:1] + one * 2, [5], gang[:2])
    assert not ref.seats_inside(cap[:1] - used[:1] + one * 2, [1], gang[:2])


# -- the cell at a width the CPU holds -----------------------------------------
@pytest.fixture(scope="module")
def cut_root(tmp_path_factory):
    """A benchmark root whose one cell is the real cell's files with the
    fleet cut to 1,024 nodes: the generator, the reference and the metric
    files are the real ones, found in ``benchmark/``."""
    tmp = tmp_path_factory.mktemp("cut")
    bench = spec.load_benchmark(ROOT)
    cell = spec.Cell(bench, CELL, ROOT)
    cell = cell.generator.cut_cell(cell, **SMALL)
    bench["paths"] = ["own", os.path.relpath(
        os.path.join(ROOT, "benchmark"), tmp)]
    bench["workloads"] = [{**cell.entry, "config": "own", "traffic": "own"}]
    for kind, doc in (("configs", cell.config), ("traffic", cell.traffic)):
        path = tmp / "own" / kind / "own.json"
        path.parent.mkdir(parents=True)
        path.write_text(json.dumps(doc))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp)


def test_the_generator_runs_through_run_cell(cut_root, cell):
    import jax

    from benchmark import run
    jax.clear_caches()
    out = run.run_cell(CELL, 3000000019, 4.0, True, require_chip=False,
                       root=cut_root)
    assert out["correct"], out["compared"]
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 13
    assert all(v == [0, 0] for v in out["compared"].values())
    line = out["run"]
    assert (line["generator"], line["reference"]) == (
        "domain_reclaim_gangs", "domain_eviction")
    # A cycle's first gang takes three waves (48 jobs: two of its rack
    # and one of the other's, which is placed again), its second one more.
    assert line["prefix_jobs_per_commit"][-1] == 48
    assert line["binds_per_cycle"] == [256]
    assert line["prescreens_per_cycle"] == [2]
    assert line["bind_cycles_after_arrival"] == [1]
    cycles = line["cycles_in_window"]
    assert line["commits"] == 2 * cycles
    assert line["evictions_written"] == (48 + 32) * 4 * cycles
    assert line["pods_that_stay_evicted"] == 256 * cycles
    assert line["pods_deleted"] == 256 * cycles
    assert line["pods_placed_again_on_own_node"] \
        + line["pods_placed_again_elsewhere"] == 64 * cycles
    assert line["primed"]["trial"] == {
        "seconds": line["primed"]["trial"]["seconds"], "nodes": 1024,
        "gang": 160, "prefix_jobs": [72, 80]}
    assert out["failed"] == 0 and out["attempted"] >= 2
    # Prime compiled the cycle's programs; neither the warm cycle nor the
    # window compiled the prescreen or a confirm again.
    assert {"batch_prefix_feasibility", "domain_aggregates",
            "allocate_jobs_kernel[128,2] bind"} \
        <= set(line["primed"]["kernels"])
    warm = {name for c in line["warm_cycles"] for name in c["compiled"]}
    assert "jit(batch_prefix_feasibility)" not in warm
    assert "jit(domain_aggregates)" not in warm
    assert line["window_compiles"] == 0
    metrics = out["metrics"]
    # Capacity is free somewhere 16 jobs (32 steps) before it is in one rack,
    # for the first gang; the second's prefix is rack-feasible at once.
    assert metrics["prescreen_domain_pruned"] == {"value": 32.0,
                                                  "unit": "prefixes/cycle"}
    assert metrics["reclaim_victims_replaced"] == {
        "value": 64.0, "unit": "pods/cycle"}
    assert metrics["prescreen_pool_cells"] == {
        "value": 2.0 * 512 * 1024, "unit": "cells/cycle"}
    assert metrics["solve_scenario_ms"]["value"] > 0
    assert metrics["topology_ms"]["value"] > 0
    assert metrics["prescreen_calls"]["value"] == 2.0
    assert metrics["prescreen_counted"]["value"] == 0.0
    assert metrics["prescreen_scan_steps"]["value"] == 4.0     # 2 a call
    assert metrics["prescreen_masked"]["value"] == 0.0
    assert metrics["reclaim_victims_examined"]["value"] > 0
    assert metrics["reclaim_host_ms"]["value"] > 0
    assert metrics["strategy_declines"]["value"] == 0.0
    assert metrics["dispatch_ms"]["value"] > 0
    for name in ("preempt_host_ms", "consolidation_host_ms",
                 "victims_filtered"):
        assert name not in metrics
    assert "scenario_prescreen_roofline" not in metrics   # no chip


def test_the_trial_stops_a_program_whose_prescreen_has_no_domain_axis(
        cell, monkeypatch):
    """The parent's program, by a test double: the topology plugin
    registers no ``required_domain_fns``, so the prescreen answers for the
    fleet, the solver spends its 16 scenarios on prefixes that free the
    GPUs somewhere, and the gang is never bound.  ``build`` stops with a
    message, soon, before the run's fleet is built."""
    import time

    from kai_scheduler_tpu.ops import topology
    monkeypatch.setattr(topology.TopologySession, "required_domains",
                        lambda self, job: None)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as stop:
        cell.generator.build(cell, 3)
    assert time.perf_counter() - t0 < 60
    assert "cannot run the configuration tas-reclaim-98k" in str(stop.value)
    assert "'gangs_not_bound': [" in str(stop.value)
    assert stop.value.code != 0


def test_a_program_without_the_counters_leaves_the_metrics_out(cell):
    metrics = [m for m in cell.per_layer if m["name"] in NEW]
    assert len(metrics) == 4
    assert set(readers.counters_wanted(metrics)) == {
        r["counter"] for r in NEW.values() if "counter" in r}
    run = {"records": [types.SimpleNamespace(counters={}, spans=[])]}
    assert readers.read_all(metrics, run) == {}
    run["records"][0].counters[
        NEW["prescreen_domain_pruned"]["counter"]] = 1736.0
    run["records"][0].spans = [("solve:scenario", "solver", 1, None, 0.0,
                                0.25)]
    assert readers.read_all(metrics, run) == {
        "prescreen_domain_pruned": {"value": 1736.0,
                                    "unit": "prefixes/cycle"},
        "solve_scenario_ms": {"value": 250.0, "unit": "ms"}}


@pytest.mark.parametrize("kind", ("rack_blind", "oldest_first", "one_more",
                                  "keep_none", "sound"))
def test_a_control_moves_its_own_counts_alone(kind):
    from control_domain import MOVES, as_said, run_control
    out = run_control(CELL, 7, kind, cut=SMALL)
    assert out["correct"] == (kind == "sound")
    assert as_said(out), out["compared"]
    assert set(MOVES[kind]) <= set(out["compared"])
