"""The cell ``preempt98k-lws-32x4`` as files: its shapes and bytes from the
files alone, what its entries add to ``BENCHMARK.json`` (asked by NAME: a
later cell is appended after this one, and a case that asks for the last
entry then fails for no fault), its generator through the same
``run_cell`` at a width the CPU holds, ``preflight.py`` over the floor,
and the controls of its reference (``control_preempt.py``), each of which
has to come out not correct by its own counts alone."""

import json
import os

import pytest

from conftest import ROOT

from benchmark.harness import readers, spec

CELL = "preempt98k-lws-32x4"
CONFIG = "preempt-98k"
MIX = "preempt-lws-32x4"
# The accepted metrics that read what the cell runs, and list it.
LISTED = (
    "allocate_jobs_kernel_ms", "allocate_jobs_kernel_roofline",
    "scenario_prescreen_ms", "scenario_prescreen_kernel_ms",
    "scenario_prescreen_roofline", "prescreen_operands_ms",
    "scenarios_skipped", "prescreen_counted", "prescreen_scan_steps",
    "prescreen_masked", "gc_full_collections", "gc_full_pause_s",
    "gc_young_pause_s", "gc_middle_pause_s", "operands_net_ms",
    "statement_net_ms", "affinity_pod_walks", "proportion_rollup_walks")
NEW = {"preempt_host_ms": {"kind": "span_self", "match": ["action:preempt"],
                           "minus": ["dispatch:*"]},
       "preemptors_solved": {
           "kind": "counter_delta",
           "counter": 'preemptors_solved_total{result="solved"}'},
       "prescreen_calls": {"kind": "counter_delta",
                           "counter": "scenario_prescreen_calls_total"}}
SMALL = dict(nodes=64, replicas=2, whole=4, victims=32, share=0.5,
             departments=2, leaves=1, limit_factor=1.0)
TRIAL = dict(nodes=256, replicas=4, whole=16, victims=128, share=0.25,
             departments=2, leaves=2, limit_factor=1.0)


@pytest.fixture(scope="module")
def cell():
    return spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)


def test_the_files_give_the_cycles_shapes(cell):
    shape = cell.generator.file_shape(cell)
    assert (shape["prefixes"], shape["rows"], shape["t"], shape["t_pad"],
            shape["nodes"], shape["resources"], shape["groups"],
            shape["replicas"]) == (2048, 4096, 4, 4, 98304, 3, 2, 32)
    # Four whole nodes, two jobs a node, two steps a job.
    assert shape["seated_at_step"] == 16
    # The replica's four pods and the core gang of the eight jobs that
    # went whole, a confirm a replica.
    assert shape["confirms"] == [[32, 16]]
    assert shape["confirm_steps"] == 32 * 20
    # The step that binds and the step that arrived: 64 jobs, two groups
    # each, 256 pods, the largest group three workers.
    assert shape["wave"] == [128, 64, 256, 4]
    assert cell.chips == 1 and cell.entry["config"] == CONFIG
    assert cell.entry["traffic"] == MIX
    assert cell.generator.__file__ == os.path.join(
        ROOT, "benchmark", "generators", "preempt_replicas.py")
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "inqueue_eviction.py")
    # The fleet is the reclaim cell's own file's, not a copy of it.
    other = spec.Cell(spec.load_benchmark(ROOT), "ns98k-reclaim-wide", ROOT)
    assert cell.generator.base.__file__ == other.generator.__file__
    assert issubclass(cell.generator.Client, cell.generator.base.Client)


def test_the_traffic_is_the_issues_to_the_letter(cell):
    traffic = cell.traffic
    assert {k: traffic[k] for k in (
        "replicas_per_cycle", "lifetime_cycles", "pending_cycles_max",
        "warm_cycles")} == {"replicas_per_cycle": 32, "lifetime_cycles": 1,
                            "pending_cycles_max": 2, "warm_cycles": 1}
    assert 16 <= traffic["replicas_per_cycle"] <= 48
    gang = traffic["gang"]
    assert (gang["priority"], gang["preemptible"]) == (125, False)
    assert [(r["name"], r["count"], r["cpu"], r["memory"], r["gpu"])
            for r in gang["roles"]] == [("leader", 1, "36", "288Gi", 8),
                                        ("worker", 3, "32", "256Gi", 8)]
    assert traffic["generator"] == "preempt_replicas"


def test_the_configuration_is_north_stars_fleet_with_the_team_inside(cell):
    config = cell.config
    bench = spec.load_benchmark(ROOT)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["file"] == "benchmark/configs/preempt-98k.json"
    assert entry["reduced"] == config["reduced"] == ["backlog", "occupancy"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell.entry["why"]) <= 200
    ns = spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                     "north-star-98k.json"))
    assert config["nodes"] == ns["nodes"]
    assert config["queues"] == ns["queues"]
    occ = config["occupancy"]
    for key in ("preemptible_nodes_share", "job_pods", "min_available",
                "preemptible", "pod"):
        assert occ[key] == ns["occupancy"][key]
    assert occ["priority"] == 50 and occ["whole_node"]["priority"] == 100
    assert {k: v for k, v in occ["whole_node"].items() if k != "priority"} \
        == ns["occupancy"]["whole_node"]
    assert config["scheduler"] == {"max_victims_considered": 1024,
                                   "scenario_prescreen_max": 2048,
                                   "scenario_prescreen_after": 1}
    assert config["reference"] == "inqueue_eviction"
    assert len(config["guarantees"]) == 8
    assert config["backlog"]["pending_jobs"] == 32
    # The priorities are the pod-grouper's.
    from kai_scheduler_tpu.models import groupers
    assert groupers.TRAIN == ("train", 50, True)
    assert groupers.BUILD == ("build", 100, False)
    assert groupers.INFERENCE == ("inference", 125, False)


def test_the_entries_are_there_and_nothing_else_moved():
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
    for name in ("gc_full_ms", "strategy_declines", "reclaim_host_ms",
                 "consolidation_host_ms", "dispatch_ms", "topology_ms"):
        assert CELL not in by_name[name]["workloads"]
    for name, reader in NEW.items():
        entry = by_name[name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "cycle_ms" and len(entry["unit"]) <= 16
        doc = spec.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert {k: doc[k] for k in ("name", "unit", "better", "source",
                                    "layer", "moves")} == {
            k: entry[k] for k in ("name", "unit", "better", "source",
                                  "layer", "moves")}
        assert doc["reader"] == reader
        assert doc["reader"]["kind"] in readers.KINDS
    assert by_name["preempt_host_ms"]["layer"] == "session and actions"
    assert (by_name["preemptors_solved"]["unit"],
            by_name["prescreen_calls"]["unit"]) == ("jobs/cycle",
                                                    "calls/cycle")
    assert bench["run_seconds"] == 51
    assert [m["name"] for m in bench["end_to_end"]] == [
        "cycle_ms", "pods_bound_per_s", "setup_s"]


def test_the_counters_the_metrics_read_are_the_programs():
    from kai_scheduler_tpu.utils.metrics import _key
    assert _key("preemptors_solved_total", {"result": "solved"}) \
        == NEW["preemptors_solved"]["counter"]


def test_reckon_and_the_least_bytes_follow_the_work(cell):
    reck = cell.generator.reckon(cell)
    one = 2048 * 98304 * 3 * 4
    assert reck["program_bytes"] == 7 * one
    assert reck["bytes"] == 4 * (98304 * 12 + 4096 * 5 + 4 * 6)
    # One pool written, and read once a run of the replica (the leader,
    # the workers), for each of the cycle's 32 calls.
    shapes = {"prefixes": 2048, "nodes": 98304, "groups": 2, "calls": 32,
              "resources": 3}
    assert cell.generator.prefix_feasibility_bytes(**shapes) \
        == 32 * 3 * one
    assert cell.generator.exact_scan_bytes(
        steps=640, nodes=98304, resources=3, label_cols=1,
        taint_cols=1) == 640 * 48 * 98304


def test_preflight_compiles_the_prescreen_for_the_chip_over_the_floor(
        capsys):
    from benchmark import preflight
    if preflight.described_chip() is None:
        pytest.skip("no v5e:2x2 topology can be described here")
    capsys.readouterr()
    assert preflight.main(["--workload", CELL]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith(CELL))
    assert "program compiled for v5e reserves 5.44 GiB" in line
    assert "UNDER" not in line


def test_preflight_without_the_compiler_is_over_the_floor_too(capsys):
    from benchmark import preflight
    assert preflight.main(["--no-compile", "--workload", CELL]) == 0
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith(CELL))
    assert "32 calls a cycle" in line and "UNDER" not in line


@pytest.fixture(scope="module")
def cut_root(tmp_path_factory):
    """A benchmark root whose one cell is the real cell's files with the
    fleet cut to 64 nodes: the generator, the reference and the metric
    files are the real ones, found in ``benchmark/``."""
    tmp = tmp_path_factory.mktemp("cut")
    bench = spec.load_benchmark(ROOT)
    cell = spec.Cell(bench, CELL, ROOT)
    cell = cell.generator.cut_cell(cell, **SMALL, warm=2)
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
    out = run.run_cell(CELL, 3000000019, 0.5, True, require_chip=False,
                       root=cut_root)
    assert out["correct"], out["compared"]
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 15
    assert all(v == [0, 0] for v in out["compared"].values())
    line = out["run"]
    assert (line["generator"], line["reference"]) == (
        "preempt_replicas", "inqueue_eviction")
    assert line["evictions_per_cycle"] == [64]
    assert line["binds_per_cycle"] == [8]
    assert line["commits_per_cycle"] == [2]
    assert line["solves_per_cycle"] == [2]
    assert line["prescreens_per_cycle"] == [2]
    assert line["bind_cycles_after_arrival"] == [1]
    assert line["primed"]["trial"] == {
        "seconds": line["primed"]["trial"]["seconds"], "nodes": 256,
        "replicas": 4, "evictions_per_cycle": [128]}
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert out["attempted"] % 2 == 0
    # Prime compiled the cycle's three programs; neither the warm cycle
    # nor the window compiled the prescreen or the confirm again.
    assert set(line["primed"]["kernels"]) == {
        "batch_prefix_feasibility", "allocate_jobs_kernel[32,16]",
        "_allocate_groups_packed[8, 4, 16, 4]"}
    warm = {name for c in line["warm_cycles"] for name in c["compiled"]}
    assert "jit(batch_prefix_feasibility)" not in warm
    assert line["window_compiles"] == 0
    metrics = out["metrics"]
    assert metrics["preemptors_solved"] == {"value": 2.0,
                                            "unit": "jobs/cycle"}
    assert metrics["prescreen_calls"] == {"value": 2.0,
                                          "unit": "calls/cycle"}
    assert metrics["preempt_host_ms"]["value"] > 0
    assert metrics["prescreen_counted"]["value"] == 0.0
    assert metrics["prescreen_scan_steps"]["value"] == 4.0     # 2 a call
    assert metrics["prescreen_masked"]["value"] == 0.0
    assert metrics["scenarios_skipped"]["value"] == 28.0       # 14 a solve
    assert metrics["affinity_pod_walks"]["value"] == 0.0
    assert metrics["proportion_rollup_walks"]["value"] == 0.0
    for name in ("reclaim_host_ms", "consolidation_host_ms", "gc_full_ms",
                 "strategy_declines"):
        assert name not in metrics
    assert "scenario_prescreen_roofline" not in metrics   # no chip


def test_a_program_without_the_span_and_counters_leaves_the_metrics_out(
        cell):
    """On the parent neither counter family exists: ``run_once`` leaves a
    missing counter out of the record, the reader finds nothing and
    returns None, and the line has no such metric.  ``action:preempt`` is
    older than this PR, so ``preempt_host_ms`` reads on the parent too."""
    import types
    metrics = [m for m in cell.per_layer if m["name"] in NEW]
    assert len(metrics) == 3
    assert set(readers.counters_wanted(metrics)) == {
        NEW["preemptors_solved"]["counter"],
        NEW["prescreen_calls"]["counter"]}
    run = {"records": [types.SimpleNamespace(counters={}, spans=[])]}
    assert readers.read_all(metrics, run) == {}
    run["records"][0].counters[NEW["prescreen_calls"]["counter"]] = 32.0
    assert readers.read_all(metrics, run) == {
        "prescreen_calls": {"value": 32.0, "unit": "calls/cycle"}}
    run["records"][0].spans = [
        ("action:preempt", "action", 1, None, 0.0, 2.0),
        ("dispatch:scenario_prescreen", "dispatch", 2, 1, 0.1, 0.5)]
    assert readers.read_all(metrics, run)["preempt_host_ms"] == {
        "value": 1500.0, "unit": "ms"}


def test_the_client_deepens_the_flight_recorder_for_the_whole_step(cell):
    """512 spans a cycle would drop two thirds of a step of 32 solves, and
    the span readers sum what was kept."""
    from kai_scheduler_tpu.utils.tracing import TRACER
    gen = cell.generator
    assert gen.SPANS_A_SOLVE * 32 + 512 > 2048
    before = TRACER.max_spans_per_trace
    try:
        cut = gen.cut_cell(cell, **SMALL)
        client = gen.build(cut, 3)
        assert TRACER.max_spans_per_trace >= gen.SPANS_A_SOLVE * 2 + 512
        client.close()
    finally:
        TRACER.max_spans_per_trace = before


@pytest.mark.parametrize("cut", (SMALL, TRIAL), ids=("64n", "256n"))
@pytest.mark.parametrize("kind", ("queue_blind", "priority_blind",
                                  "one_more", "partial_gang", "sound"))
def test_a_control_moves_its_own_counts_alone(kind, cut):
    from control_preempt import MOVES, as_said, run_control
    out = run_control(CELL, 7, kind, cut=cut)
    assert out["correct"] == (kind == "sound")
    assert as_said(out), out["compared"]
    assert set(MOVES[kind]) <= set(out["compared"])


@pytest.mark.parametrize("seed", (1, 2, 3))
@pytest.mark.parametrize("kind", ("queue_blind", "priority_blind",
                                  "one_more", "partial_gang", "sound"))
def test_the_controls_at_the_cells_own_width(kind, seed):
    """98,304 nodes, 32 replicas a cycle, no device: the fleet is built
    and the preemptor is numpy."""
    from control_preempt import as_said, run_control
    out = run_control(CELL, seed, kind)
    assert out["correct"] == (kind == "sound")
    assert as_said(out), out["compared"]
    moved = {k: v[0] for k, v in out["compared"].items() if v[0]}
    assert moved == {
        "queue_blind": {"victims_from_other_queue": 8},
        "priority_blind": {"victims_not_lower_priority": 8},
        "one_more": {"evictions_beyond_need": 4,
                     "evictions_not_reference": 4},
        "partial_gang": {"gangs_partly_bound": 1}, "sound": {}}[kind]
