"""The cell ``pools98k-pytorchjob-256`` as files: its shapes and bytes from
the files alone, what its entries add to ``BENCHMARK.json``, its generator
through the same ``run_cell`` at a width the CPU holds, the reference's
reading of selectors, affinity terms and taints on numbers made by hand,
and the controls of its reference (``control_pools.py``), each of which
has to come out not correct by its own counts alone."""

import json
import os

import numpy as np
import pytest

from conftest import ROOT

from benchmark.harness import readers, spec

CELL = "pools98k-pytorchjob-256"
CONFIG = "pools-98k"
KEY = "nvidia.com/gpu.product"
A100, H100, H200 = ("NVIDIA-A100-SXM4-80GB", "NVIDIA-H100-80GB-HBM3",
                    "NVIDIA-H200")
# The accepted metrics that read what the cell runs, and list it.
LISTED = (
    "allocate_jobs_kernel_ms", "allocate_jobs_kernel_roofline",
    "scenario_prescreen_ms", "scenario_prescreen_kernel_ms",
    "scenario_prescreen_roofline", "reclaim_host_ms",
    "prescreen_operands_ms", "scenarios_skipped", "prescreen_counted",
    "prescreen_scan_steps", "gc_full_collections", "gc_full_pause_s",
    "gc_young_pause_s", "gc_middle_pause_s", "operands_net_ms",
    "statement_net_ms", "affinity_pod_walks")
NEW = {"prescreen_masked": "scenario_prescreen_masked_total",
       "victims_filtered":
       'reclaim_victims_filtered_total{reason="excluded-node"}',
       "arena_full_rebuilds": "arena_full_rebuild_total"}
PRESCREEN_CELLS = ["ns98k-reclaim-wide", "defrag98k-pytorchjob-1k",
                   "spread98k-pytorchjob-256", CELL]
SMALL = dict(nodes=64, pools=(24, 32, 8), idle=4, whole=4, gang=24,
             victims=32, share=0.5, departments=2, leaves=2)
SHARED = dict(nodes=256, pools=(96, 128, 32), idle=8, whole=8, gang=32,
              victims=64)


@pytest.fixture(scope="module")
def cell():
    return spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)


def test_the_files_give_the_cycles_shapes(cell):
    shape = cell.generator.file_shape(cell)
    assert (shape["prefixes"], shape["rows"], shape["t"], shape["t_pad"],
            shape["nodes"], shape["resources"], shape["runs"]) == (
        1024, 2048, 256, 256, 98304, 3, 2)
    assert shape["confirms"] == [[512, 4], [512, 128]]
    assert shape["scan_steps"] == 257 + 384 + 256
    assert cell.chips == 1 and cell.entry["config"] == CONFIG
    assert cell.generator.__file__ == os.path.join(
        ROOT, "benchmark", "generators", "pool_reclaim_gangs.py")
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "pool_eviction.py")
    # The client is the reclaim cell's own file's, not a copy of it.
    other = spec.Cell(spec.load_benchmark(ROOT), "ns98k-reclaim-wide", ROOT)
    assert cell.generator.base.__file__ == other.generator.__file__
    assert issubclass(cell.generator.Client, cell.generator.base.Client)


def test_the_traffic_is_the_issues_to_the_letter(cell):
    traffic = cell.traffic
    assert {k: traffic[k] for k in (
        "pending_per_cycle", "lifetime_cycles", "pending_cycles_max",
        "warm_cycles")} == {"pending_per_cycle": 1, "lifetime_cycles": 1,
                            "pending_cycles_max": 2, "warm_cycles": 1}
    gang = traffic["gang"]
    assert [(r["name"], r["count"], r["cpu"], r["memory"], r["gpu"])
            for r in gang["roles"]] == [("master", 1, "8", "64Gi", 1),
                                        ("worker", 255, "4", "32Gi", 1)]
    assert gang["node_affinity_required"] == [{"expressions": [
        {"key": KEY, "operator": "In", "values": [H100, H200]}]}]
    assert gang["tolerations"] == ["reserved"]
    assert cell.generator.gang_constraints(traffic) == (
        gang["node_affinity_required"], {"reserved"})


def test_the_configuration_is_north_stars_fleet_in_three_pools(cell):
    config = cell.config
    bench = spec.load_benchmark(ROOT)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"] == ["backlog", "occupancy"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell.entry["why"]) <= 200
    ns = spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                     "north-star-98k.json"))
    assert {k: v for k, v in config["nodes"].items() if k != "pools"} \
        == ns["nodes"]
    assert config["queues"] == ns["queues"]
    assert config["scheduler"] == ns["scheduler"]
    assert [(p["name"], p["nodes"], p["labels"], p["taints"])
            for p in config["nodes"]["pools"]] == [
        ("a100", 32768, {KEY: A100}, []), ("h100", 49152, {KEY: H100}, []),
        ("h200", 16384, {KEY: H200}, ["reserved"])]
    occ = config["occupancy"]
    for key in ("preemptible_nodes_share", "job_pods", "min_available",
                "preemptible", "pod", "whole_node"):
        assert occ[key] == ns["occupancy"][key]
    assert occ["idle_nodes"] == {"pool": "a100", "count": 64}
    assert config["reference"] == "pool_eviction"
    assert len(config["guarantees"]) == 9
    pools = cell.generator.pool_of_nodes(config)
    assert np.bincount(pools).tolist() == [32768, 49152, 16384]
    assert (np.diff(pools) >= 0).all()       # contiguous blocks by index


def test_the_entries_are_appended_and_nothing_else_moved():
    bench = spec.load_benchmark(ROOT)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED:
        assert by_name[name]["workloads"][-1] == CELL
    for name in ("gc_full_ms", "strategy_declines"):
        assert CELL not in by_name[name]["workloads"]
    last = bench["per_layer"][-4:]
    assert [m["name"] for m in last] == [
        "prescreen_masked", "node_affinity_ms", "victims_filtered",
        "arena_full_rebuilds"]
    assert last[0]["workloads"] == PRESCREEN_CELLS
    assert all(m["workloads"] == [CELL] for m in last[1:])
    assert all(m["moves"] == "cycle_ms" and len(m["unit"]) <= 16
               for m in last)
    for entry in last:
        doc = spec.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", entry["name"] + ".json"))
        assert {k: doc[k] for k in ("unit", "better", "source", "layer",
                                    "moves")} == {
            k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
        assert doc["reader"]["kind"] in readers.KINDS
        if entry["name"] in NEW:
            assert doc["reader"] == {"kind": "counter_delta",
                                     "counter": NEW[entry["name"]]}
        else:
            assert doc["reader"] == {"kind": "span_sum",
                                     "match": ["predicates:node_affinity"]}


def test_the_counters_the_metrics_read_are_the_programs():
    from kai_scheduler_tpu.utils.metrics import _key
    assert _key("reclaim_victims_filtered_total",
                {"reason": "excluded-node"}) == NEW["victims_filtered"]


def test_reckon_and_the_least_bytes_follow_the_work_not_the_form(cell):
    reck = cell.generator.reckon(cell)
    one = 1024 * 98304 * 3 * 4
    assert reck["program_bytes"] == 7 * one
    assert reck["bytes"] == 4_765_696 + 512 * 98304
    # One pool written, and read once a run of the gang with the run's
    # mask row: two runs, whatever t_pad is.
    shapes = {"prefixes": 1024, "nodes": 98304, "resources": 3, "runs": 2}
    assert cell.generator.prefix_feasibility_bytes(**shapes) \
        == 3 * one + 2 * 98304
    # The exact scan's step reads its [N] row of the mask too.
    assert cell.generator.exact_scan_bytes(
        steps=897, nodes=98304, resources=3, label_cols=1,
        taint_cols=1) == 897 * 49 * 98304


def test_preflight_compiles_the_masked_prescreen_for_the_chip(capsys):
    """Under a mask the program holds the vmapped exact scan with ONE
    ``[T,N]`` mask beside it (not one a prefix), and the TPU compiler
    reserves for a described v5e what the chip reserved for the scanned
    form before (PR 35, PR 42)."""
    from benchmark import preflight
    if preflight.described_chip() is None:
        pytest.skip("no v5e:2x2 topology can be described here")
    capsys.readouterr()
    assert preflight.main(["--workload", CELL]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith(CELL))
    assert "program compiled for v5e reserves 6.47 GiB" in line
    assert "UNDER" not in line


@pytest.fixture(scope="module")
def cut_root(tmp_path_factory):
    """A benchmark root whose one cell is the real cell's files with the
    fleet cut to 64 nodes: the generator, the reference and the metric
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
    out = run.run_cell(CELL, 3000000019, 0.5, True, require_chip=False,
                       root=cut_root)
    assert out["correct"], out["compared"]
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 14
    line = out["run"]
    assert (line["generator"], line["reference"]) == (
        "pool_reclaim_gangs", "pool_eviction")
    assert line["evictions_per_cycle"] == [24]
    assert line["binds_per_cycle"] == [24]
    assert line["prescreens_per_cycle"] == [1]
    assert line["bind_cycles_after_arrival"] == [1]
    assert line["placements_checked"] == 24 * line["cycles_in_window"]
    assert line["nodes_the_gang_may_use"] == 40
    # The A100 victims: a share of 0.5 of 24 nodes, two jobs a node.
    assert line["victims_filtered_per_cycle"] == [24]
    assert line["primed"]["trial"]["evictions_per_cycle"] == [64]
    assert out["failed"] == 0 and out["attempted"] >= 1
    # Prime compiled the masked programs of the cycle; the warm cycle and
    # the window compiled none of them.
    assert set(line["primed"]["kernels"]) == {
        "batch_prefix_feasibility", "allocate_jobs_kernel[32,2] bind",
        "allocate_jobs_kernel[32,4]", "allocate_jobs_kernel[64,8]"}
    warm = {name for c in line["warm_cycles"] for name in c["compiled"]}
    assert not warm & {"jit(batch_prefix_feasibility)",
                       "jit(_allocate_groups_packed)",
                       "jit(allocate_jobs_kernel)"}
    assert line["window_compiles"] == 0
    metrics = out["metrics"]
    assert metrics["prescreen_masked"] == {"value": 1.0,
                                           "unit": "calls/cycle"}
    assert metrics["prescreen_scan_steps"]["value"] == 32.0    # t_pad
    assert metrics["prescreen_counted"]["value"] == 0.0
    assert metrics["scenarios_skipped"]["value"] == 10.0
    assert metrics["device_calls"]["value"] == 5.0
    assert metrics["victims_filtered"] == {"value": 24.0,
                                           "unit": "jobs/cycle"}
    # The gang's pods carry a toleration and come and go: every cycle's
    # vocabulary differs from the one before, and the arena packs in full.
    assert metrics["arena_full_rebuilds"]["value"] == 1.0
    assert metrics["node_affinity_ms"]["value"] > 0
    assert metrics["affinity_pod_walks"]["value"] == 0.0
    assert "gc_full_ms" not in metrics and "strategy_declines" not in metrics
    assert "scenario_prescreen_roofline" not in metrics   # no chip


def test_a_program_without_the_counters_leaves_the_metrics_out(cell):
    """On the parent none of the three new families, nor the span, exists
    (``arena_full_rebuild_total`` does): ``run_once`` leaves a missing
    counter out of the record, the reader finds nothing and returns None,
    and the line has no such metric."""
    import types
    metrics = [m for m in cell.per_layer
               if m["name"] in ("prescreen_masked", "victims_filtered",
                                "node_affinity_ms")]
    assert len(metrics) == 3
    assert set(readers.counters_wanted(metrics)) == {
        NEW["prescreen_masked"], NEW["victims_filtered"]}
    run = {"records": [types.SimpleNamespace(counters={}, spans=[])]}
    assert readers.read_all(metrics, run) == {}
    run["records"][0].counters[NEW["prescreen_masked"]] = 0.0
    assert readers.read_all(metrics, run) == {
        "prescreen_masked": {"value": 0.0, "unit": "calls/cycle"}}


@pytest.mark.parametrize("cut", (SMALL, SHARED), ids=("64n", "256n"))
@pytest.mark.parametrize("kind", ("mask_blind", "victim_blind",
                                  "selector_blind", "sound"))
def test_a_control_moves_its_own_counts_alone(kind, cut):
    from control_pools import MOVES, as_said, run_control
    out = run_control(CELL, 7, kind, cut=cut)
    assert out["correct"] == (kind == "sound")
    assert as_said(out), out["compared"]
    if kind == "selector_blind":
        # One victim nominated outside its pool in each of four reclaims.
        assert out["compared"]["pods_outside_pool"][0] == 4
    assert set(MOVES[kind]) <= set(out["compared"])


@pytest.mark.parametrize("seed", (1, 2, 3))
@pytest.mark.parametrize("kind", ("mask_blind", "victim_blind",
                                  "selector_blind", "sound"))
def test_the_controls_at_the_cells_own_width(kind, seed):
    """98,304 nodes, no device: the fleet is built and the reclaimer is
    numpy.  ``mask_blind`` binds every gang on the idle A100s and never
    reclaims: 256 pods outside the pool a cycle."""
    from control_pools import as_said, run_control
    out = run_control(CELL, seed, kind)
    assert out["correct"] == (kind == "sound")
    assert as_said(out), out["compared"]
    if kind == "mask_blind":
        assert out["compared"]["pods_outside_pool"][0] == 4 * 256
    if kind == "victim_blind":
        assert out["compared"]["evictions_on_excluded_nodes"][0] > 0
        assert out["compared"]["evictions_beyond_need"][0] > 0


def test_which_nodes_a_pod_may_use(cell):
    """``admitted`` on labels, taints and terms made by hand."""
    ref = cell.reference
    labels = [{KEY: A100}, {KEY: H100}, {KEY: H200}, {}, {KEY: H100, "x": "1"}]
    taints = [set(), set(), {"reserved"}, set(), {"reserved", "other"}]
    hopper = [{"expressions": [{"key": KEY, "operator": "In",
                                "values": [H100, H200]}]}]

    def nodes(selector=None, terms=None, tolerations=()):
        return np.flatnonzero(ref.admitted(
            labels, taints, selector or {}, terms or [],
            tolerations)).tolist()

    assert nodes() == [0, 1, 3]                   # the untainted nodes
    assert nodes(tolerations={"reserved"}) == [0, 1, 2, 3]
    assert nodes(tolerations={"reserved", "other"}) == [0, 1, 2, 3, 4]
    assert nodes(terms=hopper) == [1]
    assert nodes(terms=hopper, tolerations={"reserved"}) == [1, 2]
    assert nodes(selector={KEY: H200}, tolerations={"reserved"}) == [2]
    assert nodes(selector={KEY: H200}) == []
    # OR across terms, AND inside one.
    either = hopper + [{"expressions": [
        {"key": KEY, "operator": "DoesNotExist"}]}]
    assert nodes(terms=either) == [1, 3]
    both = [{"expressions": hopper[0]["expressions"] + [
        {"key": "x", "operator": "Exists"}]}]
    assert nodes(terms=both, tolerations={"reserved", "other"}) == [4]
    not_a100 = [{"expressions": [{"key": KEY, "operator": "NotIn",
                                  "values": [A100]}]}]
    assert nodes(terms=not_a100) == [1, 3]        # no label is not A100
    assert nodes(terms=[{"expressions": []}]) == []   # matches nothing
    assert ref.pods_outside([0, 1, 2], ref.admitted(
        labels, taints, {}, hopper, {"reserved"})) == 1


def test_the_fewest_evictions_and_the_order_under_a_row(cell):
    """What is idle on a node the gang may not use counts for nothing, and
    the bin-pack order is among the admitted nodes."""
    ref = cell.reference
    capacity = np.tile([64000.0, 512.0, 8.0], (4, 1))
    used = np.tile([32000.0, 256.0, 8.0], (4, 1))
    used[0, 2] = 0.0                              # 8 GPUs idle on node 0
    used[2, 2] = 6.0                              # 2 idle on node 2
    pods = np.full(4, 8)
    worker, master = [4000.0, 32.0, 1.0], [8000.0, 64.0, 1.0]
    gang = np.array([master] + [worker] * 5)
    victims = np.tile(worker, (8, 1))
    anywhere = np.ones(4, bool)
    hopper = np.array([False, True, True, True])
    assert ref.fewest_evictions(capacity, used, pods, 110, gang, victims,
                                anywhere) == 0
    assert ref.fewest_evictions(capacity, used, pods, 110, gang, victims,
                                hopper) == 4
    assert ref.evictions_on_excluded_nodes([0, 1, 0, 3], hopper) == 2
    # Bin-pack: the fullest feasible node first; under the row node 0's
    # eight idle GPUs are not there, and seven pods fit nowhere.
    used[1, 2] = 5.0                              # 3 idle on node 1
    assert ref.place_gang(capacity, used, pods, 110, gang[:4],
                          anywhere).tolist() == [2, 2, 1, 1]
    assert ref.place_gang(capacity, used, pods, 110, gang[:5],
                          hopper).tolist() == [2, 2, 1, 1, 1]
    assert ref.place_gang(capacity, used, pods, 110, gang,
                          hopper) is None
    assert ref.place_gang(capacity, used, pods, 110, gang,
                          anywhere).tolist() == [2, 2, 1, 1, 1, 0]
    assert ref.placements_not_reference(
        capacity, used, pods, 110, gang[:5], [2, 2, 1, 1, 0], hopper) == 1
