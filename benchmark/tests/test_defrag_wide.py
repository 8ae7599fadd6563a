"""The cell ``defrag98k-pytorchjob-1k`` as files: its shapes and bytes from
the files alone, its four metric files and the eight lists it joined, its
generator through the same ``run_cell`` at a width the CPU holds, and the
controls of its reference (``control_relocation.py``), each of which has to
come out not correct by the count named for it."""

import json
import os

import numpy as np
import pytest

from conftest import ROOT

from benchmark.harness import readers, spec

CELL = "defrag98k-pytorchjob-1k"
NEW_METRICS = {
    "consolidation_host_ms": "span_self",
    "pods_moved": "counter_delta",
    "victims_replaced": "counter_delta",
    "allocate_bulk_ms": "span_sum",
}
JOINED = ("allocate_jobs_kernel_ms", "allocate_jobs_kernel_roofline",
          "scenario_prescreen_ms", "scenario_prescreen_kernel_ms",
          "scenario_prescreen_roofline", "prescreen_operands_ms",
          "scenarios_skipped", "prescreen_counted")
SMALL = dict(nodes=64, share=0.75, departments=2, leaves=2,
             fragment_queues=2, whole=4, workers=3, victims=16)
SHARED = dict(nodes=256, share=0.25, departments=4, leaves=2,
              fragment_queues=1, whole=8, workers=7, victims=32)


@pytest.fixture(scope="module")
def cell():
    return spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)


def test_the_files_give_the_cycles_shapes(cell):
    shape = cell.generator.file_shape(cell)
    assert (shape["prefixes"], shape["rows"], shape["t_pad"],
            shape["nodes"], shape["resources"]) == (1024, 2048, 128,
                                                    98304, 3)
    # The confirm's two shapes: the gang and the first victim job; the
    # gang and the 128 victim jobs it places again, 129 chunks, 384 tasks.
    assert shape["confirms"] == [[256, 4], [512, 256]]
    assert shape["confirm_steps"] == 130 + 384
    # The wave's two rounds: 128 fragment jobs and two gangs of two groups
    # (132 groups, 130 jobs, 512 tasks), then the arrival alone.
    assert shape["waves"] == [[256, 256, 512, 128], [2, 1, 128, 128]]
    assert (shape["groups"], shape["moved"]) == (2, 256)
    assert cell.chips == 1 and cell.entry["config"] == "defrag-98k"
    assert cell.entry["traffic"] == "consolidate-pytorchjob-128x8"
    assert cell.generator.__file__ == os.path.join(
        ROOT, "benchmark", "generators", "consolidation_gangs.py")
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "relocation.py")


def test_the_configuration_states_its_cuts_and_guarantees(cell):
    config = cell.config
    entry = next(c for c in spec.load_benchmark(ROOT)["configs"]
                 if c["name"] == "defrag-98k")
    assert entry["reduced"] == config["reduced"] == ["backlog", "occupancy"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert len(config["guarantees"]) == 7
    assert config["scheduler"] == {"max_victims_considered": 1024,
                                   "scenario_prescreen_max": 1024,
                                   "scenario_prescreen_after": 1}
    occ = config["occupancy"]
    frag = occ["fragment"]
    assert round(98304 * occ["fragmented_nodes_share"]) == 49152
    # A fragment job stands at its gang minimum, one job a node, and
    # nothing else shapes the start.
    assert frag["job_pods"] == frag["min_available"] == 2
    assert set(frag) == {"job_pods", "min_available", "preemptible",
                         "queues", "pod"}
    assert cell.traffic["gang"]["roles"][0]["name"] == "master"
    assert cell.traffic["gang"]["preemptible"] is False
    # The first gang binds in the second cycle and completes before the
    # fourth, which is the first the window holds.
    assert cell.traffic["warm_cycles"] \
        == cell.traffic["lifetime_cycles"] + 2
    assert "running_at_start" not in cell.traffic


def test_reckon_and_the_kernels_least_bytes(cell):
    reck = cell.generator.reckon(cell)
    one = 1024 * 98304 * 3 * 4
    assert reck["program_bytes"] == 7 * one
    assert reck["bytes"] == 4_762_624
    # One pool written, and read once for each of the gang's two groups:
    # 4.4 ms at the chip's 819 GB/s.
    assert cell.generator.prefix_feasibility_bytes(
        prefixes=1024, nodes=98304, groups=2, resources=3) == 3 * one
    assert 3 * one / 819e9 == pytest.approx(4.42e-3, rel=2e-3)
    # The confirms' 514 real steps at 48 bytes a node a step.
    assert cell.generator.exact_scan_bytes(
        steps=514, nodes=98304, resources=3, label_cols=1,
        taint_cols=1) == 514 * 48 * 98304


def test_preflight_judges_the_cell_by_its_programs_part(capsys):
    from benchmark import preflight
    assert preflight.main(["--no-compile", "--workload", CELL]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith(CELL))
    assert "client's buffers reckoned 4.8 MB" in line
    assert "program's temporaries reckoned 7.88 GiB" in line
    assert "UNDER" not in line


def test_the_metric_files_and_the_lists_the_cell_joined(cell):
    bench = spec.load_benchmark(ROOT)
    by_name = {m["name"]: m for m in cell.per_layer}
    for name, kind in NEW_METRICS.items():
        doc = by_name[name]
        assert doc["reader"]["kind"] == kind and kind in readers.KINDS
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert {k: doc[k] for k in ("unit", "better", "source", "layer",
                                    "moves")} == {
            k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
    for name in JOINED:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL
        assert name in by_name
    # The key-less entries are this cell's too; the first cell's dispatch
    # and topology spans and the reclaim action's own time are not.
    assert {"snapshot_ms", "session_open_ms", "allocate_host_ms",
            "device_calls", "device_idle", "statement_ms",
            "upload_bytes"} <= set(by_name)
    assert not {"dispatch_ms", "topology_ms", "reclaim_host_ms"} \
        & set(by_name)
    for other in ("tas65k-pytorchjob-16k", "ns98k-reclaim-wide"):
        cut = spec.Cell(bench, other, ROOT)
        assert not set(NEW_METRICS) & {m["name"] for m in cut.per_layer}


@pytest.fixture(scope="module")
def cut_root(tmp_path_factory):
    """A benchmark root whose one cell is the real cell's files with the
    fleet cut to 64 nodes: the generator, the reference and the metric
    files are the real ones, found in ``benchmark/``."""
    from control_relocation import cut_cell
    tmp = tmp_path_factory.mktemp("cut")
    bench = spec.load_benchmark(ROOT)
    cell = cut_cell(spec.Cell(bench, CELL, ROOT), **SMALL)
    bench["paths"] = ["own", os.path.relpath(
        os.path.join(ROOT, "benchmark"), tmp)]
    bench["workloads"] = [{**cell.entry, "config": "own", "traffic": "own"}]
    for kind, doc in (("configs", cell.config), ("traffic", cell.traffic)):
        path = tmp / "own" / kind / "own.json"
        path.parent.mkdir(parents=True)
        path.write_text(json.dumps(doc))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp)


def test_the_generator_runs_through_run_cell(cut_root):
    import jax

    from benchmark import run
    jax.clear_caches()
    out = run.run_cell(CELL, 3000000019, 0.5, True, require_chip=False,
                       root=cut_root)
    assert out["correct"], out["compared"]
    assert list(out["compared"]) == list(
        spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT).generator.LIMITS)
    assert len(out["compared"]) == 11
    line = out["run"]
    assert (line["generator"], line["reference"]) == ("consolidation_gangs",
                                                      "relocation")
    # One consolidation in every cycle of the window: the four nodes'
    # eight pods moved, every one with a place, the gang and last cycle's
    # eight bound.
    assert line["evictions_per_cycle"] == [8]
    assert line["places_per_cycle"] == [12]
    assert line["binds_per_cycle"] == [12]
    assert line["prescreens_per_cycle"] == [1]
    assert line["counted_prescreens_per_cycle"] == [0.0]
    assert line["bind_cycles_after_arrival"] == [1]
    assert out["failed"] == 0 and out["attempted"] >= 1
    # At this width five jobs wait, under the wave's threshold of 32: the
    # allocate action takes them one by one.
    assert line["dispatches"] == [
        "dispatch:allocate_grouped", "dispatch:allocate_jobs",
        "dispatch:allocate_jobs_multi", "dispatch:fair_share",
        "dispatch:scenario_prescreen"]
    assert set(line["primed"]["kernels"]) == {
        "batch_prefix_feasibility", "allocate_jobs_kernel[8,4]",
        "allocate_jobs_kernel[16,8]",
        "_allocate_groups_packed[8, 8, 16, 4]",
        "_allocate_groups_packed[2, 1, 4, 4]",
        "allocate_jobs_kernel[4,2] first cycle"}
    warm = {name for c in line["warm_cycles"] for name in c["compiled"]}
    assert not warm & {"jit(batch_prefix_feasibility)",
                       "jit(allocate_jobs_kernel)"}
    assert line["window_compiles"] == 0
    metrics = out["metrics"]
    assert metrics["consolidation_host_ms"]["value"] > 0
    assert metrics["pods_moved"] == {"value": 8.0, "unit": "pods/cycle"}
    assert metrics["victims_replaced"] == {"value": 8.0,
                                           "unit": "pods/cycle"}
    assert metrics["scenarios_skipped"] == {"value": 2.0,
                                            "unit": "scenarios/cycle"}
    assert metrics["prescreen_counted"] == {"value": 0.0,
                                            "unit": "calls/cycle"}
    assert metrics["scenario_prescreen_ms"]["value"] > 0
    assert metrics["prescreen_operands_ms"]["value"] > 0
    assert "scenario_prescreen_roofline" not in metrics   # no chip
    assert {"snapshot_ms", "session_open_ms", "allocate_host_ms",
            "operands_ms", "statement_ms", "stage_ms", "device_wait_ms",
            "device_calls", "upload_bytes", "convert_bytes",
            "download_bytes"} <= set(metrics)
    # No wave at this width, so nothing for its metric to read.
    assert not {"reclaim_host_ms", "allocate_bulk_ms"} & set(metrics)


def test_a_program_that_moves_the_newest_first_cannot_run_the_cell(
        cut_root, monkeypatch):
    """The trial before the fleet is built (``try_fewest_moves``): with
    the victim order the program had before PR 37 the run stops with a
    message and a status other than 0, and prints no result."""
    from benchmark import run
    from control_relocation import newest_first
    from kai_scheduler_tpu.actions import consolidation
    monkeypatch.setattr(consolidation, "collect_consolidation_victims",
                        newest_first)
    with pytest.raises(SystemExit) as stop:
        run.run_cell(CELL, 2066160830, 0.5, False, require_chip=False,
                     root=cut_root)
    assert stop.value.code not in (0, None)
    assert "cannot run the configuration" in str(stop.value.code)
    assert "6 pods" in str(stop.value.code)


@pytest.mark.parametrize("cut", (SMALL, SHARED), ids=("64n", "256n"))
@pytest.mark.parametrize("kind, numbers", (
    ("one_more", {"moves_beyond_need"}),
    ("lose_one", {"moved_without_place", "moved_not_rebound"}),
    ("split", {"victim_gangs_split", "moves_beyond_need"}),
    ("not_preemptible", {"moved_not_preemptible"})))
def test_a_control_comes_out_not_correct(kind, numbers, cut):
    from control_relocation import run_control
    out = run_control(CELL, 7, kind, cut=cut)
    assert not out["correct"]
    # By the count named for it (a pod moved alone leaves its node
    # occupied, so it is also a move beyond need), and by no other.
    assert {k for k, v in out["compared"].items() if v[0]} == numbers
    if kind == "lose_one":
        assert out["compared"]["moved_without_place"][0] == 1
        assert out["compared"]["moved_not_rebound"][0] == 1
    if kind == "not_preemptible":
        assert out["compared"]["moved_not_preemptible"][0] == 2


@pytest.mark.parametrize("cut", (SMALL, SHARED), ids=("64n", "256n"))
def test_the_plain_consolidator_with_nothing_dropped_is_correct(cut):
    from control_relocation import run_control
    out = run_control(CELL, 7, "sound", cut=cut)
    assert out["correct"], out["compared"]


def test_the_fewest_moves_of_the_reference(cell):
    ref = cell.reference
    capacity = np.tile([64.0, 512.0, 8.0], (5, 1))
    pod = np.array([4.0, 32.0, 1.0])
    used = np.array([2 * pod, 2 * pod, 8 * pod, capacity[3], 4 * pod])
    pods = np.array([2, 2, 8, 1, 4])
    movable = used.copy()
    movable[3] = 0                   # a whole-node pod does not move
    largest = np.where(movable > 0, pod, 0.0)
    worker = np.array([32.0, 256.0, 8.0])
    master = np.array([36.0, 288.0, 8.0])
    gang = np.array([master, worker])
    assert not ref.pods_that_fit(capacity, used, pods, 110, gang).any()
    # Two nodes lacked, the two cheapest cost two pods each.
    assert ref.fewest_moves(capacity, used, pods, 110, gang, movable,
                            largest) == 4
    # Three lacked: the third costs four; four lacked: eight more.
    three = np.array([master, worker, worker])
    assert ref.fewest_moves(capacity, used, pods, 110, three, movable,
                            largest) == 8
    four = np.array([master, worker, worker, worker])
    assert ref.fewest_moves(capacity, used, pods, 110, four, movable,
                            largest) == 16
    # Five: the node under the whole-node pod gives nothing up, so no
    # move seats the gang and none is needed.
    five = np.array([master, worker, worker, worker, worker])
    assert ref.fewest_moves(capacity, used, pods, 110, five, movable,
                            largest) == 0
    # A node that is idle takes a pod for nothing.
    used[0], pods[0], movable[0], largest[0] = 0, 0, 0, 0
    assert ref.fewest_moves(capacity, used, pods, 110, gang, movable,
                            largest) == 2


def test_the_small_rules_of_the_reference(cell):
    ref = cell.reference
    assert ref.move_faults([("a", True), ("b", False), ("c", True)],
                           {"a", "b"}) == {"moved_not_preemptible": 1,
                                           "moved_without_place": 1}
    assert ref.jobs_moved_in_part({"j": 2, "k": 2}, {"j": 1, "k": 2}) == 1
    idle = np.array([100.0, 100.0, 16.0])
    ask = np.array([64.0, 64.0, 16.0])
    assert ref.moves_without_consolidator(0, None, idle) == 0
    assert ref.moves_without_consolidator(4, None, idle) == 4
    assert ref.moves_without_consolidator(4, ask, idle) == 0
    assert ref.moves_without_consolidator(4, ask + 1, idle) == 4
    assert ref.replacements_not_bound({"a": "a2", "b": "b2"}, {"a2"}) == 1
    assert ref.gang_faults(3, 4) == {"gangs_partly_bound": 1}
