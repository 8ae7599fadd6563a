"""The cell ``ns98k-reclaim-wide`` as files: its shapes and bytes from the
files alone, its six metric files, its generator through the same
``run_cell`` at a width the CPU holds, and the controls of its reference
(``control_reclaim.py``), each of which has to come out not correct."""

import json
import os

import numpy as np
import pytest

from conftest import ROOT

from benchmark.harness import readers, spec

CELL = "ns98k-reclaim-wide"
NEW_METRICS = {
    "scenario_prescreen_ms": "span_sum",
    "scenario_prescreen_kernel_ms": "trace_program_time",
    "scenario_prescreen_roofline": "roofline",
    "reclaim_host_ms": "span_self",
    "prescreen_operands_ms": "span_self",
    "scenarios_skipped": "counter_delta",
}
SMALL = dict(nodes=64, share=1.0, departments=2, leaves=2, whole=4,
             gang=24, victims=32)
SHARED = dict(nodes=256, share=0.25, departments=4, leaves=4, whole=8,
              gang=32, victims=64)


@pytest.fixture(scope="module")
def cell():
    return spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)


def test_the_files_give_the_prescreens_shape(cell):
    shape = cell.generator.file_shape(cell)
    assert (shape["prefixes"], shape["rows"], shape["t_pad"],
            shape["nodes"], shape["resources"]) == (1024, 2048, 256,
                                                    98304, 3)
    # The confirm's two shapes: the gang and one pod of the victim that
    # shed its surplus, the gang and the core gangs of its 64 victim jobs.
    assert shape["confirms"] == [[512, 4], [512, 128]]
    assert shape["confirm_steps"] == 257 + 384
    assert cell.chips == 1 and cell.entry["config"] == "north-star-98k"
    assert cell.generator.__file__ == os.path.join(
        ROOT, "benchmark", "generators", "reclaim_gangs.py")
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "eviction.py")


def test_the_configuration_states_its_cuts_and_guarantees(cell):
    config = cell.config
    entry = next(c for c in spec.load_benchmark(ROOT)["configs"]
                 if c["name"] == "north-star-98k")
    assert entry["reduced"] == config["reduced"] == ["backlog", "occupancy"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    tiny = spec.load_json(os.path.join(
        ROOT, "benchmark", "tests", "data", "tiny", "configs",
        "tiny-reclaim.json"))
    # The fixture's guarantees word for word, and the two the reference
    # gained.
    assert config["guarantees"][:4] == tiny["guarantees"]
    assert len(config["guarantees"]) == 6
    assert config["scheduler"] == {"max_victims_considered": 1024,
                                   "scenario_prescreen_max": 1024,
                                   "scenario_prescreen_after": 1}
    # The occupier stands at its limit, 48 times what the solver may
    # consider; the cut says what chip runs it rests on.
    occ = config["occupancy"]
    jobs = round(98304 * occ["preemptible_nodes_share"]) * 8 \
        // occ["job_pods"]
    assert jobs == 49152 > config["scheduler"]["max_victims_considered"]
    tree = config["queues"]
    assert occ["preemptible_nodes_share"] == tree["limit_factor"] / (
        tree["departments"] * tree["leaves_per_department"])


def test_reckon_and_the_prescreens_least_bytes(cell):
    reck = cell.generator.reckon(cell)
    one = 1024 * 98304 * 3 * 4
    assert one == 1_207_959_552
    assert reck["program_bytes"] == 7 * one
    assert reck["bytes"] == 4_765_696
    shapes = {"prefixes": 1024, "nodes": 98304, "resources": 3}
    assert cell.generator.prefix_feasibility_bytes(**shapes) == 2 * one
    # 2.95 ms at the chip's 819 GB/s: about 0.04 % of a 7.45 s kernel.
    assert 2 * one / 819e9 == pytest.approx(2.95e-3, rel=2e-3)
    # The confirms' 641 real steps read three [N,R] f32 tables, pod room
    # and one label and one taint column: 48 bytes a node a step.
    assert cell.generator.exact_scan_bytes(
        steps=641, nodes=98304, resources=3, label_cols=1,
        taint_cols=1) == 641 * 48 * 98304


def test_preflight_judges_the_cell_by_its_programs_part(capsys):
    from benchmark import preflight
    assert preflight.main(["--no-compile", "--workload", CELL]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith(CELL))
    assert "client's buffers reckoned 4.8 MB" in line
    assert "program's temporaries reckoned 7.88 GiB" in line
    assert "UNDER" not in line


def test_preflight_compiles_the_prescreen_for_the_chip(capsys):
    """The program's part as the TPU compiler gives it for a described
    v5e: 6.4-6.5 GiB, which is what the chip reserved (PR 34)."""
    from benchmark import preflight
    if preflight.described_chip() is None:
        pytest.skip("no v5e:2x2 topology can be described here")
    capsys.readouterr()
    assert preflight.main(["--workload", CELL]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith(CELL))
    assert "program compiled for v5e reserves 6.47 GiB" in line
    assert "temporaries summed 7.69 GiB" in line


def test_the_six_metric_files_name_readers_that_exist(cell):
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
    # The entries with no ``workloads`` key are the new cell's too, and
    # the exact scan's two, which the solver's confirms run; the first
    # cell's dispatch and topology spans are not.
    assert {"snapshot_ms", "session_open_ms", "allocate_host_ms",
            "device_calls", "device_idle", "statement_ms", "upload_bytes",
            "allocate_jobs_kernel_ms",
            "allocate_jobs_kernel_roofline"} <= set(by_name)
    assert not {"dispatch_ms", "topology_ms"} & set(by_name)
    first = spec.Cell(bench, "tas65k-pytorchjob-16k", ROOT)
    assert not set(NEW_METRICS) & {m["name"] for m in first.per_layer}


@pytest.fixture(scope="module")
def cut_root(tmp_path_factory):
    """A benchmark root whose one cell is the real cell's files with the
    fleet cut to 64 nodes: the generator, the reference and the metric
    files are the real ones, found in ``benchmark/``."""
    from control_reclaim import cut_cell
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
    assert (line["generator"], line["reference"]) == ("reclaim_gangs",
                                                      "eviction")
    # One reclaim and one bind in every cycle of the window.
    assert line["evictions_per_cycle"] == [24]
    assert line["binds_per_cycle"] == [24]
    assert line["prescreens_per_cycle"] == [1]
    assert line["bind_cycles_after_arrival"] == [1]
    assert out["failed"] == 0 and out["attempted"] >= 1
    # Prime compiled the four programs of the cycle; the warm cycles and
    # the window compiled none of them.
    assert set(line["primed"]["kernels"]) == {
        "batch_prefix_feasibility", "_allocate_groups_packed",
        "allocate_jobs_kernel[32,4]", "allocate_jobs_kernel[64,8]"}
    warm = {name for c in line["warm_cycles"] for name in c["compiled"]}
    assert not warm & {"jit(batch_prefix_feasibility)",
                       "jit(_allocate_groups_packed)",
                       "jit(allocate_jobs_kernel)"}
    assert line["window_compiles"] == 0
    # The new metrics that need no chip, and the key-less ones.
    metrics = out["metrics"]
    assert metrics["scenario_prescreen_ms"]["value"] > 0
    assert metrics["reclaim_host_ms"]["value"] > 0
    assert metrics["prescreen_operands_ms"]["value"] > 0
    assert metrics["scenarios_skipped"] == {"value": 10.0,
                                            "unit": "scenarios/cycle"}
    assert metrics["device_calls"]["value"] == 5.0
    assert "scenario_prescreen_roofline" not in metrics   # no chip
    assert {"snapshot_ms", "session_open_ms", "allocate_host_ms",
            "operands_ms", "statement_ms", "stage_ms", "device_wait_ms",
            "upload_bytes", "convert_bytes", "download_bytes"} <= set(
        metrics)
    # statement_ms counts the reclaim's commit beside the allocate's.
    assert "topology_ms" not in metrics or metrics["topology_ms"]


@pytest.mark.parametrize("cut", (SMALL, SHARED), ids=("64n", "256n"))
@pytest.mark.parametrize("kind, number", (
    ("one_more", "evictions_beyond_need"),
    ("own_queue", "victims_from_own_queue"),
    ("evict_all", "evictions_beyond_need")))
def test_a_control_comes_out_not_correct(kind, number, cut):
    from control_reclaim import run_control
    out = run_control(CELL, 7, kind, cut=cut)
    assert not out["correct"]
    assert out["compared"][number][0] > 0
    if kind == "one_more":
        # One job of four pods more in each of the four cycles, and
        # nothing else at fault.
        assert {k for k, v in out["compared"].items() if v[0]} == {number}
        assert out["compared"][number][0] == 16
    if kind == "evict_all" and cut["share"] == 1.0:
        assert out["compared"]["victim_queue_below_quota"][0] >= 1


@pytest.mark.parametrize("cut", (SMALL, SHARED), ids=("64n", "256n"))
def test_the_plain_reclaimer_with_nothing_dropped_is_correct(cut):
    from control_reclaim import run_control
    out = run_control(CELL, 7, "sound", cut=cut)
    assert out["correct"], out["compared"]


def test_the_quota_rule_of_the_reference(cell):
    """``victim_queue_below_quota`` on numbers made by hand: a queue over
    its share may lose pods down to the line, one under it none; and
    nothing is taken for a reclaimer that stands over its own."""
    ref = cell.reference
    deserved = {"a": np.array([100.0, 100.0, 8.0]),
                "b": np.array([100.0, 100.0, 8.0])}
    pod = np.array([4.0, 4.0, 1.0])
    used = {"a": np.array([40.0, 40.0, 10.0]), "b": np.zeros(3)}
    asks = 2 * pod
    assert ref.victim_queue_below_quota(
        deserved, used, {"a": [pod, pod]}, "b", asks) == 0
    # Three pods take "a" from 10 GPUs to 7: the third was taken from a
    # queue that stood at its share of every resource.
    assert ref.victim_queue_below_quota(
        deserved, used, {"a": [pod, pod, pod]}, "b", asks) == 1
    # The reclaimer's queue already holds its 8 GPUs.
    full = {**used, "b": np.array([32.0, 32.0, 8.0])}
    assert ref.victim_queue_below_quota(
        deserved, full, {"a": [pod]}, "b", pod) == 1
    assert ref.victim_queue_below_quota(deserved, full, {}, "b", pod) == 0
    share = ref.deserved_share(np.array([64.0, 64.0, 16.0]), 2, 2, leaf=True)
    assert share.tolist() == [16.0, 16.0, 4.0]


def test_the_fewest_evictions_of_the_reference(cell):
    ref = cell.reference
    capacity = np.tile([64.0, 512.0, 8.0], (4, 1))
    used = capacity.copy()
    used[1] = [56.0, 448.0, 6.0]         # two GPUs idle on one node
    pods = np.array([8, 6, 8, 8])
    gang = np.tile([4.0, 32.0, 1.0], (6, 1))
    victims = np.tile([4.0, 32.0, 1.0], (5, 1))
    assert ref.pods_that_fit(capacity, used, pods, 110, gang).sum() == 2
    assert ref.fewest_evictions(capacity, used, pods, 110, gang,
                                victims) == 4
    # Pod room binds too.
    assert ref.pods_that_fit(capacity, used, pods, 7, gang).sum() == 1
