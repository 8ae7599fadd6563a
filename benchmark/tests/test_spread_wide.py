"""The cell ``spread98k-pytorchjob-256`` as files: its shapes and bytes
from the files alone, what its entries add to ``BENCHMARK.json``, its
generator through the same ``run_cell`` at a width the CPU holds, the
reference's spread order on numbers made by hand, and the controls of its
reference (``control_spread.py``), each of which has to come out not
correct by its own count alone."""

import json
import os

import numpy as np
import pytest

from conftest import ROOT

from benchmark.harness import readers, spec

CELL = "spread98k-pytorchjob-256"
CONFIG = "spread-98k"
# The accepted metrics that read what the cell runs, and list it.
LISTED = (
    "allocate_jobs_kernel_ms", "allocate_jobs_kernel_roofline",
    "scenario_prescreen_ms", "scenario_prescreen_kernel_ms",
    "scenario_prescreen_roofline", "reclaim_host_ms",
    "prescreen_operands_ms", "scenarios_skipped", "prescreen_counted",
    "prescreen_scan_steps", "gc_full_collections", "gc_full_pause_s",
    "gc_young_pause_s", "gc_middle_pause_s", "operands_net_ms",
    "statement_net_ms", "affinity_pod_walks")
DECLINED = 'batched_form_declined_total{form="prescreen_runs",' \
    'reason="strategy"}'
SMALL = dict(nodes=64, share=1.0, departments=2, leaves=2, whole=4,
             gang=24, victims=32)
SHARED = dict(nodes=256, share=0.25, departments=4, leaves=4, whole=8,
              gang=32, victims=64)


@pytest.fixture(scope="module")
def cell():
    return spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)


def test_the_files_give_the_cycles_shapes(cell):
    shape = cell.generator.file_shape(cell)
    assert (shape["prefixes"], shape["rows"], shape["t"], shape["t_pad"],
            shape["nodes"], shape["resources"]) == (1024, 2048, 256, 256,
                                                    98304, 3)
    # The confirms are the reclaim cell's; the exact scan's real steps add
    # the bind of last cycle's gang.
    assert shape["confirms"] == [[512, 4], [512, 128]]
    assert shape["scan_steps"] == 257 + 384 + 256
    assert cell.chips == 1 and cell.entry["config"] == CONFIG
    assert cell.generator.__file__ == os.path.join(
        ROOT, "benchmark", "generators", "spread_reclaim_gangs.py")
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "spread_eviction.py")
    # The client is the reclaim cell's own file's, not a copy of it.
    other = spec.Cell(spec.load_benchmark(ROOT), "ns98k-reclaim-wide", ROOT)
    assert cell.generator.base.__file__ == other.generator.__file__
    assert issubclass(cell.generator.Client, cell.generator.base.Client)
    roles = cell.traffic["gang"]["roles"]
    assert [(r["name"], r["count"]) for r in roles] == [("master", 1),
                                                        ("worker", 255)]


@pytest.mark.parametrize("seed", (1, 2, 3, 4242300014, 4242300015))
def test_every_seed_gives_the_departments_the_same_sums(seed):
    """The two leaf queues that stay empty beside the reclaimer's lie in
    neither the occupier's nor the reclaimer's department (seed 4242300014
    would leave two of the occupier's siblings empty, 4242300015 one of
    each department's): at the cell's own queue tree, on 256 nodes."""
    from control_spread import cut_cell
    cut = cut_cell(spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT),
                   **SHARED)
    client = cut.generator.Client(cut, seed)
    ledger = client.ledger
    parent = ledger.queue_parent
    holds = {q: ledger.queue_used[q][2] for q, p in parent.items() if p}
    for queue in (client.occupier, client.reclaimer):
        siblings = [q for q in holds if parent[q] == parent[queue]
                    and q != queue]
        assert [holds[q] for q in siblings] == [16 * 8.0] * 3
    assert holds[client.reclaimer] == 0
    assert sorted(holds.values()).count(0.0) == 3
    assert sum(v == 16 * 8.0 for v in holds.values()) == 12
    client.close()


def test_the_strategies_come_from_the_operators_settings(cell):
    from kai_scheduler_tpu.ops.scoring import BINPACK, SPREAD
    assert cell.generator.strategies(cell) == {"gpu_strategy": SPREAD,
                                               "cpu_strategy": SPREAD}
    other = spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)
    other.config = {**other.config, "scheduler": {
        **other.config["scheduler"], "cpu_placement_strategy": "binpack"}}
    assert cell.generator.strategies(other) == {"gpu_strategy": SPREAD,
                                                "cpu_strategy": BINPACK}


def test_the_configuration_is_north_stars_fleet_under_spread(cell):
    config = cell.config
    bench = spec.load_benchmark(ROOT)
    entry = bench["configs"][-1]
    assert entry["name"] == CONFIG
    assert entry["reduced"] == config["reduced"] == ["backlog", "occupancy"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell.entry["why"]) <= 200
    ns = spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                     "north-star-98k.json"))
    for key in ("nodes", "topologies", "queues"):
        assert config[key] == ns[key]
    assert {k: v for k, v in config["occupancy"].items() if k != "why"} \
        == {k: v for k, v in ns["occupancy"].items() if k != "why"}
    assert config["scheduler"] == {
        **ns["scheduler"], "gpu_placement_strategy": "spread",
        "cpu_placement_strategy": "spread"}
    # North star's guarantees word for word, and where the gang lands.
    assert config["guarantees"][:-1] == ns["guarantees"]
    assert "largest free share" in config["guarantees"][-1]
    assert config["reference"] == "spread_eviction"


def test_the_entries_are_appended_and_nothing_else_moved():
    bench = spec.load_benchmark(ROOT)
    assert bench["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED:
        assert by_name[name]["workloads"][-1] == CELL
    # gc_full_ms is not among them: a window of five cycles holds a
    # gc:full span or does not.
    assert CELL not in by_name["gc_full_ms"]["workloads"]
    last = bench["per_layer"][-1]
    assert last["name"] == "strategy_declines"
    assert last["workloads"] == [w["name"] for w in bench["workloads"]]
    doc = spec.load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                      "strategy_declines.json"))
    assert {k: doc[k] for k in ("unit", "better", "source", "layer",
                                "moves")} == {
        k: last[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert doc["reader"] == {"kind": "counter_delta", "counter": DECLINED}
    assert doc["reader"]["kind"] in readers.KINDS


def test_the_counter_the_metric_reads_is_the_programs():
    from kai_scheduler_tpu.framework import propose
    from kai_scheduler_tpu.utils.metrics import _key
    assert ("prescreen_runs", "strategy") in propose.DECLINES
    assert _key("batched_form_declined_total",
                {"form": "prescreen_runs", "reason": "strategy"}) == DECLINED


def test_reckon_and_the_least_bytes_do_not_depend_on_the_form(cell):
    reck = cell.generator.reckon(cell)
    one = 1024 * 98304 * 3 * 4
    assert reck["program_bytes"] == 7 * one
    assert reck["bytes"] == 4_765_696
    shapes = {"prefixes": 1024, "nodes": 98304, "resources": 3}
    # One pool written and read once, as the reclaim cell's: a spread form
    # that answers a run in one pass moves no less.
    assert cell.generator.prefix_feasibility_bytes(**shapes) == 2 * one
    assert cell.generator.exact_scan_bytes(
        steps=897, nodes=98304, resources=3, label_cols=1,
        taint_cols=1) == 897 * 48 * 98304


def test_preflight_judges_the_cell_by_its_programs_part(capsys):
    from benchmark import preflight
    assert preflight.main(["--no-compile", "--workload", CELL]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith(CELL))
    assert "program's temporaries reckoned 7.88 GiB" in line
    assert "UNDER" not in line


def test_preflight_compiles_the_spread_prescreen_for_the_chip(capsys):
    """Under spread the program holds the vmapped exact scan, and the TPU
    compiler reserves for a described v5e what the chip reserved for it
    while bin-pack still took that branch (PR 35 to PR 37)."""
    from benchmark import preflight
    if preflight.described_chip() is None:
        pytest.skip("no v5e:2x2 topology can be described here")
    capsys.readouterr()
    assert preflight.main(["--workload", CELL]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith(CELL))
    assert "program compiled for v5e reserves 6.47 GiB" in line


@pytest.fixture(scope="module")
def cut_root(tmp_path_factory):
    """A benchmark root whose one cell is the real cell's files with the
    fleet cut to 64 nodes: the generator, the reference and the metric
    files are the real ones, found in ``benchmark/``."""
    from control_spread import cut_cell
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


def test_the_generator_runs_through_run_cell(cut_root, cell):
    import jax

    from benchmark import run
    jax.clear_caches()
    out = run.run_cell(CELL, 3000000019, 0.5, True, require_chip=False,
                       root=cut_root)
    assert out["correct"], out["compared"]
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 12
    assert out["compared"]["placements_not_reference"] == [0, 0]
    line = out["run"]
    assert (line["generator"], line["reference"]) == (
        "spread_reclaim_gangs", "spread_eviction")
    assert line["evictions_per_cycle"] == [24]
    assert line["binds_per_cycle"] == [24]
    assert line["prescreens_per_cycle"] == [1]
    assert line["bind_cycles_after_arrival"] == [1]
    assert line["placements_checked"] == 24 * line["cycles_in_window"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    # Prime compiled the spread programs of the cycle, the grouped fill
    # not among them; the warm cycle and the window compiled none of them.
    assert set(line["primed"]["kernels"]) == {
        "batch_prefix_feasibility", "allocate_jobs_kernel[32,2] bind",
        "allocate_jobs_kernel[32,4]", "allocate_jobs_kernel[64,8]"}
    warm = {name for c in line["warm_cycles"] for name in c["compiled"]}
    assert not warm & {"jit(batch_prefix_feasibility)",
                       "jit(_allocate_groups_packed)",
                       "jit(allocate_jobs_kernel)"}
    assert line["window_compiles"] == 0
    metrics = out["metrics"]
    assert metrics["strategy_declines"] == {"value": 1.0,
                                            "unit": "calls/cycle"}
    assert metrics["prescreen_scan_steps"]["value"] == 32.0    # t_pad
    assert metrics["prescreen_counted"]["value"] == 0.0
    assert metrics["scenarios_skipped"]["value"] == 10.0
    assert metrics["device_calls"]["value"] == 5.0
    assert metrics["affinity_pod_walks"]["value"] == 0.0
    assert "gc_full_ms" not in metrics
    assert "scenario_prescreen_roofline" not in metrics   # no chip


def test_a_program_without_the_counter_leaves_the_metric_out(cell):
    """On the parent the family does not exist: ``run_once`` leaves the
    counter out of the record, the reader finds nothing and returns None,
    and the line has no ``strategy_declines``."""
    import types
    (metric,) = [m for m in cell.per_layer
                 if m["name"] == "strategy_declines"]
    assert readers.counters_wanted([metric]) == (DECLINED,)
    run = {"records": [types.SimpleNamespace(counters={}, spans=[])]}
    assert readers.read_all([metric], run) == {}
    run["records"][0].counters[DECLINED] = 0.0
    assert readers.read_all([metric], run) == {
        "strategy_declines": {"value": 0.0, "unit": "calls/cycle"}}


@pytest.mark.parametrize("cut", (SMALL, SHARED), ids=("64n", "256n"))
@pytest.mark.parametrize("kind", ("binpack", "stale", "one_more", "sound"))
def test_a_control_moves_its_own_count_alone(kind, cut):
    from control_spread import MOVES, as_said, run_control
    out = run_control(CELL, 7, kind, cut=cut)
    assert out["correct"] == (kind == "sound")
    assert as_said(out), out["compared"]
    if kind == "one_more":
        # One job of four pods more in each of the four cycles.
        assert out["compared"][MOVES[kind]][0] == 16


def test_the_spread_order_of_the_reference(cell):
    """``place_gang`` on numbers made by hand: the largest free share
    first, the first node by name among equals, each pod against the state
    the pods before it left; a pod that fits nowhere binds nothing."""
    ref = cell.reference
    capacity = np.tile([64000.0, 512.0 * 2 ** 30, 8.0], (4, 1))
    used = np.zeros_like(capacity)
    used[:, 2] = [8, 4, 0, 4]            # GPUs free: 0, 4, 8, 4
    pods = np.zeros(4, np.int64)
    worker = np.array([4000.0, 32.0 * 2 ** 30, 1.0])
    master = np.array([8000.0, 64.0 * 2 ** 30, 1.0])
    reqs = np.array([master] + [worker] * 9)
    want = ref.place_gang(capacity, used, pods, 110, reqs)
    # Node 2 leads until it is level with 1 and 3, then they take turns.
    assert want.tolist() == [2, 2, 2, 2, 1, 2, 3, 1, 2, 3]
    assert ref.placements_not_reference(capacity, used, pods, 110, reqs,
                                        want) == 0
    packed = np.array([1, 1, 1, 1, 2, 2, 2, 2, 2, 2])
    assert ref.placements_not_reference(capacity, used, pods, 110, reqs,
                                        packed) == 8
    # Seventeen pods for sixteen free GPUs: none binds.
    assert ref.place_gang(capacity, used, pods, 110,
                          np.array([worker] * 17)) is None
    # Pod room binds too.
    assert ref.place_gang(capacity, used, pods, 3,
                          np.array([worker] * 10)) is None


def test_the_fewest_evictions_count_what_stays_idle(cell):
    """The repair over ``reference/eviction.py``: a master that asks twice
    a victim's cpu, beside idle cpu on every node, needs no victim more."""
    ref = cell.reference
    capacity = np.tile([64000.0, 512.0, 8.0], (4, 1))
    used = np.tile([32000.0, 256.0, 8.0], (4, 1))       # no GPU idle
    pods = np.full(4, 8)
    worker, master = [4000.0, 32.0, 1.0], [8000.0, 64.0, 1.0]
    gang = np.array([master] + [worker] * 5)
    victims = np.tile(worker, (8, 1))
    assert ref.fewest_evictions(capacity, used, pods, 110, gang,
                                victims) == 6
    other = spec.Cell(spec.load_benchmark(ROOT), "ns98k-reclaim-wide", ROOT)
    assert other.reference.fewest_evictions(capacity, used, pods, 110, gang,
                                            victims) == 7
    # Where cpu does bind, it counts.
    used[:, 0] = 64000.0
    assert ref.fewest_evictions(capacity, used, pods, 110, gang,
                                victims) == 7
