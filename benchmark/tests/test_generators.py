"""The seam between ``run.py`` and a cell's files: the generator, the
reference and the scheduler's settings are found by the names the files
give, a name that no file has stops the run, and a second generator that
is no copy of the first (``data/tiny/generators/reclaim_gangs.py``, cross-
queue reclaim at 64 nodes) runs through the same ``run_cell``."""

import json
import os

import pytest

from conftest import DATA, ROOT

from benchmark.harness import loop, readers, spec

RECLAIM = "tiny-reclaim-gang"
# The keys ``run`` had before the cell's files named a generator, in order.
RUN_KEYS = ["wall_s", "cycles_in_window", "cycle_s", "compile_cache",
            "primed", "warm_cycles", "window_compiles",
            "guard_moved_in_warm", "guard_moved", "gangs", "gang_roles",
            "placements_checked"]


def run_cell(workload, seed, trace=False, seconds=0.5, root=DATA):
    from benchmark import run
    return run.run_cell(workload, seed, seconds, trace, require_chip=False,
                        root=root)


@pytest.fixture
def broken_root(tmp_path):
    """``write(configs={...}, traffic={...})`` makes a benchmark root whose
    one cell names a traffic file and a configuration of its own, the
    fixtures' with those keys changed, and returns its path."""
    bench = spec.load_benchmark(DATA)
    bench["paths"] = ["own", os.path.relpath(os.path.join(DATA, "tiny"),
                                             tmp_path),
                      os.path.relpath(os.path.join(ROOT, "benchmark"),
                                      tmp_path)]
    bench["workloads"] = [{"name": "own-cell", "config": "own",
                           "traffic": "own", "chips": 1, "why": "fixture"}]
    tiny = os.path.join(DATA, "tiny")
    docs = {"configs": spec.load_json(
                os.path.join(tiny, "configs", "tiny-tas.json")),
            "traffic": spec.load_json(
                os.path.join(tiny, "traffic", "pytorchjob-256-superpod.json"))}

    def write(**changes):
        for kind, doc in docs.items():
            doc = {**doc, **changes.get(kind, {})}
            path = tmp_path / "own" / kind / "own.json"
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(doc))
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        return str(tmp_path)
    return write


def test_a_cell_of_files_alone_runs(broken_root):
    out = run_cell("own-cell", 3, root=broken_root())
    assert out["correct"] and out["run"]["generator"] == "closed_loop_gangs"


@pytest.mark.parametrize("kind, change, named, told", (
    ("traffic", {"generator": "no_such_generator"}, "traffic/own.json",
     "no_such_generator"),
    ("configs", {"reference": "no_such_reference"}, "configs/own.json",
     "no_such_reference"),
    ("configs", {"scheduler": {"max_victims_considerd": 64}},
     "configs/own.json", "max_victims_considerd"),
    ("configs", {"scheduler": "default SchedulerConfig"},
     "configs/own.json", "has to be an object"),
))
def test_a_name_that_nothing_has_stops_the_run(broken_root, monkeypatch,
                                               kind, change, named, told):
    """A generator or a reference that no file has, a ``scheduler`` key
    that ``apply_dict`` never asks for, a ``scheduler`` that is a
    sentence: the run stops before any fleet is built, and the message
    names the file at fault."""
    from benchmark.harness import cluster
    root = broken_root(**{kind: change})
    monkeypatch.setattr(cluster, "build_fleet", lambda *a: pytest.fail(
        "the fleet was built"))
    with pytest.raises(SystemExit) as stop:
        run_cell("own-cell", 3, root=root)
    assert named in str(stop.value) and told in str(stop.value)


def test_every_setting_apply_dict_reads_is_known():
    """The settings are whatever ``apply_dict`` asks the document for:
    the reclaim deployment's three, a plugin tier, a feature gate."""
    settings = {"max_victims_considered": 64, "scenario_prescreen_max": 32,
                "scenario_prescreen_after": 2, "k_value": 0.5,
                "actions": "allocate, reclaim",
                "tiers": [{"plugins": ["predicates", "proportion"]}],
                "queue_depth_per_action": {"reclaim": 4},
                "feature_gates": {"SomeGate": True}}
    conf = loop.scheduler_config({"scheduler": settings})
    assert conf.max_victims_considered == 64
    assert conf.scenario_prescreen_max == 32 and conf.k_value == 0.5
    assert conf.actions == ["allocate", "reclaim"]
    assert [p.name for p in conf.plugins] == ["predicates", "proportion"]
    assert conf.queue_depth_per_action == {"reclaim": 4}
    default = loop.scheduler_config({"scheduler": {}})
    assert default.max_victims_considered == 32
    assert default.scenario_prescreen_max == 256


def test_the_result_line_has_the_keys_and_values_it_had():
    """``tiny-tas-gang`` before and after the generator became a file: the
    line's keys in their order, what does not depend on the clock, and
    every key ``run`` had (it gained the names the cell's files give)."""
    out = run_cell("tiny-tas-gang", 3000000019)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "run", "compared"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"] == {k: [0, 0] for k in (
        "gangs_not_bound", "gangs_partly_bound", "foreign_binds",
        "nodes_over_capacity", "queues_over_limit",
        "gangs_refused_by_reference", "pods_outside_domain",
        "placements_not_reference")}
    assert list(out["compared"]) == list(
        spec.Cell(spec.load_benchmark(DATA), "tiny-tas-gang",
                  DATA).generator.LIMITS)
    assert {k: v["unit"] for k, v in out["metrics"].items()} == {
        "cycle_ms": "ms", "pods_bound_per_s": "pods/s", "setup_s": "s"}
    cycles = out["attempted"]
    run = out["run"]
    assert [k for k in run if k in RUN_KEYS] == RUN_KEYS
    assert run["cycles_in_window"] == run["gangs"] == cycles
    assert len(run["cycle_s"]) == cycles
    assert run["gang_roles"] == [{"name": "master", "count": 1},
                                 {"name": "worker", "count": 255}]
    assert run["placements_checked"] == 256 * cycles
    assert out["metrics"]["pods_bound_per_s"]["value"] == pytest.approx(
        256 * 1e3 / out["metrics"]["cycle_ms"]["value"])
    assert {k: run["primed"][k] for k in (
        "t_pad", "nodes", "resources", "label_cols", "taint_cols")} == {
        "t_pad": 256, "nodes": 1024, "resources": 3, "label_cols": 1,
        "taint_cols": 1}
    assert (run["generator"], run["reference"], run["scheduler"]) == (
        "closed_loop_gangs", "placement", {})


@pytest.mark.parametrize("workload", ("tiny-tas-gang", "tiny-plain-gang",
                                      RECLAIM))
def test_the_warm_cycle_compiles_nothing_prime_compiled(workload,
                                                        monkeypatch):
    """With every in-process cache dropped, ``prime`` compiles its kernel
    in the variant the cycle dispatches, so that the warm cycles do not;
    without ``prime`` the first warm cycle does."""
    import jax
    cell = spec.Cell(spec.load_benchmark(DATA), workload, DATA)
    jax.clear_caches()
    run = run_cell(workload, 21)["run"]
    kernel = f"jit({run['primed']['kernel']})"
    warm = [name for c in run["warm_cycles"] for name in c["compiled"]]
    assert kernel not in warm and run["window_compiles"] == 0
    if workload == "tiny-tas-gang":
        assert run["primed"]["operands"] == "job rows [2,N]"

    def no_prime(client, watch):
        client.primed = {"t": 0, **cell.generator.file_shape(cell)}
        return {"kernel": run["primed"]["kernel"]}
    monkeypatch.setattr(cell.generator, "prime", no_prime)
    jax.clear_caches()
    unprimed = run_cell(workload, 21)["run"]
    assert kernel in unprimed["warm_cycles"][0]["compiled"]


def test_a_counter_the_program_lacks_is_left_out():
    cell = spec.Cell(spec.load_benchmark(DATA), "tiny-tas-gang", DATA)
    client = cell.generator.build(
        cell, 4, counters=("device_kernel_calls", "no_such_counter"))
    rec = client.cycle()
    assert rec.counters == {"device_kernel_calls": 1.0}
    metrics = [{"name": n, "unit": "calls/cycle",
                "reader": {"kind": "counter_delta", "counter": c}}
               for n, c in (("device_calls", "device_kernel_calls"),
                            ("absent_calls", "no_such_counter"))]
    assert set(readers.read_all(metrics, {"records": [rec]})) == {
        "device_calls"}


# -- the second generator ----------------------------------------------------
@pytest.mark.parametrize("seed", (7, 3000000019))
def test_reclaim_runs_through_the_same_run_cell(seed):
    out = run_cell(RECLAIM, seed, trace=True)
    assert out["correct"], out["compared"]
    run = out["run"]
    assert (run["generator"], run["reference"]) == ("reclaim_gangs",
                                                    "eviction")
    assert run["scheduler"] == {"max_victims_considered": 64,
                                "scenario_prescreen_max": 64,
                                "scenario_prescreen_after": 1}
    # Evictions were read back, 24 one-GPU victims a gang, and every gang
    # bound in the cycle after the one it arrived in.
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert run["evictions"] >= 24 * out["attempted"]
    assert run["bind_cycles_after_arrival"] == [1]
    # Its own numbers, none of the first generator's.
    assert "victims_from_own_queue" in out["compared"]
    assert "placements_not_reference" not in out["compared"]
    # The prescreen kernel was dispatched, and its span read by a metric
    # that is a file beside the generator.
    assert out["metrics"]["scenario_prescreen_ms"]["value"] > 0
    assert "scenario_prescreen_roofline" not in out["metrics"]   # no chip
    assert out["metrics"]["device_calls"]["value"] > 1


def test_reclaim_uses_nothing_of_the_first_generators_comparison():
    import ast
    cell = spec.Cell(spec.load_benchmark(DATA), RECLAIM, DATA)
    gen = cell.generator
    assert gen.__file__.startswith(os.path.join(DATA, "tiny", "generators"))
    tree = ast.parse(open(gen.__file__).read())
    imported = {a.name for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert not any("closed_loop_gangs" in name or "placement" in name
                   for name in imported if name)
    assert cell.reference.__name__ == "benchmark_reference_eviction"


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_reclaims_control_leaves_nodes_over_capacity(seed):
    """The control binds the gang where it arrives, on nodes that are
    full, and evicts nobody: the guarantee it drops is the node's
    capacity."""
    from benchmark.harness import cluster as fleet
    cell = spec.Cell(spec.load_benchmark(DATA), RECLAIM, DATA)
    client = cell.generator.build(cell, seed)

    def control_cycle():
        if client.gang is not None:
            gang = client.gang[0]
            client.sched.cache.bound.extend(
                (name, fleet.node_name(i // 8))
                for i, name in enumerate(gang.names))
    client.sched.run_once = control_cycle
    for _ in range(4):
        client.cycle()
    out = cell.generator.compare(client.records, client.ledger, cell)
    assert not out["correct"]
    assert out["compared"]["nodes_over_capacity"][0] >= 3
    assert out["compared"]["victims_from_own_queue"][0] == 0


def test_a_victim_of_the_reclaimers_own_queue_is_not_correct():
    """One eviction altered where the client reads it: the victim's queue,
    as the book has it, made the reclaimer's own."""
    cell = spec.Cell(spec.load_benchmark(DATA), RECLAIM, DATA)
    client = cell.generator.build(cell, 5)
    for _ in range(2):
        client.cycle()
    rec = next(r for r in client.records if r.evicted)
    rec.evicted[0].queue = rec.pending.queue
    rec.running_before[rec.evicted[-1].job] += 1   # one pod left behind
    out = cell.generator.compare(client.records, client.ledger, cell)
    assert not out["correct"]
    assert out["compared"]["victims_from_own_queue"][0] == 1
    assert out["compared"]["victim_gangs_below_minimum"][0] == 1


def test_the_roofline_reader_finds_the_generators_own_byte_count():
    cell = spec.Cell(spec.load_benchmark(DATA), RECLAIM, DATA)
    metric = next(m for m in cell.per_layer
                  if m["name"] == "scenario_prescreen_roofline")
    shape = {"prefixes": 64, "nodes": 64, "resources": 3}
    run = {"reduced": {"programs": {"jit_batch_prefix_feasibility(7)": 1e-6},
                       "busy_s": 1e-6, "window_s": 1e-3},
           "traced_cycles": 1, "device_kind": "TPU v5 lite",
           "generator": cell.generator,
           "kernel_shapes": {"prefix_feasibility_bytes": shape}}
    need = 2.0 * 64 * 64 * 3 * 4
    assert readers.read_all([metric], run) == {
        "scenario_prescreen_roofline": {
            "value": pytest.approx(100.0 * need / 819e9 / 1e-6),
            "unit": "%"}}
    # A cell whose generator gives no shape for the model reports nothing.
    run["kernel_shapes"] = {}
    assert readers.read_all([metric], run) == {}


def test_preflight_prints_what_the_generator_reckons(capsys):
    from benchmark import preflight
    assert preflight.main(["--no-compile"]) == 0
    out = capsys.readouterr().out
    assert "[2,N] score and mask rows" in out and "reckoned 4.2 MB" in out
    assert "an admitted cell is not held to" in out
    assert preflight.main(["--no-compile", "--root", DATA]) == 0
    capsys.readouterr()
    assert preflight.main(["--no-compile", "--root", DATA,
                           "--workload", RECLAIM]) == 1
    out = capsys.readouterr().out
    assert "[K=64, N=64, R=3] f32 = 49,152 bytes an array x 7" in out
    assert out.count("UNDER THE 4.00 GiB FLOOR") == 1
