"""``affinity_pod_walks`` (PR 41): the per-layer metric that reads the
pod-affinity gate's counter, loaded through ``spec.Cell`` from the real
``BENCHMARK.json`` and read on a cycle of the tiny fixture.  No pod of the
fixture carries an inter-pod term, as no pod of any cell does: the gate
lists no pod and the metric reads 0, which is not the same as the parent's
line, where no such counter exists and the metric is left out."""

import pytest

from conftest import DATA

from benchmark.harness import readers, spec

NAME = "affinity_pod_walks"
COUNTER = "podaffinity_pod_walks_total"


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def real_cell(bench):
    return spec.Cell(bench, bench["workloads"][0]["name"])


@pytest.fixture(scope="module")
def cycle(real_cell):
    tiny = spec.Cell(spec.load_benchmark(DATA), "tiny-tas-gang", DATA)
    client = tiny.generator.build(
        tiny, 3000000041,
        counters=readers.counters_wanted(real_cell.per_layer))
    client.cycle()
    return client.cycle()


def read(real_cell, rec):
    out = readers.read_all(real_cell.per_layer, {"records": [rec]})
    return {name: m["value"] for name, m in out.items()}


def test_the_file_says_what_its_entry_says(bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    for cell in entry["workloads"]:
        (doc,) = [m for m in spec.Cell(bench, cell).per_layer
                  if m["name"] == NAME]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert doc[key] == entry[key], key
        assert doc["reader"] == {"kind": "counter_delta", "counter": COUNTER}
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "session and actions"
    assert entry["moves"] == "cycle_ms" and entry["better"] == "lower"


def test_a_fleet_without_terms_reads_zero_walks(real_cell, cycle):
    assert COUNTER in readers.counters_wanted(real_cell.per_layer)
    assert cycle.counters[COUNTER] == 0
    assert read(real_cell, cycle)[NAME] == 0


def test_walks_are_counted_a_cycle(real_cell):
    class Rec:
        counters = {COUNTER: 4.0}
        spans = []

    assert read(real_cell, Rec)[NAME] == 4.0


def test_a_program_without_the_counter_leaves_the_metric_out(real_cell,
                                                             cycle):
    class Parent:
        counters = {c: v for c, v in cycle.counters.items() if c != COUNTER}
        spans = cycle.spans

    assert NAME not in read(real_cell, Parent)
