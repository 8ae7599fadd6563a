"""The per-layer metrics that read the collector's span and counters
(PR 40), loaded through ``spec.Cell`` from the real ``BENCHMARK.json`` and
read on a cycle of the tiny fixture in which one full collection is forced
under ``propose:operands``.  The parent's view of that cycle is the same
record without the ``gc:full`` span and without the counters."""

import gc

import pytest

from conftest import DATA

from benchmark.harness import readers, spec

GC_METRICS = ("gc_full_ms", "gc_full_collections", "gc_full_pause_s",
              "gc_young_pause_s", "gc_middle_pause_s")
NET_OF = {"operands_net_ms": "operands_ms",
          "statement_net_ms": "statement_ms"}
NEW = GC_METRICS + tuple(NET_OF)


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def real_cell(bench):
    return spec.Cell(bench, bench["workloads"][0]["name"])


@pytest.fixture(scope="module")
def cycle(real_cell):
    """The record of the second cycle of ``tiny-tas-gang``, with one full
    collection forced where the pod walk builds the task rows and no
    other collection of that generation."""
    from kai_scheduler_tpu.framework import propose
    tiny = spec.Cell(spec.load_benchmark(DATA), "tiny-tas-gang", DATA)
    client = tiny.generator.build(
        tiny, 3000000019,
        counters=readers.counters_wanted(real_cell.per_layer))
    client.cycle()
    task_operands = propose.task_operands

    def collecting(*args, **kwargs):
        gc.collect()
        return task_operands(*args, **kwargs)

    gc.collect()
    gc.disable()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(propose, "task_operands", collecting)
        try:
            return client.cycle()
        finally:
            gc.enable()


def read(real_cell, rec):
    out = readers.read_all(real_cell.per_layer, {"records": [rec]})
    return {name: m["value"] for name, m in out.items()}


def test_each_new_file_says_what_its_entry_says(bench, real_cell):
    entries = {m["name"]: m for m in bench["per_layer"]}
    files = {m["name"]: m for m in real_cell.per_layer}
    cells = [w["name"] for w in bench["workloads"]]
    # Appended, in the issue's order, after everything that was there.
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    for name in NEW:
        entry, doc = entries[name], files[name]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert doc[key] == entry[key], (name, key)
        assert entry["workloads"] == cells
        assert entry["layer"] == "session and actions"
        assert entry["moves"] == "cycle_ms" and entry["better"] == "lower"
        assert doc["reader"]["kind"] in ("span_sum", "span_self",
                                         "counter_delta")
        assert 1 <= len(entry["unit"]) <= 16


def test_the_net_metrics_differ_from_the_old_ones_in_their_minus_alone(
        real_cell):
    files = {m["name"]: m for m in real_cell.per_layer}
    for net, gross in NET_OF.items():
        match = files[gross]["reader"]["match"]
        assert files[net]["reader"]["match"] == match
        assert files[net]["reader"]["minus"] == \
            files[gross]["reader"].get("minus", []) + ["gc:*"]
        assert "gc:*" not in files[gross]["reader"].get("minus", [])


def test_a_forced_full_collection_is_read_by_every_new_metric(real_cell,
                                                              cycle):
    ms = read(real_cell, cycle)
    assert set(NEW) <= set(ms)
    by_id = {sid: (name, parent) for name, _k, sid, parent, _s, _d
             in cycle.spans}
    (full,) = [s for s in cycle.spans if s[0] == "gc:full"]
    assert full[1] == "gc" and by_id[full[3]][0] == "propose:operands"
    assert ms["gc_full_ms"] == pytest.approx(1e3 * full[5]) and full[5] > 0
    assert ms["gc_full_collections"] >= 1
    # The counter holds the client's collections too (one, before the
    # cycle); the span is the cycle's alone.
    assert ms["gc_full_pause_s"] >= full[5]
    assert ms["gc_young_pause_s"] >= 0 and ms["gc_middle_pause_s"] >= 0
    assert ms["operands_ms"] - ms["operands_net_ms"] == pytest.approx(
        1e3 * full[5], abs=1e-6)
    assert 0 < ms["operands_net_ms"] < ms["operands_ms"]
    # Nothing fell under a statement span: net and gross agree.
    assert ms["statement_net_ms"] == pytest.approx(ms["statement_ms"])


def test_a_collection_under_a_statement_span_comes_off_its_net(real_cell):
    class Rec:
        counters = {}
        spans = [("statement:apply", "allocate", 1, None, 0.0, 0.5),
                 ("statement:commit", "allocate", 2, None, 0.5, 0.25),
                 ("seam:stage", "seam", 3, 2, 0.5, 0.2),
                 ("gc:full", "gc", 4, 3, 0.55, 0.125)]

    ms = read(real_cell, Rec)
    assert ms["statement_ms"] == pytest.approx(750.0)
    assert ms["statement_net_ms"] == pytest.approx(625.0)
    assert ms["gc_full_ms"] == pytest.approx(125.0)


def test_a_program_without_the_collector_reports_no_gc_metric(real_cell,
                                                              cycle):
    """On the parent no span is called ``gc:full`` and no counter
    ``gc_*``: the five are left out of the line, not printed as 0, and
    the two net metrics read what the old ones read."""
    class Parent:
        counters = {c: v for c, v in cycle.counters.items()
                    if not c.startswith("gc_")}
        spans = [s for s in cycle.spans if s[0] != "gc:full"]

    assert len(Parent.spans) == len(cycle.spans) - 1
    # Four counters behind the five: gc_full_ms reads the span.
    assert len(Parent.counters) == len(cycle.counters) - 4
    ms = read(real_cell, Parent)
    assert not set(GC_METRICS) & set(ms)
    for net, gross in NET_OF.items():
        assert ms[net] == ms[gross] > 0
