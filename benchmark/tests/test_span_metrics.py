"""The per-layer metrics that read the program's own spans and counters
(PR 25), loaded through ``spec.Cell`` from the real ``BENCHMARK.json``
and read on a cycle of the tiny fixture, in the 32-bit regime the chip
runs.  The cycle is run once; the parent's view of it is the same cycle
without the spans this PR names."""

from fnmatch import fnmatchcase

import pytest

from conftest import DATA

from benchmark.harness import readers, spec

SPAN_METRICS = ("topology_ms", "operands_ms", "statement_ms", "stage_ms",
                "device_wait_ms")
COUNT_METRICS = ("upload_bytes", "convert_bytes", "download_bytes")
# Every span name the program gained with these metrics.
NEW_SPANS = ("allocate:*", "topology:*", "extra_scores:*", "propose:*",
             "seam:*", "statement:*")


@pytest.fixture(scope="module")
def real_cell():
    bench = spec.load_benchmark()
    return spec.Cell(bench, bench["workloads"][0]["name"])


@pytest.fixture(scope="module")
def cycle(real_cell):
    """The record of the second cycle of ``tiny-tas-gang``."""
    import jax
    assert not jax.config.jax_enable_x64
    tiny = spec.Cell(spec.load_benchmark(DATA), "tiny-tas-gang", DATA)
    client = tiny.generator.build(
        tiny, 3000000019,
        counters=readers.counters_wanted(real_cell.per_layer))
    client.cycle()
    return client.cycle()


def test_every_new_metric_is_in_the_cell_with_its_file(real_cell):
    names = {m["name"]: m for m in real_cell.per_layer}
    assert set(SPAN_METRICS + COUNT_METRICS) <= set(names)
    for name in SPAN_METRICS + COUNT_METRICS:
        m = names[name]
        assert m["moves"] == "cycle_ms" and m["better"] == "lower"
        assert m["reader"]["kind"] in ("span_sum", "span_self",
                                       "counter_delta")


def test_each_reader_returns_a_number_on_the_fixtures_cycle(real_cell,
                                                            cycle):
    rec = cycle
    out = readers.read_all(real_cell.per_layer, {"records": [rec]})
    for name in SPAN_METRICS:
        assert out[name]["value"] > 0, name
    for name in COUNT_METRICS:
        assert out[name]["value"] > 0 and out[name]["unit"] == "bytes/cycle"
    spans = {name: dur for name, _k, _i, _p, _s, dur in rec.spans}
    ms = {k: v["value"] for k, v in out.items()}
    assert ms["topology_ms"] == pytest.approx(1e3 * (
        spans["topology:subset_nodes"] + spans["extra_scores:topology"]))
    scores = sum(d for n, d in spans.items()
                 if n.startswith("extra_scores:"))
    assert ms["operands_ms"] == pytest.approx(1e3 * (
        spans["propose:operands"] - scores))
    assert ms["stage_ms"] <= ms["dispatch_ms"]
    assert ms["device_wait_ms"] <= ms["dispatch_ms"]
    # The new spans account for the action: what they leave of
    # allocate_host_ms is the action's own loop and the job's gates.
    named = 1e3 * sum(spans[n] for n in (
        "allocate:order", "topology:subset_nodes", "propose:operands",
        "propose:unpack", "statement:apply", "statement:commit"))
    assert 0 < named <= ms["allocate_host_ms"]


def test_the_counts_are_what_the_fixtures_shapes_give(real_cell, cycle):
    """256 pods (no padding row) against 1,024 nodes, 32-bit, in the row
    form (since PR 26): the task rows and the job's ``[2,N]`` score rows
    (built in f64, narrowed at the seam) and bool mask rows go up, one
    packed int32 result comes down."""
    rec = cycle
    stage = [s for s in rec.spans if s[0] == "seam:stage"]
    assert len(stage) == 1
    t_pad, n, r = 256, 1024, 3
    # Task rows: [t_pad, R] requests, the job, selector and toleration
    # columns (one each), and the two jobs' allowed flags.
    task_rows = t_pad * 4 * (r + 1 + 1 + 1) + 2
    job_rows = 2 * n * 4 + 2 * n
    assert rec.counters["device_upload_bytes"] == task_rows + job_rows
    # Narrowed on the host: the f64 requests and the f64 score rows.
    assert rec.counters["host_convert_bytes"] == t_pad * r * 8 + 2 * n * 8
    # One packed int32 result: placements ++ pipelined ++ job_success.
    assert rec.counters["device_download_bytes"] == (2 * t_pad + 2) * 4
    # The generator reckons the same operands from the files alone.
    tiny = spec.Cell(spec.load_benchmark(DATA), "tiny-tas-gang", DATA)
    reck = tiny.generator.reckon(tiny)
    tables = n * 4 * (3 * r + 1 + 1 + 1)
    assert reck["bytes"] == tables + (task_rows - 2) + job_rows


def test_counts_repeat_exactly_across_seeds(real_cell):
    tiny = spec.Cell(spec.load_benchmark(DATA), "tiny-tas-gang", DATA)
    wanted = readers.counters_wanted(real_cell.per_layer)
    seen = set()
    for seed in (1, 2):
        client = tiny.generator.build(tiny, seed, counters=wanted)
        rec = client.cycle()
        seen.add((rec.counters["device_upload_bytes"],
                  rec.counters["host_convert_bytes"],
                  rec.counters["device_download_bytes"]))
    assert len(seen) == 1


def parent_view(rec):
    """The spans the parent commit records for the same cycle: the new
    ones gone, their children hung on the nearest span that stays."""
    new = {s[2]: s[3] for s in rec.spans
           if any(fnmatchcase(s[0], p) for p in NEW_SPANS)}
    kept = []
    for name, kind, sid, parent, start, dur in rec.spans:
        if sid in new:
            continue
        while parent in new:
            parent = new[parent]
        kept.append((name, kind, sid, parent, start, dur))
    assert 0 < len(kept) < len(rec.spans)
    return kept


@pytest.mark.parametrize("metric", ("allocate_host_ms", "dispatch_ms",
                                    "snapshot_ms", "device_calls"))
def test_old_metrics_read_what_they_read_on_the_parent(real_cell, cycle,
                                                       metric):
    """The parent's trace of the same cycle is this one without the new
    spans: nothing new matches ``dispatch:*`` or sits where a reader of
    an old metric would count it."""
    rec = cycle

    class Parent:
        counters = rec.counters
        spans = parent_view(rec)

    m = [m for m in real_cell.per_layer if m["name"] == metric]
    here = readers.read_all(m, {"records": [rec]})
    there = readers.read_all(m, {"records": [Parent]})
    assert here == there and here[metric]["value"] > 0


def test_a_program_without_the_spans_reports_none_of_them(real_cell,
                                                          cycle):
    """On the parent the readers find nothing and the line leaves the
    metrics out; they do not raise and do not print 0."""
    rec = cycle

    class Parent:
        counters = {"device_kernel_calls": 1.0}
        spans = parent_view(rec)

    out = readers.read_all(real_cell.per_layer, {"records": [Parent]})
    assert not set(SPAN_METRICS + COUNT_METRICS) & set(out)
    assert {"allocate_host_ms", "dispatch_ms", "snapshot_ms",
            "device_calls"} <= set(out)


def test_session_open_is_the_three_plugins_spans(real_cell, cycle):
    """Between ``snapshot`` and ``action:allocate``: what PR 32 shortened
    and no metric held (PR 34)."""
    m = [m for m in real_cell.per_layer if m["name"] == "session_open_ms"]
    assert m and "workloads" not in next(
        e for e in spec.load_benchmark()["per_layer"]
        if e["name"] == "session_open_ms")
    spans = {name: dur for name, _k, _i, _p, _s, dur in cycle.spans}
    out = readers.read_all(m, {"records": [cycle]})
    assert out["session_open_ms"]["value"] == pytest.approx(1e3 * sum(
        spans[f"plugin:{p}"] for p in ("proportion", "topology",
                                       "predicates")))
    assert out["session_open_ms"]["value"] > 0


def test_a_pack_under_the_snapshot_span_is_counted_once(real_cell):
    """``ClusterArena.pack`` opens ``snapshot_delta`` under ``run_once``'s
    ``snapshot``: a cell that drives it reads the parent span alone."""
    class Rec:
        counters = {}
        spans = [("snapshot", "snapshot", 1, None, 0.0, 0.5),
                 ("snapshot_delta", "snapshot", 2, 1, 0.1, 0.3)]

    m = [m for m in real_cell.per_layer if m["name"] == "snapshot_ms"]
    assert readers.read_all(m, {"records": [Rec]}) == {
        "snapshot_ms": {"value": pytest.approx(500.0), "unit": "ms"}}
