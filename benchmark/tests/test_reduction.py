"""The reduction from spans and traces to metrics, on hand-made input and
on a small trace recorded on a TPU v5e (``data/tiny_tpu.xplane.pb``)."""

import os

import pytest

from conftest import DATA

from benchmark.harness import readers
from benchmark.harness import trace as tr


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_busy_idle_and_programs():
    raw = {"devices": [{"name": "/device:TPU:0",
                        "modules": [("jit_k(1)", 100, 400),
                                    ("jit_other(2)", 700, 100)],
                        "ops": [("fusion.1", 100, 150), ("fusion.1", 200, 100),
                                ("while.2", 300, 200), ("copy", 700, 100)]}],
           "annotations": [("bench:run_once", 0, 1000)]}
    red = tr.reduce(raw, window_s=1e-6)
    assert red["busy_s"] == pytest.approx(500e-9)
    assert tr.program_seconds(red, "jit_k*") == pytest.approx(400e-9)
    assert tr.program_seconds(red, "jit_absent*") is None
    assert tr.top(red["ops"])[0] == ["fusion.1", pytest.approx(250e-9)]
    # gaps inside the annotated window: [0,100], [500,700], [800,1000]
    assert sorted(b - a for a, b in red["gaps"]) == [100, 200, 200]
    # The gap [500,700] lies under action:allocate, 100 ns of it under a
    # dispatch span below that; the other two gaps under no span.
    gaps = dict(tr.name_gaps(red, raw, lambda i: [
        ("action:allocate", 450, 720), ("dispatch:k", 600, 720)]))
    assert gaps == {"run_once": pytest.approx(300e-9),
                    "run_once/action:allocate": pytest.approx(100e-9),
                    "run_once/dispatch:k": pytest.approx(100e-9)}
    run = {"reduced": red, "traced_cycles": 1}
    assert readers.trace_idle({}, run) == pytest.approx(50.0)
    assert readers.trace_program_time({"match": "jit_k*"}, run) \
        == pytest.approx(400e-6)


def test_no_device_plane_reads_nothing():
    assert tr.reduce({"devices": [], "annotations": []}, 1.0) is None
    assert readers.trace_idle({}, {"reduced": None}) is None
    assert readers.roofline({"match": "x"}, {"reduced": None}) is None


class Rec:
    def __init__(self, spans, counters=None):
        self.spans, self.counters = spans, counters or {}


def test_span_readers():
    spans = [("snapshot", "snapshot", "s1", "s0", 0.0, 0.010),
             ("dispatch:fair_share", "kernel", "s2", "s0", 0.01, 0.001),
             ("dispatch:allocate_jobs", "kernel", "s4", "s3", 0.02, 0.030),
             ("dispatch:allocate_jobs_fetch", "kernel", "s5", "s3", 0.05,
              0.020),
             ("action:allocate", "action", "s3", "s0", 0.015, 0.100)]
    run = {"records": [Rec(spans, {"device_kernel_calls": 1.0})]}
    assert readers.span_sum({"match": ["snapshot", "snapshot_delta"]}, run) \
        == pytest.approx(10.0)
    assert readers.span_self({"match": ["action:allocate"],
                              "minus": ["dispatch:*"]}, run) \
        == pytest.approx(50.0)
    assert readers.counter_delta({"counter": "device_kernel_calls"}, run) == 1
    assert readers.span_sum({"match": ["absent"]}, run) is None


def test_roofline_needs_a_known_device():
    from benchmark import roofline as rf
    assert rf.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        rf.peaks("TPU v9")
    need = rf.exact_scan_bytes(steps=10, nodes=100, has_mask=True,
                               label_cols=1, taint_cols=1)
    assert need == 10 * (400 + 100 + 3600 + 400 + 800)


FIXTURE = os.path.join(DATA, "tiny_tpu.xplane.pb")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded TPU trace in this checkout")
def test_recorded_tpu_trace():
    raw = tr.read(FIXTURE)
    assert len(raw["devices"]) == 1
    assert [a[0] for a in raw["annotations"]] == ["bench:run_once"] * 2
    red = tr.reduce(raw, window_s=1.0)
    secs = tr.program_seconds(red, "jit_tiny_scan*")
    # Two calls of about 11 us each; the operations inside them cover a
    # part of that (busy time is the union of operations, not of programs).
    assert secs == pytest.approx(21.8e-6, rel=0.05)
    assert 0 < red["busy_s"] <= secs
    assert all(not name.startswith("%") or " = " not in name
               for name in red["ops"])
