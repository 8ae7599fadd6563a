"""End to end at a tiny size on the CPU: the reference against the exact
kernel, the controls, a broken timed path, and the refusal to report
device numbers without a chip."""

import subprocess
import sys

import numpy as np
import pytest

from conftest import DATA, ROOT

# The committed cell's shape (preferred level), a required level, no
# topology: the paths of the generator, the reference and the comparison.
CELLS = ("tiny-tas-gang", "tiny-required-gang", "tiny-plain-gang")
TOPOLOGY_CELLS = CELLS[:2]


def run_cell(workload, seed, trace=False, seconds=0.5):
    from benchmark import run
    return run.run_cell(workload, seed, seconds, trace, require_chip=False,
                        root=DATA)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", (7, 3000000019))
def test_reference_agrees_with_the_exact_kernel(workload, seed):
    out = run_cell(workload, seed)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["run"]["window_compiles"] == 0
    # Every pod of every gang of the window was compared.
    assert out["run"]["placements_checked"] == sum(
        r["count"] for r in out["run"]["gang_roles"]) * out["attempted"]
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"cycle_ms", "pods_bound_per_s", "setup_s"}


@pytest.mark.parametrize("workload", CELLS)
def test_traced_cpu_run_reports_no_device_metric(workload):
    out = run_cell(workload, 11, trace=True)
    assert out["correct"], out["compared"]
    # Spans and counters are read; nothing that needs a device trace is.
    assert {"snapshot_ms", "allocate_host_ms", "dispatch_ms",
            "device_calls"} <= set(out["metrics"])
    assert out["metrics"]["device_calls"]["value"] == 1.0
    for name in ("allocate_jobs_kernel_ms", "allocate_jobs_kernel_roofline",
                 "device_idle"):
        assert name not in out["metrics"]
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_without_a_chip_the_command_prints_no_result():
    from benchmark.harness import spec
    cell = spec.load_benchmark()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("workload", CELLS)
def test_stale_control_fills_nodes_past_capacity(workload):
    from control import run_control
    for seed in (1, 2, 3):
        out = run_control(workload, seed, "stale", root=DATA)
        assert not out["correct"]
        assert out["compared"]["nodes_over_capacity"][0] > 0
        assert out["compared"]["placements_not_reference"][0] > 0


@pytest.mark.parametrize("workload", TOPOLOGY_CELLS)
def test_topology_blind_control_leaves_the_domain(workload):
    """Placed over the whole fleet the gang lands in the first superpod by
    name that has room.  On seeds where that is the fullest one too (1 and
    7 of the first 8) the control is the reference; on the others every
    pod is somewhere else."""
    from control import run_control
    for seed in (2, 3, 4):
        out = run_control(workload, seed, "no_topology", root=DATA)
        assert not out["correct"]
        assert out["compared"]["placements_not_reference"][0] >= 256


def test_queue_blind_control_passes_a_limit_and_the_program_does_not():
    from control import run_control
    out = run_control("tiny-tight-queue", 1, "no_queue_limit", cycles=1,
                      root=DATA)
    assert not out["correct"]
    assert out["compared"]["queues_over_limit"][0] > 0
    assert out["compared"]["gangs_refused_by_reference"][0] > 0
    # The scheduler itself refuses the same gang: nothing binds.
    out = run_cell("tiny-tight-queue", 1)
    assert out["compared"]["queues_over_limit"][0] == 0
    assert out["compared"]["gangs_not_bound"][0] == out["attempted"]


@pytest.mark.parametrize("shift", (1, 600))
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_not_correct(workload, shift, monkeypatch):
    """One answer altered where it is produced: the kernel's placements,
    as the session unpacks them, with one worker moved to the next node,
    or 600 nodes on: out of its superpod of 256."""
    from kai_scheduler_tpu.framework import session

    real = session._unpack_allocation

    def altered(result, t):
        placed, piped, success = real(result, t)
        placed = np.array(placed)
        placed[t // 2] = (placed[t // 2] + shift) % 1024
        return placed, piped, success

    monkeypatch.setattr(session, "_unpack_allocation", altered)
    out = run_cell(workload, 5)
    assert not out["correct"], out["compared"]
    c = {k: v[0] for k, v in out["compared"].items()}
    if workload == "tiny-required-gang" and shift > 256:
        # The program itself refuses a gang with a pod outside the
        # required domain: nothing binds.
        assert c["gangs_not_bound"] == out["attempted"]
        return
    assert c["placements_not_reference"] > 0, c
    if workload == "tiny-tas-gang" and shift > 256:
        assert c["pods_outside_domain"] == out["attempted"]
    if shift > 256 and workload in TOPOLOGY_CELLS:
        assert out["compared"]["pods_outside_domain"][0] == out["attempted"]


GIB = 2 ** 30
# ``memory_stats()`` as the v5e's runtime gives it (PERF.md section 6, PR
# 34): buffers under ``bytes_in_use``, a program's reservation apart.
MEMORY_CASES = {
    "in use alone": (
        {"bytes_in_use": 4 * GIB, "peak_bytes_in_use": 4 * GIB + 512,
         "bytes_reserved": 0, "peak_bytes_reserved": 0},
        4 * GIB + 512),
    "reserved alone": (
        {"bytes_in_use": 0, "peak_bytes_in_use": 0,
         "bytes_reserved": 8 * GIB, "peak_bytes_reserved": 8 * GIB},
        8 * GIB),
    # A 4 GiB buffer deleted before an 8 GiB reservation: the chip never
    # held 12.
    "both, peaks at different instants": (
        {"bytes_in_use": 2 ** 20, "peak_bytes_in_use": 4 * GIB,
         "bytes_reserved": 8 * GIB, "peak_bytes_reserved": 8 * GIB},
        8 * GIB + 2 ** 20),
    "both, in one call": (
        {"bytes_in_use": 3 * GIB, "peak_bytes_in_use": 3 * GIB,
         "bytes_reserved": 6 * GIB, "peak_bytes_reserved": 6 * GIB},
        9 * GIB),
    "the reservation given back before the read": (
        {"bytes_in_use": 10 * GIB, "peak_bytes_in_use": 10 * GIB,
         "bytes_reserved": 0, "peak_bytes_reserved": 8 * GIB},
        10 * GIB),
    "a backend with the old key alone": (
        {"peak_bytes_in_use": 5 * GIB}, 5 * GIB),
    "a backend with neither key": ({"num_allocs": 3}, 0),
    "a backend with no stats": (None, 0),
}


@pytest.mark.parametrize("case", MEMORY_CASES)
def test_the_memory_figure_never_adds_two_peaks(case):
    from benchmark import run
    stats, figure = MEMORY_CASES[case]
    out = run.device_memory(stats)
    stats = stats or {}
    assert out == {
        "memory_peak_bytes": figure,
        "memory_in_use_peak_bytes": stats.get("peak_bytes_in_use", 0),
        "memory_reserved_peak_bytes": stats.get("peak_bytes_reserved", 0),
        "memory_at_read_bytes": (stats.get("bytes_in_use", 0)
                                 + stats.get("bytes_reserved", 0))}


@pytest.mark.parametrize("chips, fullest", ((4, 2), (2, 1), (1, 0)))
def test_memory_peak_is_the_fullest_of_the_cells_devices(monkeypatch, chips,
                                                         fullest):
    """Four devices of which the third is fullest, by its reservation: the
    figure and the parts are that device's, and a device past the cell's
    ``chips`` is not read."""
    import jax
    from benchmark import run

    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    devices = [Device({"peak_bytes_in_use": GIB}),
               Device({"peak_bytes_in_use": 2 * GIB,
                       "peak_bytes_reserved": GIB}),
               Device({"peak_bytes_in_use": GIB // 2,
                       "peak_bytes_reserved": 7 * GIB}),
               Device(None)]
    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    assert run.memory_peak(chips) == run.device_memory(
        devices[fullest].stats)
    assert run.memory_peak(4)["memory_reserved_peak_bytes"] == 7 * GIB


def test_the_result_line_keeps_its_keys_and_the_device_gains_the_parts():
    out = run_cell("tiny-plain-gang", 13)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "run", "compared"]
    assert list(out["device"]) == [
        "platform", "kind", "count", "memory_peak_bytes",
        "memory_in_use_peak_bytes", "memory_reserved_peak_bytes",
        "memory_at_read_bytes"]


def test_preflight_judges_a_cell_by_the_larger_of_its_two_parts(capsys):
    """The reclaim fixture's parts: 6,400 bytes of operands in the
    client's buffers, seven ``[K,N,R]`` arrays in the program."""
    from benchmark import preflight
    from benchmark.harness import spec
    cell = spec.Cell(spec.load_benchmark(DATA), "tiny-reclaim-gang", DATA)
    reck = cell.generator.reckon(cell)
    assert reck["bytes"] == 6400 and reck["program_bytes"] == 7 * 49152
    assert preflight.judged_bytes(reck["bytes"],
                                  reck["program_bytes"]) == 7 * 49152
    # 6 MB of operands beside a reservation of 6.47 GiB is over the floor,
    # and two parts of 3 GiB, whose peaks need not coincide, are not.
    assert preflight.judged_bytes(6e6, 6.47 * GIB) > preflight.FLOOR_BYTES
    assert preflight.judged_bytes(3.0 * GIB, 3.0 * GIB) \
        < preflight.FLOOR_BYTES
    assert preflight.main(["--no-compile", "--root", DATA, "--workload",
                           "tiny-reclaim-gang"]) == 1
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("tiny-reclaim-gang")][0]
    assert "client's buffers reckoned 0.0 MB" in line
    assert "program's temporaries reckoned 0.3 MB" in line
    assert line.endswith("UNDER THE 4.00 GiB FLOOR")
