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
