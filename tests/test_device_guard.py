"""Chaos ring: the device-guard's degraded-mode contract, exercised
deterministically — no real TPU, no real hangs (utils/deviceguard.py,
docs/DEGRADATION.md).

Covers the ISSUE acceptance ladder end to end: a hung device never blocks
a cycle (watchdog abandons the worker); transient errors retry with
backoff and succeed on the device; persistent failure trips the circuit
breaker and scheduling degrades to the CPU fallback; a mid-cycle device
death rolls back uncommitted statements (no phantom allocations); the
breaker half-open-probes its way back once the fault clears; and all of
it surfaces on /healthz, /metrics, and scheduler events.  The final
smoke runs bench.py itself under ``KAI_FAULT_INJECT=hang`` and asserts
the bench degrades to CPU in seconds and exits non-zero.
"""

import json
import random
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.scheduler import Scheduler
from kai_scheduler_tpu.server import healthz_payload
from kai_scheduler_tpu.utils.cluster_spec import build_cluster
from kai_scheduler_tpu.utils.deviceguard import (CLOSED, HALF_OPEN, OPEN,
                                                 CircuitBreaker,
                                                 CycleDeadlineExceeded,
                                                 DeviceGuard,
                                                 DeviceGuardError,
                                                 DeviceTimeout,
                                                 FaultInjector, Watchdog,
                                                 configure_device_guard,
                                                 device_guard,
                                                 reset_device_guard,
                                                 run_with_deadline)
from kai_scheduler_tpu.utils.metrics import METRICS

pytestmark = pytest.mark.chaos

REPO = Path(__file__).resolve().parent.parent


class FakeClock:
    """Deterministic breaker clock: cooloffs elapse by advance(), never
    by wall time."""

    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(autouse=True)
def fresh_guard(monkeypatch):
    """Each chaos test gets a pristine singleton and a clean KAI_* env —
    faults configured by one test must never leak into the next."""
    for var in ("KAI_FAULT_INJECT", "KAI_DEVICE_DEADLINE_S",
                "KAI_DEVICE_RETRIES", "KAI_BREAKER_THRESHOLD",
                "KAI_BREAKER_COOLOFF_S", "KAI_FAULT_SEED"):
        monkeypatch.delenv(var, raising=False)
    reset_device_guard()
    yield
    reset_device_guard()


def small_cluster():
    """4 nodes x 8 GPUs, 4 gangs of 2 one-GPU tasks: everything fits."""
    return build_cluster({
        "nodes": {f"n{i}": {"gpu": 8} for i in range(4)},
        "queues": {"q": {}},
        "jobs": {f"j{i}": {"queue": "q", "min_available": 2,
                           "tasks": [{"cpu": "1", "mem": "1Gi",
                                      "gpu": 1}] * 2}
                 for i in range(4)},
    })


def _flaky_seed(p: float, want: tuple) -> int:
    """Find a seed whose first draws match ``want`` (True = injected
    error) — the test documents its own determinism instead of
    hardcoding magic RNG constants."""
    for seed in range(1000):
        rng = random.Random(seed)
        if tuple(rng.random() < p for _ in want) == want:
            return seed
    raise AssertionError("no seed found")


# -- watchdog primitives ------------------------------------------------------

class TestWatchdog:
    def test_no_deadline_runs_inline(self):
        assert run_with_deadline(lambda: 7, None) == 7
        assert run_with_deadline(lambda: 7, 0) == 7

    def test_deadline_abandons_hung_worker(self):
        """The calling thread is released at the deadline and the
        abandoned worker exits promptly via the cancel event — a hang
        costs one deadline, not a thread leak."""
        released = threading.Event()

        def hung(cancel=None):
            cancel.wait(60.0)
            released.set()
            raise RuntimeError("should be swallowed by abandonment")

        t0 = time.monotonic()
        with pytest.raises(DeviceTimeout):
            run_with_deadline(hung, 0.2, label="t")
        assert time.monotonic() - t0 < 2.0
        assert released.wait(2.0), "worker never observed its cancel"

    def test_worker_exception_relayed(self):
        with pytest.raises(ValueError, match="boom"):
            run_with_deadline(lambda: (_ for _ in ()).throw(
                ValueError("boom")), 5.0)

    def test_watchdog_cancel_is_idempotent(self):
        fired = []
        wd = Watchdog(0.05, lambda: fired.append(1)).start()
        wd.cancel()
        wd.cancel()
        time.sleep(0.15)
        assert not fired and wd.fired  # fired flag means "won't fire"


class TestFaultInjector:
    def test_unknown_mode_is_loud(self):
        with pytest.raises(ValueError, match="unknown fault-inject"):
            FaultInjector("explode")

    def test_flaky_stream_is_deterministic(self):
        a = FaultInjector("flaky:0.5", seed=3)
        b = FaultInjector("flaky:0.5", seed=3)
        outcomes = []
        for inj in (a, b):
            errs = []
            for _ in range(8):
                try:
                    inj.before("k", threading.Event())
                    errs.append(False)
                except RuntimeError:
                    errs.append(True)
            outcomes.append(errs)
        assert outcomes[0] == outcomes[1]


# -- the guard: timeout, retry, fallback --------------------------------------

class TestGuardedCall:
    def test_hang_times_out_then_cpu_fallback_completes(self):
        calls = []
        guard = DeviceGuard(deadline_s=0.2, retries=2, breaker_threshold=3,
                            fault="hang")
        t0 = time.monotonic()
        out = guard.call(lambda: calls.append(1) or 42, label="k")
        assert out == 42
        assert time.monotonic() - t0 < 5.0
        # A hang is not retried (each retry would burn a full deadline);
        # the thunk ran exactly once — on the clean fallback path.
        assert guard.timeouts == 1 and guard.retried == 0
        assert guard.fallback_calls == 1 and calls == [1]

    def test_flaky_retries_then_succeeds_on_device(self):
        seed = _flaky_seed(0.5, (True, False))  # error, then clean
        retries0 = METRICS.counters.get("device_guard_retries", 0)
        guard = DeviceGuard(deadline_s=5.0, retries=2, breaker_threshold=3,
                            fault="flaky:0.5", fault_seed=seed,
                            backoff_base_s=0.01)
        assert guard.call(lambda: 7, label="k") == 7
        assert guard.retried == 1 and guard.fallback_calls == 0
        assert guard.breaker.state == CLOSED
        assert guard.breaker.consecutive_failures == 0
        assert METRICS.counters["device_guard_retries"] == retries0 + 1

    def test_badshape_rejected_by_validator_falls_back(self):
        class Result:
            def __init__(self):
                self.placements = np.zeros((8, 4))

        guard = DeviceGuard(deadline_s=5.0, retries=2, breaker_threshold=3,
                            fault="badshape")
        out = guard.call(Result, label="k",
                         validate=lambda r: r.placements.shape[0] == 8)
        assert out.placements.shape[0] == 8  # the fallback's clean result
        # Deterministic corruption is not retried.
        assert guard.bad_results == 1 and guard.retried == 0
        assert guard.fallback_calls == 1

    def test_badshape_truncates_bare_array_results(self):
        """score_nodes-style dispatches return a bare array, not a
        result container: badshape must corrupt those too (leading-axis
        truncation), and the validator must catch it — returning an
        opaque proxy that passes validation would make the fault a
        no-op for exactly these call sites."""
        guard = DeviceGuard(deadline_s=5.0, retries=0, breaker_threshold=9,
                            fault="badshape")
        out = guard.call(lambda: np.zeros(16), label="k",
                         validate=lambda r: getattr(r, "shape", (0,))[0]
                         == 16)
        assert isinstance(out, np.ndarray) and out.shape == (16,)
        assert guard.bad_results == 1 and guard.fallback_calls == 1

    def test_watchdog_workers_are_reused(self):
        """Healthy dispatches must not spawn a thread each — the worker
        returns to the idle pool and serves the next call (hot-path
        overhead, code-review finding)."""
        idents = []
        for _ in range(4):
            run_with_deadline(
                lambda: idents.append(threading.get_ident()), 5.0)
        assert len(set(idents)) == 1, idents

    def test_fallback_disabled_raises_device_guard_error(self):
        guard = DeviceGuard(deadline_s=5.0, retries=0, breaker_threshold=3,
                            fault="error", fallback_enabled=False)
        with pytest.raises(DeviceGuardError):
            guard.call(lambda: 1, label="k")

    def test_cycle_deadline_aborts_before_dispatch(self):
        clock = FakeClock()
        guard = DeviceGuard(deadline_s=5.0, clock=clock)
        calls = []
        with pytest.raises(CycleDeadlineExceeded):
            guard.call(lambda: calls.append(1), label="k",
                       cycle_deadline_at=clock() - 1.0)
        assert not calls  # neither device nor fallback was attempted

    def test_budget_exhausted_by_device_attempt_skips_fallback(self):
        """A device attempt that burns the rest of the cycle budget must
        surface CycleDeadlineExceeded — the fallback must neither run
        unwatched (a <= 0 deadline reads as "inline, no watchdog") nor
        run at all."""
        clock = FakeClock()
        guard = DeviceGuard(deadline_s=5.0, retries=0, clock=clock)
        calls = []

        def burns_budget():
            calls.append(1)
            clock.advance(20.0)
            raise RuntimeError("transient device error")

        with pytest.raises(CycleDeadlineExceeded, match="CPU fallback"):
            guard.call(burns_budget, label="k",
                       cycle_deadline_at=clock() + 10.0)
        assert calls == [1]  # one device attempt, zero fallback runs


# -- circuit breaker ----------------------------------------------------------

class TestCircuitBreaker:
    def test_trip_cooloff_half_open_recover(self):
        clock = FakeClock()
        br = CircuitBreaker(threshold=2, cooloff_s=30.0, clock=clock)
        assert br.allow_device()
        assert not br.record_failure("e1")
        assert br.record_failure("e2")  # second consecutive: trips
        assert br.state == OPEN
        assert not br.allow_device()    # cooloff not elapsed
        clock.advance(31.0)
        assert br.allow_device()        # the half-open probe
        assert br.state == HALF_OPEN
        assert not br.allow_device()    # concurrent calls stay on fallback
        assert br.record_success()      # probe succeeded -> closed
        assert br.state == CLOSED and br.consecutive_failures == 0

    def test_half_open_failure_reopens(self):
        clock = FakeClock()
        br = CircuitBreaker(threshold=3, cooloff_s=10.0, clock=clock)
        for _ in range(3):
            br.record_failure("e")
        clock.advance(11.0)
        assert br.allow_device() and br.state == HALF_OPEN
        br.record_failure("probe failed")  # single failure while probing
        assert br.state == OPEN
        assert not br.allow_device()  # a fresh cooloff window started

    def test_open_breaker_dedups_degraded_events(self):
        clock = FakeClock()
        events = []
        guard = DeviceGuard(deadline_s=5.0, retries=0, breaker_threshold=1,
                            fault="error", clock=clock)
        sink = lambda kind, msg: events.append(kind)  # noqa: E731
        guard.call(lambda: 1, label="k", record_event=sink)  # trips
        assert events.count("DeviceGuardTripped") == 1
        degraded0 = events.count("DeviceGuardDegraded")
        guard.call(lambda: 1, label="k", record_event=sink)
        guard.call(lambda: 1, label="k", record_event=sink)
        # Only the FIRST open-skipped call announces; the rest are silent
        # (one event per state change, not one per dispatch).
        assert events.count("DeviceGuardDegraded") == degraded0 + 1


# -- the fleet: full cycles under injected faults -----------------------------

class TestSchedulerUnderFaults:
    def test_hang_cycle_completes_degraded_then_recovers(self):
        """The acceptance path: with KAI_FAULT_INJECT=hang a full cycle
        completes within its deadline on the CPU fallback, /healthz
        reports degraded with the breaker open, faults surface in
        metrics and events, and the next cycle after the fault clears
        recovers through the half-open probe."""
        clock = FakeClock()
        timeouts0 = METRICS.counters.get("device_guard_timeouts", 0)
        trips0 = METRICS.counters.get("device_guard_trips", 0)
        guard = configure_device_guard(
            deadline_s=0.3, retries=0, breaker_threshold=1,
            breaker_cooloff_s=60.0, fault="hang", clock=clock)
        sched = Scheduler(lambda: small_cluster(),
                          SchedulerConfig(cycle_deadline_s=120.0))
        t0 = time.monotonic()
        ssn = sched.run_once()
        elapsed = time.monotonic() - t0
        assert ssn.aborted is None, ssn.aborted
        assert elapsed < 120.0
        assert len(ssn.cache.bound) == 8  # every pod placed, degraded
        assert guard.breaker.state == OPEN
        assert guard.timeouts >= 1 and guard.fallback_calls >= 1
        # Observability: metrics families and scheduler events.
        assert METRICS.counters["device_guard_timeouts"] > timeouts0
        assert METRICS.counters["device_guard_trips"] > trips0
        assert METRICS.gauges["device_guard_state"] == 2
        kinds = {k for k, _ in ssn.cache.events}
        assert "DeviceGuardTripped" in kinds
        assert "DeviceGuardDegraded" in kinds
        health = healthz_payload()
        assert health["status"] == "degraded"
        assert health["device_guard"]["state"] == "open"
        assert health["device_guard"]["fault_inject"] == "hang"

        # Fault clears, cooloff elapses: the next cycle's first dispatch
        # is the half-open probe; success closes the breaker.  The 0.3s
        # deadline existed to make the injected hang cheap — the probe
        # is a REAL kernel call that may pay an XLA compile, so give it
        # a production-shaped deadline.
        guard.clear_fault()
        guard.deadline_s = 60.0
        clock.advance(61.0)
        ssn2 = Scheduler(lambda: small_cluster(),
                         SchedulerConfig()).run_once()
        assert len(ssn2.cache.bound) == 8
        assert guard.breaker.state == CLOSED
        assert METRICS.gauges["device_guard_state"] == 0
        assert "DeviceGuardRecovered" in {k for k, _ in ssn2.cache.events}
        assert healthz_payload()["status"] == "ok"

    def test_mid_cycle_death_rolls_back_uncommitted(self, monkeypatch):
        """A device death after an action already staged (uncommitted)
        placements: the cycle aborts, the statement rolls back, and the
        cache shows no phantom allocations — then a healthy retry cycle
        schedules everything."""
        guard = configure_device_guard(deadline_s=5.0, retries=0,
                                       breaker_threshold=100,
                                       fallback_enabled=False)
        cluster = small_cluster()
        staged = {}

        class PartialThenDeviceDeath:
            name = "chaos"

            def execute(self, ssn):
                st = ssn.statement()
                pg = next(iter(ssn.cluster.podgroups.values()))
                task = next(iter(pg.pods.values()))
                staged["task"] = task
                staged["idle_before"] = ssn.node_idle.copy()
                st.allocate(task, "n0")
                assert task.node_name == "n0"  # staged, not committed
                # The device dies only NOW — session open (fair-share
                # dispatch included) ran clean, so the abort is pinned to
                # this mid-action death.
                guard.set_fault("error")
                ssn.dispatch_kernel(lambda: 1, label="chaos")  # dies

        monkeypatch.setattr("kai_scheduler_tpu.scheduler.build_actions",
                            lambda names: [PartialThenDeviceDeath()])
        aborts0 = METRICS.counters.get("scheduler_cycle_aborts", 0)
        sched = Scheduler(lambda: cluster, SchedulerConfig())
        ssn = sched.run_once()
        assert ssn.aborted and "chaos" in ssn.aborted
        assert METRICS.counters["scheduler_cycle_aborts"] == aborts0 + 1
        # No phantom allocation anywhere: object graph, dense mirrors,
        # or cache.
        assert not staged["task"].node_name
        assert np.array_equal(ssn.node_idle, staged["idle_before"])
        assert not ssn.cache.bound
        assert "CycleAborted" in {k for k, _ in ssn.cache.events}

        # The same cluster schedules fully once the device heals.
        monkeypatch.undo()
        reset_device_guard()
        ssn2 = Scheduler(lambda: cluster, SchedulerConfig()).run_once()
        assert len(ssn2.cache.bound) == 8

    def test_cycle_deadline_skips_actions_and_is_counted(self):
        deadl0 = METRICS.counters.get("scheduler_cycle_deadline_exceeded",
                                      0)
        sched = Scheduler(lambda: small_cluster(),
                          SchedulerConfig(cycle_deadline_s=1e-9))
        ssn = sched.run_once()
        assert ssn.aborted and "cycle deadline" in ssn.aborted
        assert not ssn.cache.bound  # no action ran
        assert METRICS.counters["scheduler_cycle_deadline_exceeded"] \
            == deadl0 + 1

    def test_guard_configures_from_environment(self, monkeypatch):
        monkeypatch.setenv("KAI_FAULT_INJECT", "slow:5")
        monkeypatch.setenv("KAI_DEVICE_DEADLINE_S", "12.5")
        monkeypatch.setenv("KAI_BREAKER_THRESHOLD", "7")
        reset_device_guard()
        guard = device_guard()
        assert guard.injector.mode == "slow"
        assert guard.injector.slow_ms == 5.0
        assert guard.deadline_s == 12.5
        assert guard.breaker.threshold == 7
        assert healthz_payload()["device_guard"]["fault_inject"] == "slow:5"


# -- bench delivery smoke -----------------------------------------------------

def test_bench_fault_inject_hang_degrades_to_cpu(tmp_path):
    """bench.py under an injected device hang must deliver a primary
    number on the guard's CPU fallback — annotated @guard-degraded with
    the breaker open — in seconds, and exit non-zero: a run the guard
    degraded is never a clean measurement."""
    import os

    env = dict(os.environ)
    env.update({"BENCH_SMOKE": "1",
                "KAI_DEVICE_DEADLINE_S": "1.5", "KAI_DEVICE_RETRIES": "0",
                "KAI_BREAKER_THRESHOLD": "1", "JAX_PLATFORMS": "cpu",
                "PYTHONUNBUFFERED": "1"})
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-u", str(REPO / "bench.py"),
         "--fault-inject=hang"],
        env=env, cwd=str(REPO), capture_output=True, text=True,
        timeout=240)
    elapsed = time.monotonic() - t0
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, (proc.stdout, proc.stderr[-2000:])
    assert proc.returncode != 0, "a guard fallback must fail the bench"
    result = lines[-1]
    assert result["metric"].endswith("@guard-degraded"), result["metric"]
    assert result["vs_baseline"] is None
    status = result["detail"]["device_guard"]
    assert status["state"] == "open"
    assert status["timeouts"] >= 1 and status["fallback_calls"] >= 1
    assert status["fault_inject"] == "hang"
    # Smoke mode must actually shrink the workload (16 jobs x 4 tasks),
    # not rebuild the full-size arrays from def-time defaults.
    assert result["detail"]["pods_placed"] == 64
    # The whole point: degrade in seconds.
    assert elapsed < 180, f"bench took {elapsed:.0f}s under hang injection"
