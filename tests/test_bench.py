"""What ``python bench.py`` does now: one process, the backend named in
every row, a JSON line per phase, and a non-zero exit when a phase failed
or the device guard fell back to the CPU.  (The streaming child, the CPU
fallback child and the parity child this file used to pin are gone.)
"""

import contextlib
import importlib.util
import io
import json
import sys
from pathlib import Path

import pytest

from kai_scheduler_tpu.utils import compile_cache
from kai_scheduler_tpu.utils.deviceguard import (configure_device_guard,
                                                 reset_device_guard)

_spec = importlib.util.spec_from_file_location(
    "bench", Path(__file__).resolve().parent.parent / "bench.py")
bench = importlib.util.module_from_spec(_spec)
sys.modules.setdefault("bench", bench)
_spec.loader.exec_module(bench)


class _Guard:
    def __init__(self, degraded=False, fallback_calls=0):
        self.degraded = degraded
        self.fallback_calls = fallback_calls

    def status(self):
        return {"fallback_calls": self.fallback_calls}


def _run_smoke(monkeypatch):
    """bench.main() at the BENCH_SMOKE size, in process: (rc, rows)."""
    monkeypatch.setenv("BENCH_SMOKE", "1")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench.main()
    rows = [json.loads(ln) for ln in out.getvalue().splitlines()
            if ln.startswith("{")]
    return rc, rows


@pytest.fixture
def clean_guard():
    reset_device_guard()
    yield
    reset_device_guard()


def test_smoke_run_is_one_process_and_exits_zero(monkeypatch, clean_guard):
    rc, rows = _run_smoke(monkeypatch)
    assert rc == 0
    assert len(rows) == 1
    row = rows[0]
    assert row["metric"] == "scheduling_cycle_latency_ms@64nodes_64pods"
    assert row["detail"]["pods_placed"] == 64
    assert row["detail"]["backend"] == "cpu"
    assert "device_guard" not in row["detail"]


def test_rows_carry_no_relay_era_keys(monkeypatch, clean_guard):
    _rc, rows = _run_smoke(monkeypatch)
    detail = rows[-1]["detail"]
    assert "rtt_ms" not in detail and "est_device_ms" not in detail
    assert "backend_note" not in detail and "tpu_error" not in detail


def test_guard_fallback_fails_the_run(monkeypatch):
    """Every dispatch errors on the 'device': the guard serves the run
    from its CPU fallback, the row says so, and the exit code is 1."""
    configure_device_guard(fault="error", retries=0, breaker_threshold=1,
                           deadline_s=30.0)
    try:
        rc, rows = _run_smoke(monkeypatch)
    finally:
        reset_device_guard()
    assert rc == 1
    row = rows[-1]
    assert row["metric"].endswith("@guard-degraded")
    assert row["vs_baseline"] is None
    assert row["detail"]["device_guard"]["fallback_calls"] >= 1
    assert row["detail"]["pods_placed"] == 64  # degraded, still answered


def test_exit_code_clean_run():
    result = {"detail": {"backend": "cpu", "tas": {"cycle_ms": 1.0}}}
    assert bench._exit_code(result, _Guard()) == 0


def test_exit_code_failed_phase():
    result = {"detail": {"backend": "cpu",
                         "large_gang": {"cycle_ms": 1.0},
                         "tas": {"error": "RuntimeError('boom')"}}}
    assert bench._exit_code(result, _Guard()) == 1


def test_exit_code_guard_fell_back_then_recovered():
    """A breaker that re-closed still mixed CPU numbers into the run."""
    result = {"detail": {"backend": "tpu"}}
    assert bench._exit_code(result, _Guard(fallback_calls=2)) == 1


def test_exit_code_guard_degraded():
    result = {"detail": {"backend": "tpu"}}
    assert bench._exit_code(result, _Guard(degraded=True)) == 1


def test_relay_machinery_is_gone():
    for name in ("orchestrate", "_cpu_env", "_stream_child", "_run_parity",
                 "parity_main", "measure_rtt", "AGGREGATE_BUDGET_S",
                 "TPU_FIRST_RESULT_S", "PARITY_FILE", "CACHE_DIR"):
        assert not hasattr(bench, name), name


def test_bench_shares_the_one_cache_helper():
    assert bench.enable_compile_cache is compile_cache.enable_compile_cache
