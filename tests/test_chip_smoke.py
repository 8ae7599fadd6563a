"""chip_smoke.py on the CPU: every stage function at a tiny size (Pallas in
interpret mode), and every way the smoke must fail — no chip, a guard
fallback, a child's non-zero exit, a missing kernel label, a skipped
stage.  The full-width run needs the chip tool; this file keeps the
script's logic honest between those runs."""

import copy
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke as cs
from kai_scheduler_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parent.parent

TINY = cs.FleetSize(nodes=64, racks=8, wave_jobs=2, wave_gang=8,
                    hetero_gangs=2, singles=32, rack_gangs=1, rack_gang=16,
                    half_gpu=2, rank_gangs=1, rank_gang=32,
                    starved_gangs=1, starved_gang=4, cycles=120,
                    wave_timeout_s=150.0)


def _child_env():
    """The children's environment: CPU, and 32-bit like the chip (tier-1's
    own process is x64)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_X64", None)
    return env


# -- Stage A, for real, tiny ---------------------------------------------------

def test_stage_a_tiny_runs_every_label_through_the_daemon(tmp_path):
    row = cs.stage_a(TINY, fused_mode="jnp", expect_platform="cpu",
                     out_dir=str(tmp_path), env=_child_env())
    assert row["platform"] == "cpu" and row["device_kind"] and row["count"]
    assert row["pods_bound"] == 126 and row["evicted"] == 4
    assert row["guard"] == {"state": "closed", "fallback_calls": 0,
                            "timeouts": 0, "bad_results": 0, "retried": 0}
    for slot in cs.KERNEL_LABELS:
        names = slot if isinstance(slot, tuple) else (slot,)
        assert any(n in row["dispatches"] for n in names), slot
    assert row["fused_taken"]["jnp"] > 0
    assert row["node_store"] in ("native", "numpy")
    assert row["setup_s"] > 0 and row["run_ms"] > 0


def test_main_without_a_chip_fails_and_says_why():
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=str(REPO), env=_child_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout


# -- Stage A's verdict over doctored observations ------------------------------

_SPAN = {"count": 1, "fallback": False, "timed_out": False, "first_s": 0.1}
GOOD = {
    "healthz": {
        "status": "ok",
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                   "count": 1},
        "device_guard": {"state": "closed", "fallback_calls": 0,
                         "timeouts": 0, "bad_results": 0, "retried": 0}},
    "metrics": ('allocate_fused_taken_total{mode="pallas"} 9.0\n'
                "arena_full_rebuild_total 1.0\n"
                "arena_scatter_rows 25.0\n"
                "usage_decay_dispatch_total 10.0\n"),
    "kernel_spans": {label: dict(_SPAN) for label in (
        "fair_share", "arena_static_upload", "arena_state_upload",
        "arena_scatter", "allocate_grouped", "allocate_jobs",
        "allocate_bulk", "score_nodes", "rank_place",
        "scenario_prescreen")},
    "daemon_rc": 0,
}


def _doctored(**edits):
    obs = copy.deepcopy(GOOD)
    for path, value in edits.items():
        node = obs
        *parents, leaf = path.split("/")
        for key in parents:
            node = node[key]
        node[leaf] = value
    return obs


def test_check_daemon_accepts_a_clean_run():
    cs.check_daemon(GOOD, "tpu", "pallas")


@pytest.mark.parametrize("edits, says", [
    ({"healthz/device_guard/fallback_calls": 1}, "fallback_calls"),
    ({"healthz/device_guard/timeouts": 2}, "timeouts"),
    ({"healthz/device_guard/state": "open"}, "breaker"),
    ({"healthz/status": "degraded"}, "status"),
    ({"healthz/device/platform": "cpu"}, "no accelerator"),
    ({"daemon_rc": 1}, "daemon exited 1"),
    ({"kernel_spans/rank_place/fallback": True}, "rank_place"),
    ({"kernel_spans/fair_share/timed_out": True}, "fair_share"),
])
def test_check_daemon_rejects(edits, says):
    with pytest.raises(cs.SmokeFailure, match=says):
        cs.check_daemon(_doctored(**edits), "tpu", "pallas")


def test_a_missing_kernel_label_fails():
    obs = _doctored()
    del obs["kernel_spans"]["scenario_prescreen"]
    with pytest.raises(cs.SmokeFailure, match="scenario_prescreen"):
        cs.check_daemon(obs, "tpu", "pallas")


def test_either_member_fills_the_multi_or_bulk_slot():
    obs = _doctored()
    obs["kernel_spans"]["allocate_jobs_multi"] = \
        obs["kernel_spans"].pop("allocate_bulk")
    cs.check_daemon(obs, "tpu", "pallas")
    del obs["kernel_spans"]["allocate_jobs_multi"]
    with pytest.raises(cs.SmokeFailure, match="allocate_jobs_multi"):
        cs.check_daemon(obs, "tpu", "pallas")


def test_the_wrong_rung_or_a_second_rebuild_fails():
    with pytest.raises(cs.SmokeFailure, match="allocate_fused_taken"):
        cs.check_daemon(GOOD, "tpu", "jnp")
    obs = _doctored(metrics=GOOD["metrics"].replace(
        "arena_full_rebuild_total 1.0", "arena_full_rebuild_total 2.0"))
    with pytest.raises(cs.SmokeFailure, match="arena_full_rebuild"):
        cs.check_daemon(obs, "tpu", "pallas")


def test_host_checks_catch_overcommit_and_split_gangs():
    requests = {"a": (1.0, 1.0, 8.0), "b": (1.0, 1.0, 1.0)}
    assert cs.check_capacity(requests, {"a": "n00000", "b": "n00001"}) == 2
    with pytest.raises(cs.SmokeFailure, match="over capacity"):
        cs.check_capacity(requests, {"a": "n00000", "b": "n00000"})
    cs.check_racks({"g": ["a", "b"]}, {"a": "n00001", "b": "n00009"}, 8)
    with pytest.raises(cs.SmokeFailure, match="spans racks"):
        cs.check_racks({"g": ["a", "b"]},
                       {"a": "n00001", "b": "n00002"}, 8)


# -- Stage B, tiny -------------------------------------------------------------

def test_stage_b_grouped_pallas_rung_agrees_with_jnp():
    row = cs.stage_b_grouped(512, 8, 16, fused_mode="pallas")
    assert row["rung"] == "pallas" and row["platform"] == "cpu"
    assert row["placed"] == 128 and row["rung_mismatches"] == 0


def test_stage_b_grouped_auto_is_jnp_off_tpu():
    assert cs.stage_b_grouped(256, 4, 8)["rung"] == "jnp"


def test_stage_b_grouped_fails_when_the_fleet_is_too_small():
    with pytest.raises(cs.SmokeFailure, match="placed"):
        cs.stage_b_grouped(8, 8, 64)


def test_stage_b_tas_exact_and_fairshare():
    tas = cs.stage_b_tas((2, 8, 8), 16)
    assert tas["placed"] == 16 and tas["in_domain"] == 16
    assert cs.stage_b_exact(64, 16, 4)["placed"] == 64
    fair = cs.stage_b_fairshare(300)
    assert fair["max_abs_err"] <= fair["tolerance"]


def test_stage_b_prescreen_forms_agree_on_whole_quotients():
    row = cs.stage_b_prescreen(8)
    assert row["shape"] == {"prefixes": 8, "nodes": 9, "gang": 8,
                            "t_pad": 16, "rows": 32}
    assert (row["form_mismatches"], row["counted_low"],
            row["counted_high"]) == (0, 0, 0)


@pytest.mark.parametrize("off", (-1, 1), ids=("low", "high"))
def test_stage_b_prescreen_catches_a_count_one_off(monkeypatch, off):
    """What ROADMAP D12's division would do to an uncorrected count."""
    from kai_scheduler_tpu.ops import scenario_batch as sb
    monkeypatch.setattr(sb, "corrected_count",
                        lambda quotient, req, total: quotient + off)
    try:
        with pytest.raises(cs.SmokeFailure,
                           match="disagree on 4 of 8 prefixes"):
            cs.stage_b_prescreen(4)
    finally:
        sb.batch_prefix_feasibility.clear_cache()


def _fake_stage_b(monkeypatch, rc, stages):
    lines = "".join('{"stage": "%s", "platform": "tpu", "device_kind": '
                    '"k", "count": 1}\n' % s for s in stages)
    monkeypatch.setattr(
        cs.subprocess, "run",
        lambda *a, **kw: subprocess.CompletedProcess(a, rc, stdout=lines))


ALL_B = list(cs.STAGE_B)


def test_stage_b_child_nonzero_exit_fails(monkeypatch, capsys):
    _fake_stage_b(monkeypatch, 0, ALL_B)
    assert len(cs._run_stage_b()) == len(ALL_B)
    _fake_stage_b(monkeypatch, 1, ALL_B)
    with pytest.raises(cs.SmokeFailure, match="exited 1"):
        cs._run_stage_b()


def test_a_skipped_stage_fails(monkeypatch, capsys):
    _fake_stage_b(monkeypatch, 0, ALL_B[:-1])
    with pytest.raises(cs.SmokeFailure, match="expected"):
        cs._run_stage_b()


def test_stage_b_process_refuses_to_run_without_a_chip():
    with pytest.raises(cs.SmokeFailure, match="no accelerator"):
        cs.stage_b_main()


# -- the cache helper, the mesh ------------------------------------------------

def test_cache_helper_leaves_an_exported_directory_alone(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in dict(updates)


def test_cache_helper_defaults_to_the_checkout(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert dict(updates)["jax_compilation_cache_dir"] == want


def test_a_mesh_beyond_the_device_count_is_an_error():
    from kai_scheduler_tpu.framework.conf import SchedulerConfig
    from kai_scheduler_tpu.framework.session import Session
    from kai_scheduler_tpu.utils.cluster_spec import build_cluster

    cluster = build_cluster({"nodes": {"n0": {"gpu": 8}},
                             "queues": {"q": {}}, "jobs": {}})
    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"mesh of {too_many} devices"):
        Session(cluster, SchedulerConfig(mesh_devices=too_many))
