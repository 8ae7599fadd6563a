"""Scheduler server: leader election lease and endpoint handlers."""

import json
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

from kai_scheduler_tpu.server import LeaderElector


def test_daemon_cli_smoke(tmp_path):
    """The daemon binary end-to-end: bounded cycles over the embedded
    API with the profiler on, every HTTP surface serving REAL content
    (the cmd/scheduler/app/server.go RunApp smoke)."""
    from tests.fixtures import free_port

    port = free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "kai_scheduler_tpu.server",
         "--http-port", str(port), "--cycles", "400",
         "--schedule-period", "0.05", "--enable-profiler",
         "--lock-file", str(tmp_path / "lease.lock")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    def get(path, timeout=5):
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout).read()

    try:
        # The HTTP server comes up before the first cycle completes and
        # the latency histogram registers lazily at cycle end: poll for
        # the histogram, which also guarantees >=1 full cycle ran before
        # the content assertions below.
        deadline = time.monotonic() + 60
        cycled = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"daemon died rc={proc.returncode}: "
                    f"{proc.stdout.read()[-2000:]}")
            try:
                metrics = get("/metrics").decode()
                if "e2e_scheduling_latency_milliseconds" in metrics:
                    cycled = True
                    break
            except OSError:
                pass
            time.sleep(0.2)
        assert cycled, "daemon never completed a scheduling cycle"
        health = json.loads(get("/healthz"))
        assert health["status"] == "ok"  # no faults -> breaker closed
        assert health["device_guard"]["state"] == "closed"
        # Degraded observability is itself observable: lifecycle ring
        # occupancy + stackprof on/off state ride /healthz.
        obs = health["observability"]
        assert obs["lifecycle"]["ring_capacity"] >= 1
        assert obs["stackprof"]["running"] is True
        snap = json.loads(get("/get-snapshot"))
        assert snap.get("config", {}).get("actions"), snap.keys()
        assert "nodes" in snap
        order = json.loads(get("/job-order"))
        assert "order" in order
        prof = json.loads(get("/debug/profile?summary=1"))
        assert prof["total_samples"] > 0
        # Flight recorder: cycle summaries, a Chrome trace for the
        # latest cycle (root span + snapshot/plugin/action children on
        # an idle cluster), pprof folded stacks, and /explain discovery.
        cycles = json.loads(get("/debug/cycles"))
        assert cycles["capacity"] >= 1 and cycles["cycles"]
        latest = cycles["cycles"][0]
        assert latest["duration_ms"] >= 0 and not latest["aborted"]
        assert "cycle" in latest["spans"]
        # Fetch by id, not default-latest: the daemon is still cycling
        # every 50ms, so "latest" could move between the two requests.
        trace = json.loads(get(f"/debug/trace?cycle={latest['trace_id']}"))
        assert trace["otherData"]["trace_id"] == latest["trace_id"]
        assert trace["traceEvents"]
        cats = {e["cat"] for e in trace["traceEvents"]}
        assert {"cycle", "snapshot", "action"} <= cats
        explain = json.loads(get("/explain"))
        assert "podgroups" in explain  # empty cluster: nothing pending
        try:
            get("/explain?podgroup=nope")
            raise AssertionError("expected 404 for unknown podgroup")
        except urllib.error.HTTPError as e:
            assert e.code == 404
        assert get("/debug/pprof")  # profiler enabled: folded stacks
        # Latency observatory: the endpoint serves (an idle cluster has
        # no timelines, but status/pod_latency structure is present).
        latency = json.loads(get("/debug/latency"))
        assert "timelines" in latency and "pod_latency" in latency
        assert latency["status"]["ring_capacity"] >= 1
        # Continuous fleet profiler: --enable-profiler armed stackprof.
        deadline = time.monotonic() + 30
        flame = b""
        while time.monotonic() < deadline and not flame.strip():
            flame = get("/debug/flame")
            time.sleep(0.2)
        assert flame.strip(), "stackprof produced no folded stacks"
        assert b";" in flame  # stack;frames count lines
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_leader_election_excludes_second_instance(tmp_path):
    lock = str(tmp_path / "lease.lock")
    a = LeaderElector(lock)
    a.acquire()
    got_b = threading.Event()
    b = LeaderElector(lock)

    def contend():
        b.acquire(poll_seconds=0.05)
        got_b.set()

    t = threading.Thread(target=contend, daemon=True)
    t.start()
    time.sleep(0.3)
    assert not got_b.is_set()  # the lease holds
    a.release()
    assert got_b.wait(timeout=5.0)  # leadership transfers on release
    b.release()


def test_leader_elect_flag_accepts_explicit_value():
    """The chart renders --leader-elect={{ value }}; argparse must accept
    both the bare flag and an explicit true/false (ADVICE r2: store_true
    rejected the explicit form and crash-looped the pod)."""
    import argparse

    from kai_scheduler_tpu.server import _parse_bool

    ap = argparse.ArgumentParser()
    ap.add_argument("--leader-elect", nargs="?", const=True, default=False,
                    type=_parse_bool)
    assert ap.parse_args([]).leader_elect is False
    assert ap.parse_args(["--leader-elect"]).leader_elect is True
    assert ap.parse_args(["--leader-elect=true"]).leader_elect is True
    assert ap.parse_args(["--leader-elect=false"]).leader_elect is False
