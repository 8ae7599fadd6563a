#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          (no arguments, no network, no git)

Drives the main path once, through the entry points a user calls, and
checks what comes out by the repo's own means.

* Stage A — the daemon answers real traffic.  An apiserver process (CPU
  only) and ``python -m kai_scheduler_tpu.server`` with its default
  flags, which owns the chip.  This process seeds a 2048-node fleet over
  ``HTTPKubeAPI`` and submits waves that between them make the daemon
  dispatch every guarded kernel label its default action list can reach,
  then asserts from the API and the daemon's own endpoints: every pod
  bound, no node over capacity when re-added in f64 on the host, guard
  counters zero, breaker closed, every kernel span ``fallback: false``.
* Stage B — the full-width kernels, in a second chip process started
  after the daemon has exited: the 98304-node x 1,048,576-pod grouped
  fill on the rung ``auto`` picks against the jnp rung, TAS at 65,536
  nodes, the exact kernel at 1024n x 2048 pods, the 10k-queue forest
  fair share against the sequential numpy reference, and the scenario
  prescreen counted against scanned on cpu quotients k x 4000 / 4000.

One process uses the chip at a time, and this parent never initialises a
JAX backend.  Every stage prints one JSON line naming the device, its
shape, ``setup_s`` (first call, compile included), ``run_ms`` (one warm
call that ends in ``block_until_ready``) and its counts — facts about
bring-up, not benchmark results.  The last line of standard output is
``{"ok": true, "device": {...}}``; without an accelerator the exit code
is non-zero and that line is not printed.  Nothing lets a failed check
continue, and there is no switch that turns the device check off.
"""

# kailint: disable-file=KAI002,KAI004 — Stage B calls the kernels and waits
# on them directly, outside the device guard, on purpose: the guard's CPU
# fallback is exactly what must not be able to hide the chip here.

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from dataclasses import asdict, dataclass

from kai_scheduler_tpu.controllers import HTTPKubeAPI, make_pod, owner_ref

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# The ten kernel labels the default action list can reach; a tuple is a
# slot either of its members fills.
KERNEL_LABELS = (
    "fair_share", "arena_static_upload", "arena_state_upload",
    "arena_scatter", "allocate_grouped", "allocate_jobs",
    ("allocate_jobs_multi", "allocate_bulk"), "score_nodes", "rank_place",
    "scenario_prescreen")

NODE_CPU, NODE_MEM_GI, NODE_GPU, NODE_PODS = 32, 256, 8, 110


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def _check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# Stage A: the daemon over HTTP
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FleetSize:
    """Sizes of the Stage A scenario.  The defaults are the full width;
    tier-1 runs the same code tiny."""
    nodes: int = 2048          # 4 x NODE_TILE: ``auto`` resolves to pallas
    racks: int = 64
    wave_jobs: int = 8         # PyTorchJobs per wave (cold wave, warm wave)
    wave_gang: int = 512
    hetero_gangs: int = 8      # 1 CPU master + 3 GPU workers each
    singles: int = 64          # >= bulk_allocation_threshold
    rack_gangs: int = 4
    rack_gang: int = 16
    half_gpu: int = 8
    rank_gangs: int = 2
    rank_gang: int = 32        # >= ops.rankplace._KERNEL_MIN_GANG
    starved_gangs: int = 2     # reclaimers; every pod needs a whole node
    starved_gang: int = 8
    cycles: int = 48           # the daemon's --cycles (the waves use ~11)
    wave_timeout_s: float = 420.0


class _Fleet:
    """The parent's view of the cluster: what it submitted, and the pod
    states the apiserver's watch stream reports back."""

    def __init__(self, api, size: FleetSize):
        self.api = api
        self.size = size
        self.requests: dict = {}   # pod name -> (cpu cores, mem Gi, gpu)
        self.node_of: dict = {}    # pod name -> node name (bound)
        self.deleting: set = set()
        self.evicted: set = set()   # deleted because the scheduler asked
        self.released: set = set()  # deleted because their work was done
        api.watch("Pod", self._on_pod)

    def _on_pod(self, event_type: str, obj: dict) -> None:
        name = obj["metadata"]["name"]
        if name not in self.requests:
            return  # the fleet's own pods (GPU-sharing reservations)
        if event_type == "DELETED":
            self.node_of.pop(name, None)
            self.deleting.discard(name)
            return
        node = obj.get("spec", {}).get("nodeName")
        if node:
            self.node_of[name] = node
        if obj["metadata"].get("deletionTimestamp"):
            self.deleting.add(name)

    # -- submissions -------------------------------------------------------
    def submit(self, pods: list, cpu=1.0, mem_gi=1.0, gpu=0.0) -> list:
        """Create ``pods`` in bulk chunks; returns their names."""
        for lo in range(0, len(pods), 500):
            outcomes = self.api.create_many(pods[lo:lo + 500])
            bad = [o for o in outcomes if not o.get("ok", True)]
            _check(not bad, f"pod create failed: {bad[:2]}")
        names = [p["metadata"]["name"] for p in pods]
        for name in names:
            self.requests[name] = (cpu, mem_gi, gpu)
        return names

    def seed_cluster(self) -> None:
        s = self.size
        nodes = [{"kind": "Node",
                  "metadata": {"name": f"n{i:05d}",
                               "labels": {"zone": f"z{i % 8}",
                                          "rack": f"r{i % s.racks}"}},
                  "spec": {},
                  "status": {"allocatable": {
                      "cpu": str(NODE_CPU), "memory": f"{NODE_MEM_GI}Gi",
                      "nvidia.com/gpu": NODE_GPU, "pods": NODE_PODS}}}
                 for i in range(s.nodes)]
        for lo in range(0, len(nodes), 500):
            self.api.create_many(nodes[lo:lo + 500])
        self.api.create({"kind": "Topology", "metadata": {"name": "dc"},
                         "spec": {"levels": [{"nodeLabel": "zone"},
                                             {"nodeLabel": "rack"}]}})
        for q in range(8):
            self.api.create({"kind": "Queue",
                             "metadata": {"name": f"fq{q}"}, "spec": {}})
        # The reclaim pair: ``hog`` may hold one node's GPUs by right,
        # ``starved`` everything its gangs ask for.
        starved_gpu = s.starved_gangs * s.starved_gang * NODE_GPU
        generous = {"cpu": str(NODE_CPU * s.nodes),
                    "memory": f"{NODE_MEM_GI * s.nodes}Gi"}
        self.api.create({"kind": "Queue", "metadata": {"name": "hog"},
                         "spec": {"deserved": dict(generous, gpu=NODE_GPU)}})
        self.api.create({"kind": "Queue", "metadata": {"name": "starved"},
                         "spec": {"deserved": dict(generous,
                                                   gpu=starved_gpu)}})

    def _owner(self, kind: str, name: str, queue: str, api_version="v1",
               annotations: dict | None = None, spec: dict | None = None):
        self.api.create({
            "kind": kind, "apiVersion": api_version,
            "metadata": {"name": name, "uid": f"{name}-uid",
                         "labels": {"kai.scheduler/queue": queue},
                         "annotations": dict(annotations or {})},
            "spec": dict(spec or {})})
        return owner_ref(kind, name, uid=f"{name}-uid",
                              api_version=api_version)

    def wave_pytorch(self, wave: int) -> list:
        """``wave_jobs`` PyTorchJobs x ``wave_gang`` workers, as
        bench.fleet_phase submits them: homogeneous gangs under the bulk
        threshold, so each goes through ``allocate_grouped``."""
        s, names = self.size, []
        for j in range(s.wave_jobs):
            job = f"w{wave}-j{j}"
            ref = self._owner(
                "PyTorchJob", job, f"fq{j % 8}", "kubeflow.org/v1",
                spec={"pytorchReplicaSpecs": {
                    "Worker": {"replicas": s.wave_gang}}})
            gpu = 1 if j % 2 == 0 else 0
            names += self.submit([make_pod(
                f"{job}-worker-{k:04d}", owner=ref, gpu=gpu,
                labels={"training.kubeflow.org/replica-type": "worker"})
                for k in range(s.wave_gang)], gpu=gpu)
        return names

    def wave_hetero(self) -> list:
        """A CPU master and three GPU workers in one gang: not
        homogeneous, so the exact kernel (``allocate_jobs``)."""
        names = []
        for j in range(self.size.hetero_gangs):
            job = f"het-{j}"
            ref = self._owner(
                "PyTorchJob", job, f"fq{j % 8}", "kubeflow.org/v1",
                spec={"pytorchReplicaSpecs": {"Master": {"replicas": 1},
                                              "Worker": {"replicas": 3}}})
            names += self.submit([make_pod(
                f"{job}-master-0", owner=ref, cpu="2",
                labels={"training.kubeflow.org/replica-type": "master"})],
                cpu=2.0)
            names += self.submit([make_pod(
                f"{job}-worker-{k}", owner=ref, gpu=1,
                labels={"training.kubeflow.org/replica-type": "worker"})
                for k in range(3)], gpu=1)
        return names

    def wave_singles(self) -> list:
        """One-pod jobs at the bulk threshold: ``allocate_bulk``."""
        return self.submit([make_pod(
            f"single-{i:04d}", queue=f"fq{i % 8}", gpu=1)
            for i in range(self.size.singles)], gpu=1)

    def wave_rack_required(self) -> list:
        """Gangs that must land inside one rack: the grouped kernel's
        mask-row variant."""
        s, names = self.size, []
        for j in range(s.rack_gangs):
            job = f"rack-{j}"
            ref = self._owner("Job", job, f"fq{j % 8}", "batch/v1", {
                "kai.scheduler/min-available": str(s.rack_gang),
                "kai.scheduler/topology": "dc",
                "kai.scheduler/topology-required-placement": "rack"})
            names += self.submit([make_pod(
                f"{job}-p{k:03d}", owner=ref, gpu=1)
                for k in range(s.rack_gang)], gpu=1)
        return names

    def wave_half_gpu(self) -> list:
        """Fractional pods take the host sharing path, which scores
        nodes on the device (``score_nodes``)."""
        return self.submit([make_pod(
            f"half-{i:03d}", queue=f"fq{i % 8}",
            annotations={"gpu-fraction": "0.5"})
            for i in range(self.size.half_gpu)], gpu=0.5)

    def wave_ranked(self) -> list:
        """Rank-annotated gangs on ``rank_place``'s kernel rung."""
        s, names = self.size, []
        for j in range(s.rank_gangs):
            job = f"mpi-{j}"
            ref = self._owner("Job", job, f"fq{j % 8}", "batch/v1", {
                "kai.scheduler/min-available": str(s.rank_gang)})
            names += self.submit([make_pod(
                f"{job}-r{k:03d}", owner=ref, gpu=2,
                annotations={"kai.scheduler/rank": str(k)})
                for k in range(s.rank_gang)], gpu=2)
        return names

    def wave_hogs(self) -> list:
        """Whole-node pods of queue ``hog``, one per node: the cluster's
        GPUs are full (the reclaim-contention shape of
        tools/scale_gen.py, with nodes for GPUs)."""
        return self.submit([make_pod(f"hog-{i:05d}", queue="hog",
                                          gpu=NODE_GPU)
                            for i in range(self.size.nodes)], gpu=NODE_GPU)

    def wave_starved(self) -> list:
        """Gangs of queue ``starved`` whose every pod needs a whole node:
        one victim frees room for one pod, so the first scenario fails
        and the solver reaches the lazy prescreen."""
        s, names = self.size, []
        for j in range(s.starved_gangs):
            job = f"starved-{j}"
            ref = self._owner("Job", job, "starved", "batch/v1", {
                "kai.scheduler/min-available": str(s.starved_gang)})
            names += self.submit([make_pod(
                f"{job}-p{k:02d}", owner=ref, gpu=NODE_GPU)
                for k in range(s.starved_gang)], gpu=NODE_GPU)
        return names

    def finalize_evictions(self) -> None:
        """The kubelet's part: a pod the scheduler marked for deletion
        goes away, which frees what the reclaimer was pipelined onto."""
        for name in sorted(self.deleting - self.evicted):
            self.evicted.add(name)
            self.api.delete("Pod", name)

    def release(self, names) -> None:
        """The pods' work is done: delete them, freeing their nodes."""
        for name in names:
            if name in self.node_of and name not in self.evicted:
                self.released.add(name)
                self.api.delete("Pod", name)

    def unbound(self, names) -> list:
        return [n for n in names if n not in self.node_of]


def check_capacity(requests: dict, node_of: dict) -> int:
    """Re-add every bound pod's request per node in f64 on the host (the
    device decided in f32) against the node's allocatable; returns the
    number of nodes holding pods."""
    import numpy as np
    used: dict = {}
    for name, node in node_of.items():
        cpu, mem, gpu = requests[name]
        row = used.setdefault(node, np.zeros(4, np.float64))
        row += (cpu, mem, gpu, 1.0)
    limit = np.array([NODE_CPU, NODE_MEM_GI, NODE_GPU, NODE_PODS],
                     np.float64)
    over = {node: row.tolist() for node, row in used.items()
            if np.any(row > limit + 1e-9)}
    _check(not over, f"nodes over capacity (cpu, memGi, gpu, pods): "
                     f"{dict(list(over.items())[:3])}")
    return len(used)


def check_racks(rack_gang_pods: dict, node_of: dict, racks: int) -> None:
    """Every rack-required gang sits inside one rack."""
    for job, pods in rack_gang_pods.items():
        got = {int(node_of[p][1:]) % racks for p in pods}
        _check(len(got) == 1, f"gang {job} spans racks {sorted(got)}")


def _metric(text: str, name: str, **labels) -> float:
    """Sum of the samples of ``name`` (with ``labels``) in a Prometheus
    text page; 0 when absent."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        head, _, value = line.rpartition(" ")
        base, _, rest = head.partition("{")
        if base != name:
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total += float(value)
    return total


def check_device(health: dict, expect_platform: str | None) -> dict:
    """The device ``/healthz`` names, which must be ``expect_platform``
    (``None`` accepts any)."""
    device = health.get("device") or {}
    _check(device.get("platform") and device.get("device_kind")
           and device.get("count"), f"/healthz names no device: {device}")
    _check(expect_platform in (None, device["platform"]),
           f"no accelerator: the daemon's JAX found platform "
           f"{device['platform']!r} ({device['device_kind']}), "
           f"not {expect_platform!r}")
    return device


def check_daemon(obs: dict, expect_platform: str | None,
                 fused_mode: str) -> None:
    """Everything Stage A asserts from the daemon's own endpoints and its
    exit, over what was observed (``obs``): raises SmokeFailure naming
    the first miss."""
    health = obs["healthz"]
    check_device(health, expect_platform)
    _check(health.get("status") == "ok",
           f"/healthz status {health.get('status')!r}")
    guard = health["device_guard"]
    _check(guard["state"] == "closed", f"breaker {guard['state']}")
    for key in ("fallback_calls", "timeouts", "bad_results"):
        _check(guard[key] == 0, f"device guard {key} = {guard[key]}")

    metrics = obs["metrics"]
    _check(_metric(metrics, "allocate_fused_taken_total",
                   mode=fused_mode) > 0,
           f"allocate_fused_taken_total{{mode={fused_mode!r}}} is 0")
    _check(_metric(metrics, "arena_full_rebuild_total") == 1,
           f"arena_full_rebuild_total = "
           f"{_metric(metrics, 'arena_full_rebuild_total')}, not 1")
    for name in ("arena_scatter_rows", "usage_decay_dispatch_total"):
        _check(_metric(metrics, name) > 0, f"{name} is 0")

    spans = obs["kernel_spans"]
    for slot in KERNEL_LABELS:
        names = slot if isinstance(slot, tuple) else (slot,)
        _check(any(n in spans for n in names),
               f"kernel label {'|'.join(names)} never dispatched "
               f"(saw {sorted(spans)})")
    for label, rec in spans.items():
        _check(not rec["fallback"] and not rec["timed_out"],
               f"kernel span {label}: fallback={rec['fallback']} "
               f"timed_out={rec['timed_out']}")
    _check(obs["daemon_rc"] == 0,
           f"daemon exited {obs['daemon_rc']} (log: {obs.get('log')})")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float = 30.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def _wait_http(url: str, proc, what: str, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _check(proc.poll() is None, f"{what} exited {proc.returncode} "
                                    "before serving")
        try:
            _get(url, timeout=2.0)
            return
        except (urllib.error.URLError, OSError):
            time.sleep(0.2)
    raise SmokeFailure(f"{what} did not serve {url} within {timeout:.0f}s")


class _TraceCollector:
    """Union of the kernel spans over /debug/trace, collected as the
    waves land (the daemon's ring keeps 32 cycles)."""

    def __init__(self, base_url: str):
        self.base = base_url
        self.seen: set = set()
        self.spans: dict = {}
        self.last_cycle = -1

    def poll(self) -> None:
        cycles = json.loads(_get(self.base + "/debug/cycles"))["cycles"]
        for summary in sorted(cycles, key=lambda c: c["cycle"]):
            trace_id = summary["trace_id"]
            if trace_id in self.seen:
                continue
            self.seen.add(trace_id)
            self.last_cycle = max(self.last_cycle, summary["cycle"])
            if not summary["spans"].get("kernel"):
                continue
            trace = json.loads(_get(
                f"{self.base}/debug/trace?cycle={trace_id}"))
            for ev in trace["traceEvents"]:
                label = ev["args"].get("kernel")
                if ev["cat"] != "kernel" or not label:
                    continue
                rec = self.spans.setdefault(label, {
                    "count": 0, "fallback": False, "timed_out": False,
                    "first_s": round(ev["dur"] / 1e6, 3)})
                rec["count"] += 1
                rec["fallback"] |= bool(ev["args"].get("fallback"))
                rec["timed_out"] |= bool(ev["args"].get("timed_out"))


def _stop(proc) -> None:
    if proc is not None and proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


def stage_a(size: FleetSize, fused_mode: str,
            expect_platform: str | None = "tpu", out_dir: str = OUT_DIR,
            env: dict | None = None) -> dict:
    """Run the daemon against real traffic; returns the stage's row.

    ``fused_mode`` is the grouped-kernel rung the daemon must report
    having taken and ``expect_platform`` the platform it must name
    (``None`` accepts any — tier-1's CPU run); ``env`` is the children's
    environment (``KAI_*`` variables never reach the daemon)."""
    os.makedirs(out_dir, exist_ok=True)
    base_env = dict(os.environ if env is None else env)
    base_env["PYTHONPATH"] = ROOT + os.pathsep \
        + base_env.get("PYTHONPATH", "")
    api_port, http_port = _free_port(), _free_port()
    api_url = f"http://127.0.0.1:{api_port}"
    daemon_url = f"http://127.0.0.1:{http_port}"
    journal = os.path.join(out_dir, "bind.journal")
    if os.path.exists(journal):
        os.unlink(journal)
    log_path = os.path.join(out_dir, "daemon.log")
    apiserver = daemon = api = None
    t_stage = time.monotonic()
    try:
        with open(os.path.join(out_dir, "apiserver.log"), "w") as api_log:
            apiserver = subprocess.Popen(
                [sys.executable, "-m",
                 "kai_scheduler_tpu.controllers.apiserver",
                 "--port", str(api_port)],
                cwd=ROOT, env=dict(base_env, JAX_PLATFORMS="cpu"),
                stdout=api_log, stderr=subprocess.STDOUT)
        _wait_http(api_url + "/digest", apiserver, "apiserver", 60.0)
        api = HTTPKubeAPI(api_url)
        fleet = _Fleet(api, size)
        # Nodes, queues and the topology exist before the daemon's first
        # snapshot, so its arena builds once.
        fleet.seed_cluster()

        daemon_env = {k: v for k, v in base_env.items()
                      if not k.startswith("KAI_")}
        with open(log_path, "w") as daemon_log:
            daemon = subprocess.Popen(
                [sys.executable, "-m", "kai_scheduler_tpu.server",
                 "--api-server", api_url, "--http-port", str(http_port),
                 "--leader-elect", "--commit-log", journal,
                 "--usage-db", "memory://", "--schedule-period", "0.2",
                 "--cycles", str(size.cycles)],
                cwd=ROOT, env=daemon_env, stdout=daemon_log,
                stderr=subprocess.STDOUT)
        _wait_http(daemon_url + "/healthz", daemon, "daemon", 180.0)
        # Before any traffic: a daemon that came up without the chip
        # fails the smoke now, not after the waves ran on the CPU.
        check_device(json.loads(_get(daemon_url + "/healthz")),
                     expect_platform)
        traces = _TraceCollector(daemon_url)

        def land(what: str, names: list, tick=None) -> float:
            """Wait until every pod of ``names`` is bound; seconds."""
            t0 = time.monotonic()
            next_poll = 0.0
            while True:
                api.drain()
                if tick is not None:
                    tick()
                left = fleet.unbound(names)
                now = time.monotonic()
                if now >= next_poll or not left:
                    traces.poll()
                    next_poll = now + 1.0
                if not left:
                    return now - t0
                _check(daemon.poll() is None,
                       f"daemon exited {daemon.returncode} (after cycle "
                       f"{traces.last_cycle} of {size.cycles}) before "
                       f"{what} landed; {len(left)} pods unbound; "
                       f"log: {log_path}")
                _check(now - t0 < size.wave_timeout_s,
                       f"{what}: {len(left)} of {len(names)} pods still "
                       f"unbound after {size.wave_timeout_s:.0f}s "
                       f"(e.g. {left[:3]}); log: {log_path}")
                time.sleep(0.05)

        timings = {}
        # Reclaim first, while the hogs are the only running work: they
        # fill every node, then the starved gangs arrive and must evict.
        hogs = fleet.wave_hogs()
        timings["hogs"] = land("hog fill", hogs)
        starved = fleet.wave_starved()
        timings["reclaim"] = land("reclaim", starved,
                                  tick=fleet.finalize_evictions)
        # The hogs finish; the fleet is free for the placement waves.
        fleet.release(hogs)
        cold = fleet.wave_pytorch(1)
        timings["cold_wave"] = land("cold wave", cold)
        warm = fleet.wave_pytorch(2)
        timings["warm_wave"] = land("warm wave", warm)
        timings["hetero"] = land("heterogeneous gangs", fleet.wave_hetero())
        timings["singles"] = land("single-pod jobs", fleet.wave_singles())
        rack = fleet.wave_rack_required()
        timings["rack"] = land("rack-required gangs", rack)
        timings["half_gpu"] = land("half-GPU pods", fleet.wave_half_gpu())
        timings["ranked"] = land("rank-annotated gangs", fleet.wave_ranked())

        # Everything that should be bound is, and nothing else.
        api.drain()
        expected = len(fleet.requests) - len(fleet.evicted) \
            - len(fleet.released)
        _check(len(fleet.evicted) == len(starved),
               f"{len(fleet.evicted)} hogs evicted for {len(starved)} "
               "starved pods")
        _check(not (fleet.evicted - set(hogs)),
               "a pod outside the hog wave was evicted")
        _check(len(fleet.node_of) == expected,
               f"{len(fleet.node_of)} pods bound, expected {expected}")
        listed = sum(1 for p in api.list("Pod")
                     if p["spec"].get("nodeName")
                     and p["metadata"]["name"] in fleet.requests)
        _check(listed == expected,
               f"the API lists {listed} bound pods, expected {expected}")
        nodes_used = check_capacity(fleet.requests, fleet.node_of)
        gangs: dict = {}
        for name in rack:
            gangs.setdefault(name.rsplit("-p", 1)[0], []).append(name)
        check_racks(gangs, fleet.node_of, size.racks)

        traces.poll()
        cycles_used = traces.last_cycle
        obs = {"healthz": json.loads(_get(daemon_url + "/healthz")),
               "metrics": _get(daemon_url + "/metrics").decode(),
               "kernel_spans": traces.spans, "log": log_path}
        # The daemon leaves by itself, after its N cycles.
        try:
            obs["daemon_rc"] = daemon.wait(
                timeout=size.cycles * 2.0 + 120.0)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"daemon still running after its {size.cycles} cycles "
                f"should have ended; log: {log_path}") from None
        check_daemon(obs, expect_platform, fused_mode)
    finally:
        if api is not None:
            api.close()
        _stop(daemon)
        _stop(apiserver)

    health, metrics = obs["healthz"], obs["metrics"]
    return {
        "stage": "A", **health["device"],
        "shape": asdict(size),
        "setup_s": round(timings["cold_wave"], 2),
        "run_ms": round(timings["warm_wave"] * 1e3, 1),
        "wave_s": {k: round(v, 2) for k, v in timings.items()},
        "stage_s": round(time.monotonic() - t_stage, 1),
        "pods_bound": expected, "evicted": len(fleet.evicted),
        "nodes_used": nodes_used,
        "node_store": health.get("node_store"),
        "fused_taken": {m: _metric(metrics, "allocate_fused_taken_total",
                                   mode=m) for m in ("pallas", "jnp")},
        "cycles_traced": len(traces.seen),
        "cycles_at_last_wave": cycles_used,
        "first_dispatch_s": {k: v["first_s"]
                             for k, v in sorted(traces.spans.items())},
        "dispatches": {k: v["count"]
                       for k, v in sorted(traces.spans.items())},
        "guard": {k: health["device_guard"][k]
                  for k in ("state", "fallback_calls", "timeouts",
                            "bad_results", "retried")},
    }


# ---------------------------------------------------------------------------
# Stage B: the full-width kernels (each function runs in the chip process)
# ---------------------------------------------------------------------------

def _device() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind, "count": len(devices)}


def _fused_taken() -> dict:
    """``allocate_grouped`` dispatches so far in this process, by rung."""
    from kai_scheduler_tpu.utils.metrics import METRICS
    return {mode: METRICS.counters.get(
        f'allocate_fused_taken_total{{mode="{mode}"}}', 0.0)
        for mode in ("pallas", "jnp")}


def _rung_since(before: dict) -> str:
    """The one rung every dispatch since ``before`` resolved to."""
    moved = [mode for mode, n in _fused_taken().items() if n > before[mode]]
    _check(len(moved) == 1,
           f"dispatches since {before} resolved to rungs {moved}")
    return moved[0]


def _host_capacity_check(idle0, room0, req, placements) -> None:
    """f64 re-add of a kernel's placements against the idle table."""
    import numpy as np
    placed = placements >= 0
    used = np.zeros(idle0.shape, np.float64)
    np.add.at(used, placements[placed], req[placed].astype(np.float64))
    pods = np.bincount(placements[placed], minlength=idle0.shape[0])
    over = int(np.any(used > idle0.astype(np.float64) + 1e-6, axis=1).sum())
    over_room = int((pods > room0).sum())
    _check(over == 0 and over_room == 0,
           f"{over} nodes over resource capacity, {over_room} over pod room")


def stage_b_grouped(n_nodes=98304, n_jobs=1024, gang=1024,
                    fused_mode: str | None = None) -> dict:
    """Grouped fill on the rung ``auto`` picks (``fused_mode`` pins one:
    tier-1 runs Pallas in interpret mode) against the jnp rung."""
    import jax
    import numpy as np

    import bench
    from kai_scheduler_tpu.ops import allocate_grouped as ag

    args = bench.build_arrays(n_nodes, n_jobs, gang, seed=0, placeable=True)
    nodes, tasks, allowed = args[:6], args[6:10], args[10]
    taken = _fused_taken()
    t0 = time.perf_counter()
    out = ag.allocate_grouped(nodes, *tasks, allowed, fused_mode=fused_mode)
    setup_s = time.perf_counter() - t0
    rung = _rung_since(taken)
    t0 = time.perf_counter()
    warm = ag.allocate_grouped(nodes, *tasks, allowed,
                               fused_mode=fused_mode)
    jax.block_until_ready((warm.node_idle, warm.node_releasing))
    run_ms = (time.perf_counter() - t0) * 1e3

    placements = np.asarray(out.placements)
    placed = int((placements >= 0).sum())
    _check(placed == n_jobs * gang,
           f"grouped fill placed {placed} of {n_jobs * gang}")
    _host_capacity_check(np.asarray(nodes[1]), np.asarray(nodes[5]),
                         np.asarray(tasks[0]), placements)
    ref = ag.allocate_grouped(nodes, *tasks, allowed, fused_mode="jnp")
    differ = int((placements != np.asarray(ref.placements)).sum()
                 + (np.asarray(out.pipelined)
                    != np.asarray(ref.pipelined)).sum())
    _check(differ == 0,
           f"rung {rung!r} and rung 'jnp' disagree on {differ} tasks")
    return {**_device(),
            "shape": {"nodes": n_nodes, "pods": n_jobs * gang,
                      "gang": gang},
            "setup_s": round(setup_s, 2), "run_ms": round(run_ms, 2),
            "rung": rung, "placed": placed, "rung_mismatches": differ}


def stage_b_tas(dims=(16, 64, 64), gang=1024) -> dict:
    import numpy as np

    import bench

    taken = _fused_taken()
    row = bench.tas_phase(dims, gang, iters=1)
    _check(row["pods_placed"] == gang and row["pods_in_domain"] == gang,
           f"TAS placed {row['pods_placed']} of {gang}, "
           f"{row['pods_in_domain']} inside the chosen domain")
    return {**_device(),
            "shape": {"nodes": int(np.prod(dims)), "dims": list(dims),
                      "gang": gang},
            "setup_s": row["compile_s"], "run_ms": row["cycle_ms"],
            "rung": _rung_since(taken), "placed": row["pods_placed"],
            "in_domain": row["pods_in_domain"]}


def stage_b_exact(n_nodes=1024, n_jobs=512, gang=4) -> dict:
    import jax
    import numpy as np

    import bench
    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel

    args = bench.build_arrays(n_nodes, n_jobs, gang, seed=0)
    t0 = time.perf_counter()
    out = allocate_jobs_kernel(*args)
    placements = np.asarray(out.placements)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(allocate_jobs_kernel(*args).placements)
    run_ms = (time.perf_counter() - t0) * 1e3
    placed = int((placements >= 0).sum())
    _check(placed == n_jobs * gang,
           f"exact kernel placed {placed} of {n_jobs * gang}")
    _host_capacity_check(np.asarray(args[1]), np.asarray(args[5]),
                         np.asarray(args[6]), placements)
    return {**_device(),
            "shape": {"nodes": n_nodes, "pods": n_jobs * gang,
                      "gang": gang},
            "setup_s": round(setup_s, 2), "run_ms": round(run_ms, 2),
            "placed": placed}


# Forest fair share runs in f32 on the chip against an f64 sequential
# reference: shares are sums and products of O(10k) terms at magnitudes
# up to ``total`` = 2e5, so the bound is stated relative to ``total``.
FAIRSHARE_RTOL = 1e-5


def stage_b_fairshare(n_queues=10000, bands=1) -> dict:
    import numpy as np

    import bench
    from kai_scheduler_tpu.ops import fairshare as fs

    inst = bench.fairshare_inputs(n_queues, bands=bands, seed=0)
    prep = fs.prepared_forest(inst["parent"], inst["priority"],
                              inst["creation"], inst["uids"],
                              inst["deserved"], inst["limit"], inst["oqw"])

    def forest():
        return np.asarray(fs.fair_share_forest(
            inst["total"], 1.0, prep, inst["request"], inst["usage"]))

    t0 = time.perf_counter()
    got = forest()
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    forest()  # ends in the host fetch of the shares
    run_ms = (time.perf_counter() - t0) * 1e3

    # The sequential reference, one sibling group at a time, top down.
    hier = fs.QueueHierarchy.build(inst["parent"], inst["priority"],
                                   inst["creation"], inst["uids"])
    want = np.zeros_like(inst["deserved"], dtype=np.float64)
    for depth, idxs in enumerate(hier.levels):
        parents = hier.parent[idxs]
        for p in np.unique(parents):
            m = idxs[parents == p]
            pool = inst["total"] if depth == 0 else want[p]
            want[m] = fs.set_resources_share_np(
                pool, 1.0, inst["deserved"][m], inst["limit"][m],
                inst["oqw"][m], inst["request"][m], inst["usage"][m],
                inst["priority"][m], hier.tiebreak_rank[m])
    _check(got.shape == want.shape and bool(np.isfinite(got).all()),
           f"fair share shape {got.shape} / non-finite values")
    err = float(np.abs(got - want).max())
    bound = FAIRSHARE_RTOL * float(inst["total"].max())
    _check(err <= bound,
           f"forest fair share off the numpy reference by {err:.4g} "
           f"(> {bound:.4g})")
    return {**_device(),
            "shape": {"queues": n_queues, "bands": bands,
                      "levels": len(hier.levels)},
            "setup_s": round(setup_s, 2), "run_ms": round(run_ms, 2),
            "max_abs_err": err, "tolerance": bound}


def stage_b_prescreen(quotients=512) -> dict:
    """The scenario prescreen's two forms on exact-integer cpu quotients.

    TPU f32 division hands ``floor(k * 4000 / 4000)`` back one low for
    some k (ROADMAP D12), and both forms divide.  Prefix k leaves room
    for exactly k pods on node k and ``quotients - k`` on node 0, so a
    gang of ``quotients`` fits every prefix only if both counts are
    exact, and one pod more fits none only if neither is high.  With one
    pod tolerating a taint no node carries the rows are a gang of two
    runs, which the kernel steps over with the count's capacity and the
    grouped fill between them; under a static mask that refuses no node
    either gang keeps its form (the mask row is one more row set of the
    same predicates): all four must give that answer bit for bit."""
    import numpy as np

    from kai_scheduler_tpu.ops.scenario_batch import (
        batch_prefix_feasibility, dispatched_form)

    q, cpu = quotients, 4000.0
    n = q + 1
    ks = np.arange(1, q + 1)
    idle = np.zeros((n, 3))
    idle[0, 0] = q * cpu
    nodes = (np.tile([n * cpu, 1.0, 1.0], (n, 1)), idle, np.zeros((n, 3)),
             np.full((n, 1), -1, np.int32), np.full((n, 1), -1, np.int32),
             np.full(n, float(q + 1)))
    # Prefix k (row k - 1): node k gains k pods' cpu, node k - 1 loses
    # what it had gained, node 0 loses one pod's.
    step = np.concatenate([ks - 1, ks[1:] - 1, ks - 1])
    node = np.concatenate([ks, ks[:-1], np.zeros(q, int)])
    amount = np.concatenate([ks * cpu, -ks[:-1] * cpu, np.full(q, -cpu)])
    m_pad = 1 << int(len(step) - 1).bit_length()
    release_step = np.full(m_pad, q, np.int32)
    release_step[:len(step)] = step
    release_node = np.zeros(m_pad, np.int32)
    release_node[:len(node)] = node
    release_vec = np.zeros((m_pad, 3))
    release_vec[:len(amount), 0] = amount
    t_pad = 1 << int(q).bit_length()         # room for quotients + 1 pods

    def verdict(gang: int, form: str, masked: bool = False):
        job = np.where(np.arange(t_pad) < gang, 0, 1).astype(np.int32)
        req = np.where((job == 0)[:, None], [cpu, 0.0, 0.0], 0.0)
        sel = np.full((t_pad, 1), -1, np.int32)
        tol = np.full((t_pad, 1), -1, np.int32)
        if form == "grouped":
            tol[0, 0] = 5
        mask = np.ones((t_pad, n), bool) if masked else None
        reads = dispatched_form(req, job, sel, tol, mask)
        _check(reads[0] == form, f"a gang made for the {form} form reads "
                                 f"{reads}")
        return np.asarray(batch_prefix_feasibility(
            *nodes, release_step, release_node, release_vec, req, job,
            sel, tol, num_prefixes=q, task_node_mask=mask))

    t0 = time.perf_counter()
    fits = verdict(q, "counted")
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    verdict(q, "counted")                    # ends in the host fetch
    run_ms = (time.perf_counter() - t0) * 1e3
    over = verdict(q + 1, "counted")
    differ = 0
    for form, masked in (("counted", False), ("grouped", False),
                         ("counted", True), ("grouped", True)):
        off = int((~verdict(q, form, masked)).sum()
                  + verdict(q + 1, form, masked).sum())
        _check(off == 0,
               f"{form}{' under a mask' * masked} and the exact answer "
               f"disagree on {off} of {2 * q} prefixes")
        differ += off
    low, high = int((~fits).sum()), int(over.sum())
    _check(low == 0 and high == 0,
           f"{low} prefixes counted a pod short, {high} a pod over")
    return {**_device(),
            "shape": {"prefixes": q, "nodes": n, "gang": q, "t_pad": t_pad,
                      "rows": m_pad},
            "setup_s": round(setup_s, 2), "run_ms": round(run_ms, 2),
            "form_mismatches": differ, "counted_low": low,
            "counted_high": high}


STAGE_B = {"B.grouped_fill": stage_b_grouped, "B.tas": stage_b_tas,
           "B.exact": stage_b_exact, "B.fair_share": stage_b_fairshare,
           "B.scenario_prescreen": stage_b_prescreen}


def stage_b_main() -> int:
    """The second chip process: every Stage B kernel at full width."""
    from kai_scheduler_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    device = _device()
    _check(device["platform"] == "tpu",
           f"no accelerator: JAX found platform {device['platform']!r}")
    for name, stage in STAGE_B.items():
        print(json.dumps({"stage": name, **stage()}), flush=True)
    return 0


def _run_stage_b() -> list:
    """Stage B as a child process; returns its rows (also echoed)."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--stage-b"], cwd=ROOT,
        stdout=subprocess.PIPE, text=True, timeout=1500)
    rows = []
    for line in proc.stdout.splitlines():
        print(line, flush=True)
        if line.startswith("{"):
            rows.append(json.loads(line))
    _check(proc.returncode == 0,
           f"Stage B process exited {proc.returncode}")
    done = [row["stage"] for row in rows]
    _check(done == list(STAGE_B),
           f"Stage B ran {done}, expected {list(STAGE_B)}")
    return rows


def main() -> int:
    t0 = time.monotonic()
    row_a = stage_a(FleetSize(), fused_mode="pallas")
    print(json.dumps(row_a), flush=True)
    rows = [row_a] + _run_stage_b()
    devices = {(r["platform"], r["device_kind"], r["count"]) for r in rows}
    _check(len(devices) == 1, f"stages ran on different devices: {devices}")
    platform, kind, count = devices.pop()
    _check(platform == "tpu", f"no accelerator: platform {platform!r}")
    from jax._src import xla_bridge
    _check(not xla_bridge.backends_are_initialized(),
           "the parent initialised a JAX backend")
    print(f"chip_smoke: all stages passed in {time.monotonic() - t0:.0f}s",
          file=sys.stderr)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(stage_b_main() if sys.argv[1:] == ["--stage-b"]
                 else main())
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        sys.exit(1)
