"""Benchmark: full scheduling-cycle latency on the packed snapshot kernels.

Measures the device-side hot loop the reference runs as Go pointer-chasing
(predicate masks + score matrix + DRF fair share + sequential gang
allocation) as one jitted program, at BASELINE.md stepping-stone configs:

- primary: 1024 nodes x 2048 pending pods (512 gangs of 4, mixed
  requests/selectors) through the exact per-task kernel;
- large-gang: 98304 nodes x 1,048,576 pending pods (1024 gangs of 1024)
  through the grouped fill-plan kernel (ops/allocate_grouped.py) — the
  north-star scale of BASELINE.json on a single chip;
- host pipeline: the daemon's real cycle (snapshot -> session -> allocate
  action incl. statement application), host side included;
- tas-64k: topology-aware placement over a 64k-node 3D mesh (BASELINE
  config #4): per-level domain aggregation (segment sums) + a gang fill
  restricted to the chosen domain.

``python bench.py`` is the measurement, in one process, on whatever
backend JAX finds (every row names it).  It prints a COMPLETE JSON line
the moment the primary config is measured, then reprints an enriched line
as each later phase finishes; a phase that dies records an error and the
remaining phases still run, but the run then exits non-zero — as it does
when the device guard fell back to the CPU for any dispatch.  The final
line:
  {"metric": ..., "value": median_ms, "unit": "ms", "vs_baseline": ratio}
vs_baseline is measured against the repo's north-star cycle budget of
100ms (BASELINE.json: <100ms p99 @ 100k nodes / 1M pending); ratio > 1
means the cycle fits the budget at the primary config (the reference
publishes no absolute numbers to compare against — BASELINE.md).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from kai_scheduler_tpu.utils.compile_cache import enable_compile_cache

N_NODES = 1024
N_JOBS = 512
TASKS_PER_JOB = 4
N_QUEUES = 16
NORTH_STAR_MS = 100.0

# North-star-scale config (BASELINE.json): ~100k nodes / 1M pending pods.
BIG_NODES = 98304
BIG_JOBS = 1024
BIG_GANG = 1024

# Host-pipeline config (the full eager cycle, statements included).
PIPE_NODES, PIPE_JOBS, PIPE_GANG = 5000, 40, 500  # 20k pods

# TAS config (BASELINE config #4): 3D mesh 16x64x64 = 65536 nodes.
TAS_DIMS = (16, 64, 64)
TAS_GANG = 1024


def _log(msg):
    """Timestamped progress note on stderr (a tail of it shows where a
    run that died had got to)."""
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


def _span_seconds(trace, *names) -> float:
    """Seconds the spans called ``names`` took in one cycle's trace, by the
    flight recorder's totals by name (every span that closed, kept or
    dropped by the cap); 0.0 without a trace."""
    if trace is None:
        return 0.0
    return sum(trace.name_totals.get(name, (0, 0.0))[1] for name in names)


def build_arrays(n_nodes=N_NODES, n_jobs=N_JOBS, gang=TASKS_PER_JOB,
                 seed=0, placeable=False):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    alloc = np.tile([64000.0, 512e9, 8.0], (n_nodes, 1))
    idle = alloc.copy()
    idle[:, 2] -= rng.integers(0, 5, n_nodes)
    rel = np.zeros((n_nodes, 3))
    labels = np.full((n_nodes, 1), -1, np.int32)
    labels[:, 0] = rng.integers(0, 4, n_nodes)
    taints = np.full((n_nodes, 1), -1, np.int32)
    room = np.full(n_nodes, 110.0)

    n_tasks = n_jobs * gang
    task_job = np.repeat(np.arange(n_jobs, dtype=np.int32), gang)
    if placeable:
        # A demand the cluster can actually host (BENCH honesty: measuring
        # throughput on a >50%-infeasible workload muddies pods/sec): half
        # the gangs are 1-GPU trainers, half are CPU-only services, sized
        # within the cluster's idle GPU/CPU/memory pools.
        gpu_job = np.arange(n_jobs) % 2 == 0
        req = np.repeat(np.stack(
            [[1000.0, 4e9, 1.0 if gpu_job[j] else 0.0]
             for j in range(n_jobs)]), gang, axis=0)
        sel = np.full((n_tasks, 1), -1, np.int32)
    else:
        req = np.repeat(np.stack(
            [[1000.0, 4e9, float(rng.integers(1, 3))]
             for _ in range(n_jobs)]), gang, axis=0)
        sel = np.full((n_tasks, 1), -1, np.int32)
        constrained = rng.random(n_jobs) < 0.25
        job_sel = np.full(n_jobs, -1, np.int64)
        job_sel[constrained] = rng.integers(0, 4, constrained.sum())
        sel[:, 0] = np.repeat(job_sel, gang)
    tol = np.full((n_tasks, 1), -1, np.int32)
    job_allowed = np.ones(n_jobs, bool)
    return tuple(map(jnp.asarray, (
        alloc, idle, rel, labels, taints, room, req, task_job, sel, tol,
        job_allowed)))


def fleet_phase(n_nodes=2000, n_jobs=8, gang=100, waves=2,
                pipelined=False, substrate="memory"):
    """End-to-end fleet measurement with the latency observatory armed.

    Builds the full ``System`` (admission -> podgrouper -> scheduler ->
    binder -> status updater over the in-memory API), submits ``n_jobs``
    gang workloads per wave, and reports the ``pod_latency`` section the
    acceptance asks for: submit→bound p50/p99 and per-phase medians from
    the lifecycle tracker, measured on the WARM wave (the cold wave pays
    the XLA compiles; its number is reported separately), plus the
    continuous profiler's top busy frames — the host bottleneck by name.

    ``pipelined=True`` arms the overlapped cycle (DESIGN §10): commit
    I/O + binder round trips run on the commit-executor thread, so the
    measured ``warm_cycle_s`` is the main-thread cycle interval — the
    pipeline's real throughput period (the depth-1 token wait absorbs
    any commit-stage excess), reported alongside the achieved
    ``overlap_ratio``.

    ``substrate="http"`` runs the fleet against a real ``KubeAPIServer``
    over loopback HTTP — the daemon's production regime, where commit
    I/O is genuine network round trips the executor thread can overlap
    with host prep under the GIL.  On the in-memory store a write is
    microseconds of pure-Python work, so thread overlap is bounded by
    the interpreter lock and the A/B understates the pipeline.
    """
    from kai_scheduler_tpu.controllers import (System, SystemConfig,
                                               make_pod, owner_ref)
    from kai_scheduler_tpu.utils.lifecycle import LIFECYCLE
    from kai_scheduler_tpu.utils.stackprof import StackProfiler

    # The daemon-sized defaults (8192 open / 2048 ring) silently truncate
    # a 20k-pod TPU wave's stats AND break the bound-pods termination
    # check below: size the tracker to the wave, restore after.
    wave_pods = n_jobs * gang
    old_bounds = LIFECYCLE.configure_bounds(
        open_cap=max(8192, wave_pods * 2), ring=max(2048, wave_pods * 2))
    prof = StackProfiler(hz=97.0, max_stacks=8192)
    prof.start()
    # Everything from substrate construction on runs under the
    # try/finally: a failed HTTP create or System init must not leak
    # the loopback server + watch threads, the 97Hz sampler, or the
    # resized lifecycle bounds into the rest of the bench.
    server = client = system = None
    from kai_scheduler_tpu.utils import wireobs
    wire0 = wireobs.wire_totals()

    def submit_wave(wave):
        api = system.api
        create_many = getattr(api, "create_many", None)
        for j in range(n_jobs):
            name = f"fleet-w{wave}-j{j}"
            api.create({
                "kind": "PyTorchJob", "apiVersion": "kubeflow.org/v1",
                "metadata": {"name": name, "uid": f"{name}-uid",
                             "labels": {"kai.scheduler/queue":
                                        f"fq{j % 8}"}},
                "spec": {"pytorchReplicaSpecs": {
                    "Worker": {"replicas": gang}}}})
            ref = owner_ref("PyTorchJob", name, uid=f"{name}-uid",
                            api_version="kubeflow.org/v1")
            pods = [make_pod(
                f"{name}-worker-{k:04d}", owner=ref,
                gpu=1 if j % 2 == 0 else 0,
                labels={"training.kubeflow.org/replica-type":
                        "worker"}) for k in range(gang)]
            if create_many is not None:
                # Submission batches like production clients do: one
                # bulk round trip per 500-pod chunk over the wire.
                for lo in range(0, len(pods), 500):
                    create_many(pods[lo:lo + 500])
            else:
                for pod in pods:
                    api.create(pod)

    def run_until_bound(expect, max_cycles=6):
        ts = []
        # Pipelined mode: binds land asynchronously, so the loop gets a
        # small cycle allowance for the commit stage to catch up; the
        # trailing flush waits out the final in-flight batch so
        # pod_latency below sees every bound note.
        for _ in range(max_cycles + (2 if pipelined else 0)):
            t_it = time.perf_counter()
            system.run_cycle()
            ts.append(time.perf_counter() - t_it)
            if LIFECYCLE.summary().get("bound_pods", 0) >= expect:
                break
        system.flush_pipeline()
        return ts

    try:
        if substrate == "http":
            from kai_scheduler_tpu.controllers.apiserver import \
                KubeAPIServer
            from kai_scheduler_tpu.controllers.httpclient import \
                HTTPKubeAPI
            server = KubeAPIServer().start()
            client = HTTPKubeAPI(server.url)
            system = System(SystemConfig(pipelined_cycles=pipelined),
                            api=client)
        else:
            system = System(SystemConfig(pipelined_cycles=pipelined))
        api = system.api
        fleet_nodes = [{"kind": "Node",
                        "metadata": {"name": f"fn{i:05d}"}, "spec": {},
                        "status": {"allocatable": {
                            "cpu": "32", "memory": "256Gi",
                            "nvidia.com/gpu": 8, "pods": 110}}}
                       for i in range(n_nodes)]
        node_many = getattr(api, "create_many", None)
        if node_many is not None:
            for lo in range(0, len(fleet_nodes), 500):
                node_many(fleet_nodes[lo:lo + 500])
        else:
            for node in fleet_nodes:
                api.create(node)
        for q in range(8):
            api.create({"kind": "Queue", "metadata": {"name": f"fq{q}"},
                        "spec": {}})
        # Wave 1: cold (grouper depth + XLA compiles land here).
        LIFECYCLE.reset()
        submit_wave(1)
        t_c = time.perf_counter()
        cold_cycles = run_until_bound(wave_pods)
        cold_s = time.perf_counter() - t_c
        cold_bound = LIFECYCLE.summary().get("bound_pods", 0)
        _log(f"fleet cold: {cold_bound} bound in {cold_s:.2f}s "
             f"({len(cold_cycles)} cycles); warm wave")
        # Wave 2: warm — the measured submit→bound SLO.
        LIFECYCLE.reset()
        submit_wave(2)
        t_w = time.perf_counter()
        warm_cycles = run_until_bound(wave_pods)
        warm_wave_s = time.perf_counter() - t_w
        pod_latency = LIFECYCLE.summary()
    finally:
        # A phase timeout must not leave a 97Hz sampler walking every
        # thread's stack for the rest of the bench.
        prof.stop(dump=False)
        LIFECYCLE.configure_bounds(**old_bounds)
        # Snapshot the executor's evidence counters before the join
        # tears it down, then stop it (in-flight writes land first)
        # BEFORE the HTTP substrate goes away under it.
        executor_stats = None
        if system is not None:
            ex = system.commit_executor
            if ex is not None:
                ex.wait_token(ex.token(), timeout=60.0)
                executor_stats = ex.stats()
            system.stop_pipeline()
        if client is not None:
            client.close()
        if server is not None:
            server.stop()
    # Incremental host pipeline verdict: the shard cache's last-snapshot
    # dirty counts and the grouper/cache counters this PR's budget smoke
    # gates on (tools/fleet_budget.py).
    from kai_scheduler_tpu.utils.metrics import METRICS
    cache = system.schedulers[0].cache if system.schedulers else None
    incremental = {
        "last_snapshot": getattr(cache, "last_snapshot_stats", {}),
        "full_refresh_total": METRICS.counters.get(
            "cluster_cache_full_refresh_total", 0),
        "owner_cache_hits": METRICS.counters.get(
            "podgrouper_owner_cache_hits", 0),
        "owner_cache_misses": METRICS.counters.get(
            "podgrouper_owner_cache_misses", 0),
        "stale_writes_skipped": METRICS.counters.get(
            "stale_write_skipped_total", 0),
    }
    # Wire observatory verdict: byte/syscall/frame-cache movement across
    # the whole phase (zeros on the in-memory substrate), plus the
    # fragmentation gauges from the last packed snapshot (ROADMAP 4a).
    wire_moved = wireobs.wire_delta(wire0, wireobs.wire_totals())
    fragmentation = {
        key: val for key, val in METRICS.gauges.items()
        if key.startswith(("stranded_resource_total",
                           "largest_placeable_gang"))
    }
    result = {
        "config": f"{n_nodes}nodes_{n_jobs * gang}pods_fleet",
        "substrate": substrate,
        "pipelined": bool(pipelined),
        "cold_wave_s": round(cold_s, 2),
        "cold_cycles": len(cold_cycles),
        "cold_bound_pods": cold_bound,
        "warm_cycle_s": round(float(np.median(warm_cycles)), 3),
        "warm_wave_s": round(warm_wave_s, 3),
        "warm_cycles": len(warm_cycles),
        "pod_latency": pod_latency,
        "incremental": incremental,
        "wire": wire_moved,
        "fragmentation": fragmentation,
        "stackprof": {
            "samples": prof.total_samples,
            "distinct_stacks": len(prof.samples),
            "top_frames": prof.top_frames(6),
        },
    }
    if pipelined and system.pipeline_stats:
        ratios = [row["overlap_ratio"] for row in system.pipeline_stats]
        result["pipeline"] = {
            "overlap_ratio_mean": round(float(np.mean(ratios)), 3),
            "overlap_ratio_max": round(float(np.max(ratios)), 3),
            "executor": executor_stats,
        }
    return result


def forest_parent_indices(n_queues, roots=16, fanouts=(2, 2, 2, 2, 2, 8)):
    """Parent index per queue (-1 = root) for the multi-tenant org
    forest: ``roots`` top-level tenants, breadth-first fanout per depth
    (depth ~ len(fanouts)).  The one source of truth for the churn
    ring's topology — the API-object builder and the fair-share
    microbench both derive from it, so the committed ``fairshare-10k-ab``
    rows measure exactly the forest the ``churn-ring`` row runs."""
    parent = np.full(n_queues, -1, np.int64)
    cur = list(range(min(roots, n_queues)))
    next_id, depth = len(cur), 1
    while next_id < n_queues:
        nxt = []
        fanout = fanouts[min(depth - 1, len(fanouts) - 1)]
        for p in cur:
            for _ in range(fanout):
                if next_id >= n_queues:
                    break
                parent[next_id] = p
                nxt.append(next_id)
                next_id += 1
            if next_id >= n_queues:
                break
        cur = nxt or cur
        depth += 1
    return parent


def build_queue_forest(n_queues, roots=16, fanouts=(2, 2, 2, 2, 2, 8)):
    """Queue manifests for the forest of ``forest_parent_indices``.
    Returns (queue_objs, leaf_names) — pods submit against the leaves."""
    parent = forest_parent_indices(n_queues, roots, fanouts)
    names = [f"org-{i:03d}" if parent[i] < 0 else f"q{i:05d}"
             for i in range(n_queues)]
    has_child = set(parent[parent >= 0].tolist())
    leaves = [names[i] for i in range(n_queues) if i not in has_child]
    objs = [{"kind": "Queue", "metadata": {"name": names[i]},
             "spec": ({"parentQueue": names[parent[i]]}
                      if parent[i] >= 0 else {})}
            for i in range(n_queues)]
    return objs, leaves


def fairshare_inputs(n_queues=10000, roots=16,
                     fanouts=(2, 2, 2, 2, 2, 8), bands=1, seed=0) -> dict:
    """The seeded multi-tenant fair-share instance at ``n_queues``: the
    forest of ``forest_parent_indices`` with random quotas, limits,
    weights, rolled-up requests and usage.  One generator for the
    microbench below and for ``chip_smoke.py``'s check of the forest
    kernel against the sequential numpy reference."""
    from kai_scheduler_tpu.ops import fairshare as fs

    rng = np.random.default_rng(seed)
    R = 3
    q = n_queues
    parent = forest_parent_indices(q, roots, fanouts)
    priority = rng.choice(np.arange(bands) * 50, q)
    creation = rng.uniform(0, 1e6, q)
    uids = [f"tenant-{i:05d}" for i in range(q)]
    deserved = np.where(rng.random((q, R)) < 0.5, 0.0,
                        rng.integers(1, 8, (q, R)).astype(float))
    limit = np.where(rng.random((q, R)) < 0.9, -1.0,
                     rng.integers(16, 64, (q, R)).astype(float))
    oqw = rng.integers(1, 4, (q, R)).astype(float)
    request = fs.roll_up_requests(
        parent, rng.integers(0, 30, (q, R)).astype(float))
    usage = rng.uniform(0, 0.2, (q, R))
    return dict(parent=parent, priority=priority, creation=creation,
                uids=uids, deserved=deserved, limit=limit, oqw=oqw,
                request=request, usage=usage, total=np.full(R, 2e5))


def fairshare_microbench(n_queues=10000, roots=16,
                         fanouts=(2, 2, 2, 2, 2, 8), bands=1,
                         iters=7, seed=0):
    """The fair-share STEP alone at scale: what one cycle of the
    proportion plugin's division costs — the prep-cache hash plus ONE
    fused dispatch (ops/fairshare.fair_share_forest).  Its shares are
    held bit-identical to the per-level reference ``fair_share_levels``
    on this instance (asserted here; property-tested in
    tests/test_fairshare_forest.py)."""
    from kai_scheduler_tpu.ops import fairshare as fs
    from kai_scheduler_tpu.utils.metrics import METRICS

    q = n_queues
    inst = fairshare_inputs(n_queues, roots, fanouts, bands, seed)
    parent, priority, creation, uids = (inst["parent"], inst["priority"],
                                        inst["creation"], inst["uids"])
    deserved, limit, oqw = inst["deserved"], inst["limit"], inst["oqw"]
    request, usage, total = inst["request"], inst["usage"], inst["total"]
    hier = fs.QueueHierarchy.build(parent, priority, creation, uids)
    hier_depth = max(len(hier.levels), 1) - 1

    def step():
        prep = fs.prepared_forest(parent, priority, creation, uids,
                                  deserved, limit, oqw)
        # kailint: disable=KAI004 — offline micro-bench, no Session to dispatch through
        return fs.fair_share_forest(total, 1.0, prep, request, usage)

    reuse0 = METRICS.counters.get("fairshare_prep_reuse_total", 0)
    disp0 = METRICS.counters.get("fairshare_dispatch_total", 0)
    out = step()  # warm (compiles; fills the prep cache)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        step()
        ts.append((time.perf_counter() - t0) * 1000.0)
    result = {
        "queues": q,
        "depth": hier_depth,
        "bands": bands,
        "fairshare_step_ms": round(float(np.median(ts)), 2),
        "prep_reuse": int(METRICS.counters.get(
            "fairshare_prep_reuse_total", 0) - reuse0),
        "dispatches": int(METRICS.counters.get(
            "fairshare_dispatch_total", 0) - disp0),
        "iters": iters,
    }
    # Bit-parity with the reference on THIS instance, not just the
    # suite's.
    # kailint: disable=KAI004 — offline micro-bench, no Session to dispatch through
    levels = fs.fair_share_levels(total, 1.0, hier, deserved, limit, oqw,
                                  request, usage)
    assert np.array_equal(out, levels), \
        "forest fair share diverged from per-level path"
    return result


def churn_phase(n_nodes=256, n_queues=10000, cycles=8,
                submit_per_cycle=400, seed=0,
                gpu_per_node=8, pipelined=False, substrate="memory"):
    """The heavy-traffic multi-tenant churn ring (ROADMAP item 3).

    A full ``System`` over one in-memory apiserver with an O(10k)-queue
    forest (depth >= 5), driven by a CONTINUOUS stream — every cycle
    submits a burst of pods across random leaf queues, completes a
    random slice of bound pods, and evicts a few more (the kubelet
    analog then finalizes terminations) — not a one-shot fill.  Reports
    p99 submit→bound pod latency from the lifecycle tracker alongside
    cycle time and the fair-share step median.

    Capacity math (the burst-row convention): the stream is
    GPU-throughput-bound.  Cumulative submissions exceed the
    ``n_nodes * gpu_per_node`` slot pool, so at most
    ``slots + completed + evicted`` pods can ever be bound;
    ``expected_bound`` records that ceiling so a partially-bound row
    reads as the designed saturation, not a placement bug."""
    from kai_scheduler_tpu.controllers import (ShardSpec, System,
                                               SystemConfig, make_pod)
    from kai_scheduler_tpu.framework.conf import SchedulerConfig
    from kai_scheduler_tpu.utils.lifecycle import LIFECYCLE
    from kai_scheduler_tpu.utils.metrics import METRICS
    from kai_scheduler_tpu.utils.tracing import TRACER

    rng = np.random.default_rng(seed)
    cfg = SchedulerConfig(actions=["allocate"])
    server = client = None
    if substrate == "http":
        # The wire ring: the whole churn stream (submits, completes,
        # evictions, kubelet finalization) and the fleet itself run over
        # a real loopback apiserver — the daemon's production regime.
        from kai_scheduler_tpu.controllers.apiserver import KubeAPIServer
        from kai_scheduler_tpu.controllers.httpclient import HTTPKubeAPI
        server = KubeAPIServer().start()
        client = HTTPKubeAPI(server.url)
        system = System(SystemConfig(shards=[ShardSpec(config=cfg)],
                                     pipelined_cycles=pipelined),
                        api=client)
    else:
        system = System(SystemConfig(shards=[ShardSpec(config=cfg)],
                                     pipelined_cycles=pipelined))
    api = system.api
    # Selector pushdown for the driver's own queries: "bound and not
    # terminating" / "terminating" ship as field selectors (server-side
    # on the wire) instead of whole-kind lists per cycle.
    SEL_BOUND = "spec.nodeName!=,metadata.deletionTimestamp="
    SEL_TERMINATING = "metadata.deletionTimestamp!="
    t_setup = time.perf_counter()
    nodes = [{"kind": "Node",
              "metadata": {"name": f"cn{i:05d}"}, "spec": {},
              "status": {"allocatable": {
                  "cpu": "64", "memory": "512Gi",
                  "nvidia.com/gpu": gpu_per_node, "pods": 110}}}
             for i in range(n_nodes)]
    queue_objs, leaves = build_queue_forest(n_queues)
    setup_many = getattr(api, "create_many", None)
    if setup_many is not None:
        for objs in (nodes, queue_objs):
            for lo in range(0, len(objs), 500):
                setup_many(objs[lo:lo + 500])
    else:
        for obj in nodes + queue_objs:
            api.create(obj)
    setup_s = time.perf_counter() - t_setup
    _log(f"churn setup: {n_nodes} nodes, {len(queue_objs)} queues "
         f"({len(leaves)} leaves) in {setup_s:.1f}s")

    total_pods = submit_per_cycle * cycles
    old_bounds = LIFECYCLE.configure_bounds(
        open_cap=max(8192, total_pods * 2), ring=max(2048, total_pods * 2))
    serial = completed = evicted = 0
    cycle_ts, fairshare_ts = [], []
    try:
        # Warmup: two cycles with a half burst pay the XLA compiles (the
        # forest kernel + this shape's allocate ladder) so the measured
        # stream reports steady-state latencies, then the tracker resets.
        for _ in range(2):
            for _ in range(submit_per_cycle // 2):
                api.create(make_pod(f"churn-warm-{serial:06d}",
                                    queue=leaves[serial % len(leaves)],
                                    gpu=1))
                serial += 1
            system.run_cycle()
        # Warmup pods leave the cluster: the measured stream starts from
        # empty capacity so the throughput math below is exact.
        for p in api.list("Pod"):
            api.delete("Pod", p["metadata"]["name"],
                       p["metadata"].get("namespace", "default"))
        system.drain()
        system.run_cycle()
        system.flush_pipeline()
        _log("churn warmup done; measuring stream")
        LIFECYCLE.reset()
        reuse0 = METRICS.counters.get("fairshare_prep_reuse_total", 0)
        create_many = getattr(api, "create_many", None)
        for _ in range(cycles):
            leaf_idx = rng.integers(0, len(leaves), submit_per_cycle)
            burst = []
            for li in leaf_idx:
                burst.append(make_pod(f"churn-{serial:06d}",
                                      queue=leaves[int(li)], gpu=1))
                serial += 1
            if create_many is not None:
                for lo in range(0, len(burst), 500):
                    create_many(burst[lo:lo + 500])
            else:
                for pod in burst:
                    api.create(pod)
            bound = [p for p in api.list("Pod",
                                         field_selector=SEL_BOUND)
                     if p["spec"].get("nodeName")
                     and not p["metadata"].get("deletionTimestamp")]
            rng.shuffle(bound)
            n_complete = int(len(bound) * 0.2)
            n_evict = int(len(bound) * 0.05)
            for p in bound[:n_complete]:
                api.delete("Pod", p["metadata"]["name"],
                           p["metadata"].get("namespace", "default"))
            completed += n_complete
            for p in bound[n_complete:n_complete + n_evict]:
                # The stream's evict arm: involuntary removal mid-run
                # (deletionTimestamp now, finalized below).
                p["metadata"]["deletionTimestamp"] = "evicted"
                api.update(p)
            evicted += n_evict
            t0 = time.perf_counter()
            system.run_cycle()
            cycle_ts.append(time.perf_counter() - t0)
            ssn = system.schedulers[0].last_session
            step_s = _span_seconds(
                TRACER.get_trace(ssn.trace_id) if ssn is not None else None,
                "fairshare")
            if step_s:
                fairshare_ts.append(step_s)
            # Kubelet analog: terminations complete.
            for p in api.list("Pod", field_selector=SEL_TERMINATING):
                if p["metadata"].get("deletionTimestamp"):
                    api.delete("Pod", p["metadata"]["name"],
                               p["metadata"].get("namespace", "default"))
            system.drain()
        # Pipelined mode: the last cycles' binds are still in flight —
        # land them before reading the latency summary.
        system.flush_pipeline()
        system.drain()
        pod_latency = LIFECYCLE.summary()
    finally:
        LIFECYCLE.configure_bounds(**old_bounds)
        if server is not None:
            system.stop_pipeline()
            client.close()
            server.stop()

    slots = n_nodes * gpu_per_node
    expected_bound = min(total_pods, slots + completed + evicted)
    result = {
        "config": f"{n_nodes}nodes_{n_queues}queues_"
                  f"{submit_per_cycle}per_cycle",
        "pipelined": bool(pipelined),
        "substrate": substrate,
        "queues": n_queues,
        "leaves": len(leaves),
        "cycles": cycles,
        "submitted": total_pods,
        "completed": completed,
        "evicted": evicted,
        "setup_s": round(setup_s, 1),
        "cold_cycle_s": round(cycle_ts[0], 2),
        "cycle_s": round(float(np.median(cycle_ts[1:] or cycle_ts)), 3),
        "fairshare_step_ms": round(
            float(np.median(fairshare_ts[1:] or fairshare_ts)) * 1000.0,
            2) if fairshare_ts else None,
        "fairshare_prep_reuse": int(METRICS.counters.get(
            "fairshare_prep_reuse_total", 0) - reuse0),
        "pod_latency": pod_latency,
        "expected_bound": expected_bound,
        "capacity_note": (
            f"throughput-bound: {n_nodes} nodes x {gpu_per_node} GPUs = "
            f"{slots} slots vs {total_pods} one-GPU submissions; "
            f"{completed} completed + {evicted} evicted recycle their "
            f"slots, so at most {expected_bound} can be bound"),
    }
    if pipelined and system.pipeline_stats:
        ratios = [row["overlap_ratio"] for row in system.pipeline_stats]
        result["overlap_ratio_mean"] = round(float(np.mean(ratios)), 3)
    system.stop_pipeline()
    return result


def churn_main() -> int:
    """The churn-ring rows (one commit, one machine): the ring itself
    at O(10k) queues, appended as a ``churn-ring`` row carrying p99
    submit→bound, in memory and then over the wire."""
    enable_compile_cache()
    import jax

    backend = jax.default_backend()
    row = churn_phase()
    _append_result_row({"scenario": "churn-ring", "backend": backend,
                        **row})

    # The churn ring OVER THE WIRE (DESIGN §12): the same continuous
    # stream driven through a real loopback apiserver — submits,
    # completions, evictions, and the kubelet analog all pay transport,
    # with the driver's per-cycle queries pushed down as field
    # selectors.
    wrow = churn_phase(pipelined=True, substrate="http")
    _append_result_row({"scenario": "churn-ring", "backend": backend,
                        **wrow})
    _log(f"wire churn ring: cycle {wrow['cycle_s']}s, p99 submit→bound "
         f"{wrow['pod_latency'].get('submit_to_bound_p99_ms')}ms")
    return 0


def churn_wire_faults_main() -> int:
    """The churn ring OVER A LYING WIRE (PR 15): the wire churn stream
    at a reduced shape with the composite ``wire-*`` fault spec armed
    for the WHOLE run — corrupted watch frames, stalled streams,
    dropped responses, a throttle storm — measuring what fault
    tolerance costs in p99 submit→bound.  The row is annotated
    ``@wire-faults`` (the ``@guard-degraded`` convention): its numbers
    are the DEGRADED regime's, never comparable to clean churn rows.
    (The zero-double-bind invariant itself is the chaos ring's job —
    ``chaos_matrix --wire-faults``; this row records what the
    self-healing costs.)"""
    enable_compile_cache()
    import jax

    from kai_scheduler_tpu.utils.metrics import METRICS

    backend = jax.default_backend()
    # The watch-stream + throttle faults: survivable by the CLIENT's
    # own machinery (reconnect, retry-through-429/503), so the bench
    # driver needs no fault handling of its own.  The ambiguous-
    # mutation modes (wire-drop/wire-reset) stay the chaos ring's job —
    # they require the submitter itself to replay, which the ring's
    # driver does and this one deliberately does not.
    # Densities tuned so the stream still makes progress: the churn
    # shape ships thousands of watch frames per cycle, and a corrupt
    # frame costs the whole stream a reconnect + replay — every-6th
    # (the chaos ring's unit density) starves the watch entirely at
    # this volume.
    spec = "wire-corrupt:400,wire-stall:5,wire-storm:4"
    faults0 = {k: v for k, v in METRICS.counters.items()
               if k.startswith("wire_faults_injected_total")}
    # Run DELTAS, not process-lifetime absolutes: an earlier phase run
    # in the same process must not inflate this row's record.
    base = {name: METRICS.counters.get(name, 0)
            for name in ("watch_reconnect_total",
                         "bind_wave_replays_total",
                         "podgrouper_requeued_owners_total")}
    divergence0 = sum(v for k, v in METRICS.counters.items()
                      if k.startswith("cache_divergence_total"))
    prev = os.environ.get("KAI_FAULT_INJECT")
    os.environ["KAI_FAULT_INJECT"] = spec
    try:
        row = churn_phase(n_nodes=128, n_queues=512, cycles=6,
                          submit_per_cycle=200, pipelined=True,
                          substrate="http")
    finally:
        if prev is None:
            os.environ.pop("KAI_FAULT_INJECT", None)
        else:
            os.environ["KAI_FAULT_INJECT"] = prev
    injected = {
        k.split('mode="')[1].rstrip('"}'): int(v - faults0.get(k, 0))
        for k, v in METRICS.counters.items()
        if k.startswith("wire_faults_injected_total")}
    row.update({
        "annotation": "@wire-faults",
        "fault_inject": spec,
        "faults_injected": injected,
        "watch_reconnects": int(METRICS.counters.get(
            "watch_reconnect_total", 0)
            - base["watch_reconnect_total"]),
        "bind_wave_replays": int(METRICS.counters.get(
            "bind_wave_replays_total", 0)
            - base["bind_wave_replays_total"]),
        "grouper_requeues": int(METRICS.counters.get(
            "podgrouper_requeued_owners_total", 0)
            - base["podgrouper_requeued_owners_total"]),
        "cache_divergence": int(sum(
            v for k, v in METRICS.counters.items()
            if k.startswith("cache_divergence_total")) - divergence0),
    })
    _append_result_row({"scenario": "churn-ring-wire-faults",
                        "backend": backend, **row})
    _log(f"wire-fault churn ring: cycle {row['cycle_s']}s, p99 "
         f"submit→bound "
         f"{row['pod_latency'].get('submit_to_bound_p99_ms')}ms "
         f"under {spec}")
    return 0


def tas_phase(dims, gang, iters: int = 5):
    """TAS measurement at one mesh shape: per-level domain aggregation
    (segment sums over the node axis) for a 3-level mesh, then one gang
    fill restricted to the chosen domain via the grouped kernel's node
    mask.  Returns the BENCH detail dict (shared by phase 4 and the
    north-star executor)."""
    import jax.numpy as jnp

    from kai_scheduler_tpu.ops.allocate_grouped import allocate_grouped
    from kai_scheduler_tpu.ops.topology import domain_aggregates

    rng = np.random.default_rng(7)
    tas_nodes = int(np.prod(dims))
    coords = np.stack(np.unravel_index(
        np.arange(tas_nodes), dims), axis=1)
    # Level segments: superpod (dim0), rack (dim0 x dim1),
    # host-group of 8 (deepest).
    seg_l0 = coords[:, 0].astype(np.int32)
    seg_l1 = (coords[:, 0] * dims[1] + coords[:, 1]).astype(np.int32)
    seg_l2 = np.arange(tas_nodes, dtype=np.int32) // 8
    free = np.tile([64000.0, 512e9, 8.0], (tas_nodes, 1))
    free[:, 2] -= rng.integers(0, 4, tas_nodes)
    room = np.full(tas_nodes, 110.0)
    max_pod_req = np.array([1000.0, 4e9, 1.0])

    def tas_subset():
        outs = []
        for seg, d in ((seg_l2, tas_nodes // 8),
                       (seg_l1, dims[0] * dims[1]),
                       (seg_l0, dims[0])):
            f, p = domain_aggregates(
                jnp.asarray(free), jnp.asarray(room),
                jnp.asarray(seg), jnp.asarray(max_pod_req),
                float(gang), int(d))
            outs.append((np.asarray(f), np.asarray(p)))
        return outs

    t_c = time.perf_counter()
    levels = tas_subset()  # warm (compile all three shapes)
    tas_compile_s = time.perf_counter() - t_c
    # Pick the deepest level whose best domain fits the gang.
    chosen = None
    for (f, p), seg in zip(levels, (seg_l2, seg_l1, seg_l0)):
        fit = np.flatnonzero(p >= gang)
        if fit.size:
            chosen = (seg, int(fit[0]))
            break
    assert chosen is not None, "no TAS domain fits the gang"
    seg, dom = chosen
    mask = np.zeros(tas_nodes, bool)
    mask[seg == dom] = True

    tas_args = build_arrays(tas_nodes, 1, gang, placeable=True)
    nodes_t, tasks_t = tas_args[:6], tas_args[6:10]
    out = allocate_grouped(nodes_t, *tasks_t, tas_args[10],
                           node_mask=mask[None, :])  # warm
    placements = np.asarray(out.placements)
    tas_placed = int((placements >= 0).sum())
    in_domain = int(mask[placements[placements >= 0]].sum())
    tas_times = []
    for _ in range(iters):
        t_it = time.perf_counter()
        tas_subset()
        allocate_grouped(nodes_t, *tasks_t, tas_args[10],
                         node_mask=mask[None, :])
        tas_times.append((time.perf_counter() - t_it) * 1000.0)
    return {
        "config": f"{tas_nodes}nodes_3level_gang{gang}",
        "cycle_ms": round(float(np.median(tas_times)), 3),
        "pods_placed": tas_placed,
        "pods_in_domain": in_domain,
        "compile_s": round(tas_compile_s, 1),
    }


RESULTS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "docs", "scale-tests", "results.jsonl")


def _append_result_row(row: dict) -> None:
    """Append one measured row to docs/scale-tests/results.jsonl with the
    commit stamp (same convention as the scale ring's _record)."""
    commit = ""
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10).stdout.strip()
    except Exception:
        pass
    entry = {"commit": commit, "recorded_at": time.time(), **row}
    # Print BEFORE the append: if the write fails (read-only checkout,
    # full disk) the measurement of a potentially hours-long run still
    # reaches stdout instead of dying inside open().
    print(json.dumps(entry), flush=True)
    os.makedirs(os.path.dirname(RESULTS_FILE), exist_ok=True)
    with open(RESULTS_FILE, "a") as f:
        f.write(json.dumps(entry) + "\n")


def north_star_main(prime_only: bool = False, iters: int = 3,
                    append: bool = True) -> int:
    """Long-budget executor for the two north-star shapes (BASELINE
    #4/#5): the 98304-node/1M-pod grouped fill and the 64k-node 3-level
    TAS placement, on whatever backend is live.

    Correctness (pods-placed counts), compile-cache priming (every XLA
    compile persists to the compile cache, so later runs skip it), and a
    measured wall clock, recorded to docs/scale-tests/results.jsonl.
    ``prime_only`` stops after one warm execution per shape — the cache
    is populated and nothing is recorded."""
    enable_compile_cache()
    import jax

    backend = jax.default_backend()
    _log(f"north-star executor: backend={backend} "
         f"{'(prime-cache only)' if prime_only else ''}")

    from kai_scheduler_tpu.ops.allocate_grouped import allocate_grouped

    # --- shape 1: grouped fill, 98304 nodes x 1,048,576 pending pods ----
    t_total = time.perf_counter()
    _log(f"grouped fill: building {BIG_NODES}x{BIG_JOBS * BIG_GANG}")
    big = build_arrays(BIG_NODES, BIG_JOBS, BIG_GANG, placeable=True)
    nodes, tasks = big[:6], big[6:10]
    t_c = time.perf_counter()
    out = allocate_grouped(nodes, *tasks, big[10])  # warm: compile + run
    placed = int((np.asarray(out.placements) >= 0).sum())
    compile_s = time.perf_counter() - t_c
    _log(f"grouped fill warm {compile_s:.1f}s, {placed} pods placed")
    if not prime_only:
        times = []
        for _ in range(iters):
            t_it = time.perf_counter()
            allocate_grouped(nodes, *tasks, big[10])
            times.append((time.perf_counter() - t_it) * 1000.0)
        row = {
            "scenario": "north-star-grouped-fill",
            "backend": backend,
            "nodes": BIG_NODES,
            "pods": BIG_JOBS * BIG_GANG,
            "gang": BIG_GANG,
            "cycle_ms": round(float(np.median(times)), 1),
            "pods_placed": placed,
            "pods_placed_per_sec": round(
                placed / (float(np.median(times)) / 1000.0)),
            "warm_compile_s": round(compile_s, 1),
            "wall_clock_s": round(time.perf_counter() - t_total, 1),
        }
        if append:
            _append_result_row(row)
    del big, nodes, tasks, out

    # --- shape 2: 64k-node 3-level TAS ---------------------------------
    t_total = time.perf_counter()
    _log(f"tas: {int(np.prod(TAS_DIMS))} nodes dims={TAS_DIMS} "
         f"gang={TAS_GANG}")
    tas = tas_phase(TAS_DIMS, TAS_GANG, iters=(1 if prime_only else iters))
    _log(f"tas done: {tas}")
    if not prime_only:
        row = {
            "scenario": "north-star-tas64k",
            "backend": backend,
            "nodes": int(np.prod(TAS_DIMS)),
            "gang": TAS_GANG,
            "cycle_ms": tas["cycle_ms"],
            "pods_placed": tas["pods_placed"],
            "warm_compile_s": tas["compile_s"],
            "wall_clock_s": round(time.perf_counter() - t_total, 1),
        }
        if append:
            _append_result_row(row)
    return 0


def _emit(result):
    """Print one complete driver-parseable JSON line NOW.

    The driver takes the last parseable line of the tail, so each phase
    reprints the whole (enriched) result; any truncation point still
    leaves a valid number on stdout."""
    print(json.dumps(result), flush=True)


def _exit_code(result, guard) -> int:
    """0 only for a run in which every phase produced its row and no
    dispatch left the device: a phase that recorded an ``error`` and a
    guard that fell back to the CPU both fail the run."""
    failed = [name for name, row in result["detail"].items()
              if isinstance(row, dict) and "error" in row]
    if failed:
        _log(f"phases failed: {failed}")
    fell_back = guard.degraded or guard.fallback_calls > 0
    if fell_back:
        _log(f"device guard fell back: {guard.status()}")
    return 1 if failed or fell_back else 0


def main() -> int:
    """The measurement.  Emits after EVERY phase; a phase that raises
    records its error and the later phases still run.  Returns the exit
    code: non-zero when any phase failed or the device guard fell back.

    Every device dispatch of the primary phase routes through the device
    guard (utils/deviceguard.py) like the daemon's do: a hung/erroring
    device trips the breaker and the phase degrades to the guard's CPU
    fallback — under ``KAI_FAULT_INJECT=hang`` the primary number still
    lands, annotated ``@guard-degraded``, and the exit code says so.

    ``BENCH_SMOKE=1`` shrinks the primary config and skips later phases:
    the chaos ring's fault-injection smoke needs the degradation path,
    not the full measurement."""
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    n_nodes, n_jobs = (64, 16) if smoke else (N_NODES, N_JOBS)

    _log("importing jax")
    import jax
    import jax.numpy as jnp

    enable_compile_cache()

    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
    from kai_scheduler_tpu.ops.allocate_grouped import allocate_grouped
    from kai_scheduler_tpu.ops.fairshare import LevelSpec, divide_groups_jax

    _log("initializing backend")
    t_init = time.perf_counter()
    backend = jax.default_backend()
    init_s = time.perf_counter() - t_init
    on_tpu = backend == "tpu"
    _log(f"backend={backend} init={init_s:.1f}s")

    from kai_scheduler_tpu.utils.deviceguard import device_guard
    guard = device_guard()
    if guard.injector.active:
        _log(f"fault injection active: {guard.injector.spec}")

    # --- phase 1: primary config (always first, always emitted) -----------
    args = build_arrays(n_nodes, n_jobs)
    q_des = jnp.full((N_QUEUES, 3), -1.0)
    q_lim = jnp.full((N_QUEUES, 3), -1.0)
    q_w = jnp.ones((N_QUEUES, 3))
    q_req = jnp.full((N_QUEUES, 3), 1e15)
    q_use = jnp.zeros((N_QUEUES, 3))
    q_band = jnp.zeros(N_QUEUES, jnp.int32)
    q_tie = jnp.arange(N_QUEUES)
    total = jnp.asarray(np.array([64000.0, 512e9, 8.0]) * n_nodes)
    spec = LevelSpec(num_groups=1, num_bands=1)

    def cycle():
        divide_groups_jax(
            spec, total[None, :], jnp.zeros(N_QUEUES, jnp.int32), q_band,
            q_des, q_lim, q_w, q_req, q_use, q_tie, 1.0)
        return allocate_jobs_kernel(*args)

    n_tasks = n_jobs * TASKS_PER_JOB

    def _shape_ok(r):
        # badshape-class corruption must read as a device failure.
        return getattr(r.placements, "shape", (0,))[0] >= n_tasks

    t_c = time.perf_counter()
    first = guard.call(cycle, label="bench_primary", validate=_shape_ok)
    placements_np = np.asarray(first.placements)  # warm fetch
    compile_s = time.perf_counter() - t_c
    placed = int((placements_np >= 0).sum())
    _log(f"primary compiled+ran in {compile_s:.1f}s; measuring")
    fb_before = guard.fallback_calls
    times = []
    for _ in range(10):
        t_it = time.perf_counter()
        # Guarded like the daemon's dispatches: with the breaker open the
        # iteration runs the CPU fallback directly instead of re-paying
        # the watchdog deadline on a dead device.
        np.asarray(guard.call(cycle, label="bench_primary",
                              validate=_shape_ok).placements)
        times.append((time.perf_counter() - t_it) * 1000.0)
    median = float(np.median(times))

    result = {
        "metric": (f"scheduling_cycle_latency_ms@{n_nodes}nodes_"
                   f"{n_tasks}pods"),
        "value": round(median, 3),
        "unit": "ms",
        "vs_baseline": round(NORTH_STAR_MS / median, 3),
        "detail": {
            "backend": backend,
            "p99_ms": round(float(np.percentile(times, 99)), 3),
            "pods_placed": placed,
            "pods_placed_per_sec": round(placed / (median / 1000.0)),
            "primary_compile_s": round(compile_s, 1),
            "backend_init_s": round(init_s, 1),
        },
    }
    if guard.injector.active or guard.degraded or guard.fallback_calls:
        result["detail"]["device_guard"] = guard.status()
    # Annotate on ANY fallback iteration, not just a breaker left open at
    # emit time: intermittent failures mix CPU-fallback latencies into
    # the median even when trailing successes re-close the breaker.
    if guard.degraded or guard.fallback_calls > fb_before:
        # A number measured behind an open breaker is a CPU-fallback
        # number; it must never be read as a device regression.
        result["metric"] += "@guard-degraded"
        result["vs_baseline"] = None
        result["detail"]["backend_note"] = \
            "device-guard degraded to CPU fallback"
    _emit(result)

    if smoke:
        _log("smoke mode: stopping after primary phase")
        return _exit_code(result, guard)

    # --- phase 2: large-gang config, grouped fill-plan kernel --------------
    # Placeable demand (every gang can host) so pods/sec measures real
    # placement throughput, not failed-gang rollback speed.  Off-TPU the
    # shape shrinks; the config string always states the measured shape.
    big_nodes, big_jobs, big_gang = ((BIG_NODES, BIG_JOBS, BIG_GANG)
                                     if on_tpu else (8192, 128, 256))
    try:
        _log(f"large-gang: building {big_nodes}x{big_jobs * big_gang}")
        big = build_arrays(big_nodes, big_jobs, big_gang,
                           placeable=True)
        nodes, tasks = big[:6], big[6:10]
        t_c = time.perf_counter()
        out = allocate_grouped(nodes, *tasks, big[10])  # warm
        big_placed = int((out.placements >= 0).sum())
        big_compile_s = time.perf_counter() - t_c
        _log(f"large-gang compiled+ran in {big_compile_s:.1f}s")
        big_times = []
        for _ in range(5):
            t_it = time.perf_counter()
            allocate_grouped(nodes, *tasks, big[10])
            big_times.append((time.perf_counter() - t_it) * 1000.0)
        big_median = float(np.median(big_times))
        result["detail"]["large_gang"] = {
            "config": f"{big_nodes}nodes_{big_jobs * big_gang}pods_"
                      f"gang{big_gang}",
            "cycle_ms": round(big_median, 3),
            "pods_placed": big_placed,
            "pods_placed_per_sec": round(
                big_placed / (big_median / 1000.0)),
            "compile_s": round(big_compile_s, 1),
        }
    except Exception as exc:  # one phase must not kill the rest
        result["detail"]["large_gang"] = {"error": repr(exc)[:200]}
    _emit(result)

    # --- phase 3: end-to-end host pipeline ---------------------------------
    # The cycle the daemon actually runs, not just the jitted portion:
    # build ClusterInfo, open a session (pack + plugins), run the allocate
    # action including statement application.
    pipe_nodes, pipe_jobs, pipe_gang = ((PIPE_NODES, PIPE_JOBS, PIPE_GANG)
                                        if on_tpu else (2000, 8, 100))
    try:
        _log("host pipeline: building cluster")
        from kai_scheduler_tpu.actions import build_actions
        from kai_scheduler_tpu.framework import (SchedulerConfig,
                                                 Session)
        from kai_scheduler_tpu.utils.cluster_spec import build_cluster

        cspec = {
            "nodes": {f"n{i}": {"gpu": 8} for i in range(pipe_nodes)},
            "queues": {f"q{i}": {} for i in range(8)},
            "jobs": {f"j{i}": {"queue": f"q{i % 8}",
                               "min_available": pipe_gang,
                               "tasks": [{"cpu": "1", "mem": "1Gi",
                                          "gpu": 1 if i % 2 == 0
                                          else 0}] * pipe_gang}
                     for i in range(pipe_jobs)}}
        from kai_scheduler_tpu.utils.tracing import TRACER

        def one_cycle(cycle_no):
            # Traced like the daemon's run_once: the flight recorder
            # yields the per-span breakdown (snapshot/plugin/action/
            # kernel) that lands in the BENCH json below.
            cluster = build_cluster(cspec)
            t_it = time.perf_counter()
            TRACER.begin_cycle(cycle_no)
            try:
                with TRACER.span("snapshot", kind="snapshot"):
                    ssn = Session(cluster, SchedulerConfig())
                ssn.open()
                for action in build_actions(["allocate"]):
                    with TRACER.span(f"action:{action.name}",
                                     kind="action"):
                        action.execute(ssn)
            finally:
                trace = TRACER.end_cycle()
            secs = time.perf_counter() - t_it
            placed = sum(
                1 for pg in ssn.cluster.podgroups.values()
                for t in pg.pods.values() if t.node_name)
            return secs, placed, trace

        # Cold = includes this cluster shape's jit compiles (paid once
        # per binary life / compile-cache fill); steady = the cycle
        # the daemon actually repeats.  The reference's Go cycle has
        # no compile analog, so steady is the comparable number.
        first_s, pipeline_placed, _ = one_cycle(1)
        _log(f"host pipeline cold cycle {first_s:.2f}s; steady run")
        steady_s, pipeline_placed, trace = one_cycle(2)
        entry = {
            "config": f"{pipe_nodes}nodes_"
                      f"{pipe_jobs * pipe_gang}pods",
            "cycle_s": round(steady_s, 3),
            "first_cycle_s": round(first_s, 2),
            "pods_placed": pipeline_placed,
        }
        if trace is not None:
            entry["breakdown_s"] = {
                name: round(secs, 3)
                for name, (_n, secs) in trace.name_totals.items()
                if secs >= 0.001}
            entry["span_summary"] = trace.span_summary()
        result["detail"]["host_pipeline"] = entry
    except Exception as exc:
        result["detail"]["host_pipeline"] = {"error": repr(exc)[:200]}
    _emit(result)

    # --- phase 3b: steady_state — warm cycles through the persistent arena
    # The daemon's REPEATED cycle: same store, no topology changes, the
    # arena serving delta packs and scatter updates.  Reported against the
    # host_pipeline breakdown (which rebuilds the world every cycle):
    # ``snapshot_pack_s``+``arena_upload_s`` is the number the ISSUE-5
    # acceptance compares to r05's 0.010s pack at 2000 nodes/800 pods;
    # ``arena_full_rebuilds_warm`` must stay 0 on a steady-state run.
    try:
        st_nodes, st_jobs, st_gang = (
            (PIPE_NODES, PIPE_JOBS, PIPE_GANG) if on_tpu
            else (2000, 8, 100))
        _log(f"steady state: {st_nodes} nodes, "
             f"{st_jobs * st_gang} pods via persistent arena")
        from kai_scheduler_tpu.api.snapshot import pack as _full_pack
        from kai_scheduler_tpu.controllers import InMemoryKubeAPI
        from kai_scheduler_tpu.controllers.cache_builder import \
            ClusterCache
        from kai_scheduler_tpu.controllers.kubeapi import make_pod
        from kai_scheduler_tpu.controllers.podgrouper import \
            POD_GROUP_LABEL
        from kai_scheduler_tpu.framework.conf import \
            SchedulerConfig as _SConf
        from kai_scheduler_tpu.scheduler import Scheduler
        from kai_scheduler_tpu.utils.metrics import METRICS
        from kai_scheduler_tpu.utils.tracing import TRACER

        api = InMemoryKubeAPI()
        for i in range(st_nodes):
            api.create({"kind": "Node",
                        "metadata": {"name": f"n{i:05d}"}, "spec": {},
                        "status": {"allocatable": {
                            "cpu": "32", "memory": "256Gi",
                            "nvidia.com/gpu": 8, "pods": 110}}})
        for q in range(8):
            api.create({"kind": "Queue",
                        "metadata": {"name": f"q{q}"}, "spec": {}})
        for j in range(st_jobs):
            api.create({"kind": "PodGroup",
                        "metadata": {"name": f"pg{j}"},
                        "spec": {"queue": f"q{j % 8}",
                                 "minMember": st_gang}})
            for k in range(st_gang):
                api.create(make_pod(
                    f"p{j}-{k:04d}",
                    labels={POD_GROUP_LABEL: f"pg{j}"},
                    gpu=1 if j % 2 == 0 else 0))
        cache = ClusterCache(api)
        sched = Scheduler(cache.snapshot,
                          _SConf(actions=["allocate"]), cache=cache)
        t_c = time.perf_counter()
        sched.run_once()  # cold: full rebuild + compiles
        steady_cold_s = time.perf_counter() - t_c
        _log(f"steady state cold cycle {steady_cold_s:.2f}s; warm run")
        rebuilds0 = METRICS.counters.get("arena_full_rebuild_total", 0)
        scatter0 = METRICS.counters.get("arena_scatter_rows", 0)
        warm, packs, uploads = [], [], []
        for _ in range(5):
            t_it = time.perf_counter()
            ssn = sched.run_once()
            warm.append(time.perf_counter() - t_it)
            trace = TRACER.get_trace(ssn.trace_id)
            packs.append(_span_seconds(trace, "snapshot_delta"))
            uploads.append(_span_seconds(
                trace, "arena_scatter", "dispatch:arena_state_upload",
                "dispatch:arena_static_upload"))
        placed = sum(1 for pg in ssn.cluster.podgroups.values()
                     for t in pg.pods.values() if t.node_name)
        # In-run reference: a from-scratch pack of the same cluster
        # (what every cycle paid before the arena).
        ref_cluster = cache.snapshot()
        t_it = time.perf_counter()
        _full_pack(ref_cluster)
        full_pack_s = time.perf_counter() - t_it
        pack_s = float(np.median(packs))
        upload_s = float(np.median(uploads))
        result["detail"]["steady_state"] = {
            "config": f"{st_nodes}nodes_{st_jobs * st_gang}pods",
            "warm_cycle_s": round(float(np.median(warm)), 3),
            "cold_cycle_s": round(steady_cold_s, 2),
            "snapshot_pack_s": round(pack_s, 5),
            "arena_upload_s": round(upload_s, 5),
            "full_pack_s": round(full_pack_s, 5),
            "pack_speedup_vs_full": round(
                full_pack_s / pack_s, 1) if pack_s > 0 else None,
            "snapshot_delta_ratio": METRICS.gauges.get(
                "snapshot_delta_ratio"),
            "arena_full_rebuilds_warm": int(METRICS.counters.get(
                "arena_full_rebuild_total", 0) - rebuilds0),
            "arena_scatter_rows_warm": int(METRICS.counters.get(
                "arena_scatter_rows", 0) - scatter0),
            "pods_placed": placed,
        }
    except Exception as exc:
        result["detail"]["steady_state"] = {"error": repr(exc)[:200]}
    _emit(result)

    # --- phase 3c: fleet — the WHOLE controller fleet with the latency
    # observatory on.  Unlike host_pipeline/steady_state (scheduler-only),
    # this runs watch drain, podgrouper, scheduler, binder, and status
    # updater end to end and reports what the paper-facing SLO actually
    # is: submit→bound pod latency percentiles (utils/lifecycle.py) plus
    # the continuous profiler's verdict on where the host milliseconds
    # live (utils/stackprof.py).
    try:
        fl_nodes, fl_jobs, fl_gang = (
            (PIPE_NODES, PIPE_JOBS, PIPE_GANG) if on_tpu
            else (2000, 8, 100))
        _log(f"fleet: {fl_nodes} nodes, {fl_jobs * fl_gang} pods "
             f"end-to-end with lifecycle tracking + stackprof")
        result["detail"]["fleet"] = fleet_phase(fl_nodes, fl_jobs,
                                                fl_gang)
    except Exception as exc:
        result["detail"]["fleet"] = {"error": repr(exc)[:200]}
    _emit(result)

    # --- phase 4: TAS over a 64k-node 3D mesh (BASELINE config #4) ---------
    # Device-side topology cost: per-level domain aggregation (segment
    # sums over the node axis) for a 3-level mesh, then one gang fill
    # restricted to the best domain via the grouped kernel's node mask.
    try:
        dims = TAS_DIMS if on_tpu else (4, 16, 64)
        gang = TAS_GANG if on_tpu else 256
        _log(f"tas: {int(np.prod(dims))} nodes, dims={dims}, "
             f"gang={gang}")
        result["detail"]["tas"] = tas_phase(dims, gang)
    except Exception as exc:
        result["detail"]["tas"] = {"error": repr(exc)[:200]}
    _emit(result)
    return _exit_code(result, guard)


if __name__ == "__main__":
    # --fault-inject=SPEC: deterministic chaos for the degraded path
    # (tests/test_device_guard.py smoke), exported as KAI_FAULT_INJECT
    # for this process's guard.
    for _i, _arg in enumerate(sys.argv[1:], start=1):
        if _arg == "--fault-inject":
            # Space-separated form ("--fault-inject slow:100"): the spec
            # is the next argv element, not a default of hang.
            _next = sys.argv[_i + 1] if _i + 1 < len(sys.argv) else ""
            os.environ["KAI_FAULT_INJECT"] = \
                _next if _next and not _next.startswith("--") else "hang"
        elif _arg.startswith("--fault-inject="):
            os.environ["KAI_FAULT_INJECT"] = \
                _arg.partition("=")[2] or "hang"
    if "--north-star" in sys.argv:
        # Executes both north-star shapes (98304n/1M grouped fill, 64k 3-level TAS) to
        # completion on the live backend and appends the measured rows +
        # pods-placed counts to docs/scale-tests/results.jsonl.
        sys.exit(north_star_main())
    elif "--prime-cache" in sys.argv:
        # One warm execution per north-star shape: populates the
        # compile cache so later runs skip the compile, records nothing.
        sys.exit(north_star_main(prime_only=True))
    elif "--churn" in sys.argv:
        # Multi-tenant churn ring at O(10k) queues: the continuous
        # submit/complete/evict stream with p99 submit→bound, appended
        # to results.jsonl.
        sys.exit(churn_main())
    elif "--churn-wire-faults" in sys.argv:
        # The churn ring under the composite wire-fault spec (PR 15):
        # p99 submit→bound with the wire lying the whole run, annotated
        # @wire-faults, appended to results.jsonl.
        sys.exit(churn_wire_faults_main())
    else:
        sys.exit(main())
