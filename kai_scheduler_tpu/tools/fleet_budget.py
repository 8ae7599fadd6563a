"""Fleet-phase budget smoke: fail CI when the host pipeline rots.

Runs ``bench.fleet_phase`` at a small committed shape and checks the
result against ``docs/scale-tests/fleet_budget.json``:

- **wall-clock budgets** (generous, noise-tolerant): ``grouped`` and
  ``snapshotted`` phase medians and the warm cycle must stay under the
  committed ceilings — the numbers the incremental host pipeline
  (watch-delta ClusterInfo, owner-coalesced grouping, batched binds)
  brought down must not silently creep back up;
- **structural gates** (deterministic): the incremental cache must
  actually run incrementally (``cluster_cache_full_refresh_total`` stays
  at priming counts — a fallback-per-cycle regression multiplies it by
  the cycle count), the podgrouper's owner-resolution memo must see
  hits, and the GROUPED ALLOCATION path must actually take the fused
  kernel (``allocate_fused_taken_total`` counts per wrapper dispatch —
  a silent fall-back to the per-job path zeroes it while every
  wall-clock gate still passes on a fast machine);
- **allocate-kernel ceiling**: the grouped kernel itself is re-measured
  at a small committed shape (``allocate_shape``) and its median must
  stay under ``max_allocate_ms`` — the device-path analog of the
  host-pipeline medians above, so a fused-kernel regression is caught
  here instead of three PRs later at bench scale;
- **fair-share ceiling + structure**: the queue-forest division is
  re-measured at the committed 10k-queue shape (``fairshare_shape``)
  — its step median must stay under ``max_fairshare_ms`` (a silent
  fall-back to the per-level loop measures several times higher and
  trips this even on a fast machine), the prep cache must actually
  reuse (``min_prep_reuse`` hits of ``fairshare_prep_reuse_total``),
  and ``fairshare_dispatch_total`` must show exactly ONE dispatch per
  division — the structural single-dispatch guarantee of DESIGN §2b;
- **rank & time gates (DESIGN §13)**: the rank-assignment kernel is
  re-measured at ``rankplace_shape`` (median under
  ``max_rankplace_ms``, host-fallback parity asserted), and the
  usage-decay fold at ``usage_shape`` must count EXACTLY one
  ``usage_decay_dispatch_total`` per recorded cycle — a silent
  per-queue host loop multiplies it by Q while every wall clock still
  passes — with a fold-median ceiling on top;
- **wire budget (PR 19 observatory)**: the HTTP smoke runs under the
  wire observatory, and its per-cycle client-end byte/syscall/encode
  footprint plus the frame cache's BYTE-hit ratio must stay within the
  committed ``docs/scale-tests/wire_budget.json`` ceilings — disabling
  the preserialized frame cache (``KAI_WIRE_NO_FRAME_CACHE=1``)
  re-encodes every list/get response and trips the encode + byte-ratio
  gates loudly while every wall clock still passes on a fast machine;
  at least one server span must have grafted into a cycle trace, so a
  silently broken trace join fails here too;
- **compile budget (kaijit's runtime half)**: the whole run executes
  under utils/jittrace.py, and the per-kernel distinct abstract
  signatures (= XLA compilation keys) must stay within the committed
  ``docs/scale-tests/compile_budget.json`` ceilings — dropping a pow2
  bucket multiplies a kernel's signature count with every wall clock
  still green on a fast machine; a journaled kernel the static
  analyzer (tools/kaijit/) never discovered fails as an analyzer gap.

Usage (ci_check.sh runs it):

    JAX_PLATFORMS=cpu python -m kai_scheduler_tpu.tools.fleet_budget
    ... --budget docs/scale-tests/fleet_budget.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("kai-fleet-budget")
    ap.add_argument("--budget", default=None,
                    help="threshold file (default: "
                         "docs/scale-tests/fleet_budget.json)")
    ap.add_argument("--json", action="store_true",
                    help="emit the measured result as JSON")
    ap.add_argument("--compile-budget", default=None,
                    help="compile-budget manifest (default: "
                         "docs/scale-tests/compile_budget.json)")
    ap.add_argument("--wire-budget", default=None,
                    help="wire-budget manifest (default: "
                         "docs/scale-tests/wire_budget.json)")
    args = ap.parse_args(argv)

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    budget_path = args.budget or os.path.join(
        repo_root, "docs", "scale-tests", "fleet_budget.json")
    with open(budget_path) as f:
        budget = json.load(f)
    compile_budget_path = args.compile_budget or os.path.join(
        repo_root, "docs", "scale-tests", "compile_budget.json")

    sys.path.insert(0, repo_root)
    # Arm the compile-signature journal BEFORE bench imports bind any
    # kernel references — the whole budget run records under trace.
    from kai_scheduler_tpu.utils import jittrace
    from kai_scheduler_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jittrace.install()
    import bench
    from kai_scheduler_tpu.utils.metrics import METRICS

    shape = budget["shape"]
    refresh0 = METRICS.counters.get("cluster_cache_full_refresh_total", 0)
    col_fb0 = METRICS.counters.get("columnar_fallback_total", 0)

    def fused_taken():
        return sum(v for k, v in METRICS.counters.items()
                   if str(k).startswith("allocate_fused_taken_total"))

    fused0 = fused_taken()
    result = bench.fleet_phase(shape["nodes"], shape["jobs"],
                               shape["gang"])
    refreshes = METRICS.counters.get(
        "cluster_cache_full_refresh_total", 0) - refresh0
    owner_hits = METRICS.counters.get("podgrouper_owner_cache_hits", 0)
    fused_calls = fused_taken() - fused0

    # Allocate-kernel micro-measurement: the grouped kernel alone at the
    # committed shape, warm median over 5 runs.
    import time as _time

    import numpy as np

    from kai_scheduler_tpu.ops.allocate_grouped import allocate_grouped
    ashape = budget.get("allocate_shape",
                        {"nodes": 1024, "jobs": 16, "gang": 64})
    arrs = bench.build_arrays(ashape["nodes"], ashape["jobs"],
                              ashape["gang"], placeable=True)
    anodes, atasks = arrs[:6], arrs[6:10]
    # kailint: disable=KAI004 — budget micro-bench, no Session to dispatch through
    allocate_grouped(anodes, *atasks, arrs[10])  # warm/compile
    ts = []
    for _ in range(5):
        t0 = _time.perf_counter()
        # kailint: disable=KAI004 — budget micro-bench, no Session to dispatch through
        allocate_grouped(anodes, *atasks, arrs[10])
        ts.append((_time.perf_counter() - t0) * 1000.0)
    allocate_ms = float(np.median(ts))

    # Fair-share micro-measurement: the queue-forest division at the
    # committed 10k-queue shape (warm prep cache, median over 5 runs).
    fshape = budget.get("fairshare_shape", {"queues": 10000, "bands": 1})
    fs_iters = 5
    fsres = bench.fairshare_microbench(n_queues=fshape["queues"],
                                       bands=fshape.get("bands", 1),
                                       iters=fs_iters)

    # Rank-placement micro-measurement (ops/rankplace.py): the
    # assignment kernel alone at the committed gang/topology shape,
    # warm median over 5 runs.
    from kai_scheduler_tpu.ops import rankplace as rp
    rshape = budget.get("rankplace_shape",
                        {"nodes": 4096, "gang": 512, "levels": 3})
    rng = np.random.default_rng(0)
    r_nodes, r_gang = rshape["nodes"], rshape["gang"]
    r_levels = rshape.get("levels", 3)
    topo_rank = rng.permutation(r_nodes).astype(np.int32)
    level_segs = rng.integers(
        0, max(2, r_nodes // 8), (r_levels, r_nodes)).astype(np.int32)
    slots = rng.integers(0, r_nodes, r_gang).astype(np.int32)
    # kailint: disable=KAI004 — budget micro-bench, no Session to dispatch through
    rp.rank_place_padded(slots, topo_rank, level_segs)  # warm/compile
    ts = []
    for _ in range(5):
        t0 = _time.perf_counter()
        # kailint: disable=KAI004 — budget micro-bench, no Session to dispatch through
        perm, _hops = rp.rank_place_padded(slots, topo_rank, level_segs)
        np.asarray(perm)
        ts.append((_time.perf_counter() - t0) * 1000.0)
    rankplace_ms = float(np.median(ts))
    # Host-fallback parity doubles as the budget's sanity check.
    p_np, _h = rp.rank_place_np(slots, topo_rank, level_segs)
    rank_parity = bool(np.array_equal(p_np, np.asarray(perm)))

    # Usage-decay structural gate (ops/usage.py + utils/usagedb.py):
    # fold N cycles of Q-queue samples and PIN the dispatch count to
    # one per cycle — a silent per-queue host loop multiplies it by Q.
    from kai_scheduler_tpu.utils.usagedb import (InMemoryUsageDB,
                                                 UsageParams)
    ushape = budget.get("usage_shape", {"queues": 2048, "cycles": 5})
    udb = InMemoryUsageDB(UsageParams(half_life_period_seconds=600.0))
    u_alloc = {f"q{i}": rng.uniform(0, 8, 3)
               for i in range(ushape["queues"])}
    udb.record_cycle(0.0, u_alloc)  # warm/compile + row growth
    u0 = METRICS.counters.get("usage_decay_dispatch_total", 0)
    ts = []
    for cycle in range(ushape["cycles"]):
        t0 = _time.perf_counter()
        udb.record_cycle(60.0 * (cycle + 1), u_alloc)
        ts.append((_time.perf_counter() - t0) * 1000.0)
    usage_folds = METRICS.counters.get("usage_decay_dispatch_total",
                                       0) - u0
    usage_decay_ms = float(np.median(ts))

    # Arena scatter churn (compile-gate teeth): the fleet phase touches
    # only a handful of distinct dirty-row widths, so an un-bucketed
    # scatter pad would journal the SAME signature count as a bucketed
    # one and slip past the ceiling.  Sweep K=1..12 dirty rows through
    # the real DeviceStateCache scatter path: pow2 bucketing collapses
    # them to {1,2,4,8,16} compile keys, while a raw pad journals all
    # twelve — pushing ``compile_sigs:apply_deltas_kernel`` over its
    # committed ceiling.
    from kai_scheduler_tpu.framework.arena import DeviceStateCache

    class _ChurnSession:
        def __init__(self, n, r=3):
            crng = np.random.default_rng(1)
            self.node_idle = crng.uniform(0, 8, (n, r))
            self.node_releasing = np.zeros((n, r))
            self.node_room = crng.uniform(0, 110, n)
            self._dirty_rows: set[int] = set()

        def dispatch_kernel(self, thunk, label=None, validate=None):
            return thunk()

    cshape = budget.get("scatter_churn_shape",
                        {"nodes": 512, "max_rows": 12})
    churn = _ChurnSession(cshape["nodes"])
    dcache = DeviceStateCache()
    dcache.arrays(churn)  # cold upload; scatters follow
    for k in range(1, cshape["max_rows"] + 1):
        rows = rng.choice(cshape["nodes"], size=k, replace=False)
        churn.node_idle[rows] += 0.5
        churn._dirty_rows.update(int(x) for x in rows)
        dcache.arrays(churn)

    # Overlapped-pipeline smoke (DESIGN §10): the SAME fleet shape with
    # the commit executor armed.  min_overlap_ratio is the structural
    # gate — a pipeline that silently serialized (executor idle while
    # the cycle thread works) reads ~0 here while every wall clock still
    # passes on a fast machine; identical bound-pods proves the
    # speculative view never lost or doubled a placement.
    pres = bench.fleet_phase(shape["nodes"], shape["jobs"],
                             shape["gang"], pipelined=True)
    p_bound = pres.get("pod_latency", {}).get("bound_pods", 0)
    p_overlap = pres.get("pipeline", {}).get("overlap_ratio_mean")

    # HTTP daemon-regime smoke (DESIGN §12): the SAME fleet over a real
    # loopback apiserver + HTTPKubeAPI, pipelined.  The structural gates
    # are the transport-rot detectors: hot-kind list requests bounded to
    # the priming pass (steady-state cycles ship zero whole-kind lists),
    # the watch-mode cache never falls back to re-lists, bind waves land
    # through the bulk endpoints, and the preserialized frame cache
    # actually reuses its encodes.
    from kai_scheduler_tpu.utils.metrics import _key as _metric_key

    def _labeled(name, **labels):
        return METRICS.counters.get(_metric_key(name, labels), 0)

    hshape = budget.get("http_shape", {"nodes": 200, "jobs": 2,
                                       "gang": 50})
    hot_kinds = ("Pod", "Node", "Queue", "PodGroup")

    def hot_lists():
        return sum(_labeled("apiserver_list_requests_total", kind=k)
                   for k in hot_kinds)

    h_lists0 = hot_lists()
    h_refresh0 = METRICS.counters.get("cluster_cache_full_refresh_total",
                                      0)
    h_waves0 = _labeled("bulk_write_batches_total", path="bind_wave")
    h_bulk0 = (_labeled("apiserver_bulk_requests_total", op="create")
               + _labeled("apiserver_bulk_requests_total", op="patch"))
    h_hits0 = METRICS.counters.get("watch_frame_cache_hits_total", 0)
    h_miss0 = METRICS.counters.get("watch_frame_cache_misses_total", 0)
    h_graft0 = METRICS.counters.get("wire_spans_grafted_total", 0)
    hres = bench.fleet_phase(hshape["nodes"], hshape["jobs"],
                             hshape["gang"], pipelined=True,
                             substrate="http")
    h_bound = hres.get("pod_latency", {}).get("bound_pods", 0)
    h_expect = hshape["jobs"] * hshape["gang"]
    h_hits = METRICS.counters.get("watch_frame_cache_hits_total",
                                  0) - h_hits0
    h_miss = METRICS.counters.get("watch_frame_cache_misses_total",
                                  0) - h_miss0
    h_ratio = round(h_hits / max(h_hits + h_miss, 1), 3)

    # Wire-budget measurement (PR 19): the http smoke's own ``wire``
    # section is the byte/syscall delta across the whole phase; divide
    # by the cycles it took for per-cycle footprints.  Client-end
    # counters are the gated side — they move once per *attempt*, so a
    # retry storm shows up here even when the server saw each write
    # once.  Encodes = frame-cache misses (every one is a full
    # json.dumps on the serve path).
    from kai_scheduler_tpu.utils import wireobs
    wire = hres.get("wire") or {}
    h_cycles = max(1, (hres.get("cold_cycles") or 0)
                   + (hres.get("warm_cycles") or 0))

    def _wire(name, **labels):
        return wire.get(_metric_key(name, labels), 0)

    wire_client_bytes = sum(
        _wire("wire_bytes_total", dir=d, end="client", path=p)
        for d in ("in", "out") for p in wireobs.PATH_CLASSES)
    wire_client_syscalls = sum(
        _wire("wire_syscalls_total", end="client", op=op, path=p)
        for op in ("send", "recv") for p in wireobs.PATH_CLASSES)
    wire_encodes = wire.get("watch_frame_cache_misses_total", 0)
    wire_serve_encodes = wire.get("frame_cache_serve_encodes_total", 0)
    wire_cache_b = _wire("frame_cache_bytes_total", src="cache")
    wire_enc_b = _wire("frame_cache_bytes_total", src="encode")
    wire_byte_hit = round(
        wire_cache_b / max(wire_cache_b + wire_enc_b, 1), 3)
    wire_grafted = METRICS.counters.get("wire_spans_grafted_total",
                                        0) - h_graft0
    wire_budget_path = args.wire_budget or os.path.join(
        repo_root, "docs", "scale-tests", "wire_budget.json")
    with open(wire_budget_path) as f:
        wire_budget = json.load(f)

    # Columnar host-state gates (DESIGN §11): the warm fleet shape must
    # stay on the array-native snapshot path end to end — a single
    # fallback (resync aside, none should fire here) or a zero
    # columnar-rows gauge means the fast path silently rotted while
    # every wall clock still passes on a fast machine.  The build-time
    # ceiling is the direct analog of the phase medians: the median of
    # snapshot_build_latency_ms across every cycle both fleet runs took.
    col_fallbacks = METRICS.counters.get(
        "columnar_fallback_total", 0) - col_fb0
    col_rows = METRICS.gauges.get("snapshot_columnar_rows", 0)
    snap_hist = METRICS.histograms.get("snapshot_build_latency_ms")
    snap_build_ms = round(snap_hist.quantile(0.5), 1) \
        if snap_hist is not None else None

    medians = result.get("pod_latency", {}).get("phase_median_ms", {})
    bound = result.get("pod_latency", {}).get("bound_pods", 0)
    expect = shape["jobs"] * shape["gang"]
    checks = [
        ("bound_pods", bound, ">=", expect),
        ("warm_cycle_s", result.get("warm_cycle_s"),
         "<=", budget["max_warm_cycle_s"]),
        ("grouped_median_ms", medians.get("grouped"),
         "<=", budget["max_grouped_ms"]),
        ("snapshotted_median_ms", medians.get("snapshotted"),
         "<=", budget["max_snapshotted_ms"]),
        ("cluster_cache_full_refreshes", refreshes,
         "<=", budget["max_full_refreshes"]),
        ("podgrouper_owner_cache_hits", owner_hits,
         ">=", budget["min_owner_cache_hits"]),
        ("allocate_fused_taken", fused_calls,
         ">=", budget.get("min_fused_taken", 1)),
        ("allocate_kernel_median_ms", round(allocate_ms, 1),
         "<=", budget.get("max_allocate_ms", 400)),
        ("fairshare_step_median_ms", fsres["fairshare_step_ms"],
         "<=", budget.get("max_fairshare_ms", 150)),
        ("fairshare_prep_reuse", fsres["prep_reuse"],
         ">=", budget.get("min_prep_reuse", fs_iters - 1)),
        # Structural: one jitted dispatch per division (warm call + one
        # per measured iteration) — a per-level fallback multiplies this
        # by the hierarchy depth.
        ("fairshare_dispatches", fsres["dispatches"],
         "<=", fs_iters + 1),
        ("rankplace_kernel_median_ms", round(rankplace_ms, 2),
         "<=", budget.get("max_rankplace_ms", 80)),
        ("rankplace_kernel_host_parity", int(rank_parity), ">=", 1),
        # Structural: EXACTLY one jitted decay fold per recorded cycle
        # (never a per-queue host loop) — pinned from both sides.
        ("usage_decay_dispatches", usage_folds,
         "<=", ushape["cycles"]),
        ("usage_decay_dispatches_floor", usage_folds,
         ">=", ushape["cycles"]),
        ("usage_decay_median_ms", round(usage_decay_ms, 2),
         "<=", budget.get("max_usage_decay_ms", 80)),
        ("columnar_fallbacks", col_fallbacks,
         "<=", budget.get("max_columnar_fallbacks", 0)),
        ("columnar_rows", col_rows,
         ">=", budget.get("min_columnar_rows", 1)),
        ("snapshot_build_median_ms", snap_build_ms,
         "<=", budget.get("max_snapshot_build_ms", 400)),
        ("pipelined_bound_pods", p_bound, ">=", expect),
        ("pipelined_warm_cycle_s", pres.get("warm_cycle_s"),
         "<=", budget.get("max_pipelined_warm_cycle_s",
                          budget["max_warm_cycle_s"])),
        ("pipeline_overlap_ratio", p_overlap,
         ">=", budget.get("min_overlap_ratio", 0.08)),
        ("http_bound_pods", h_bound, ">=", h_expect),
        ("http_warm_cycle_s", hres.get("warm_cycle_s"),
         "<=", budget.get("max_http_warm_cycle_s", 3.0)),
        ("http_hot_kind_lists", hot_lists() - h_lists0,
         "<=", budget.get("max_http_hot_kind_lists", 10)),
        ("http_full_refreshes",
         METRICS.counters.get("cluster_cache_full_refresh_total", 0)
         - h_refresh0,
         "<=", budget.get("max_http_full_refreshes", 1)),
        ("http_bulk_bind_waves",
         _labeled("bulk_write_batches_total", path="bind_wave")
         - h_waves0,
         ">=", budget.get("min_http_bulk_bind_waves", 1)),
        ("http_bulk_requests",
         _labeled("apiserver_bulk_requests_total", op="create")
         + _labeled("apiserver_bulk_requests_total", op="patch")
         - h_bulk0,
         ">=", budget.get("min_http_bulk_requests", 2)),
        ("frame_cache_hit_ratio", h_ratio,
         ">=", budget.get("min_frame_cache_hit_ratio", 0.3)),
        ("wire_bytes_per_cycle",
         int(round(wire_client_bytes / h_cycles)),
         "<=", wire_budget["max_bytes_per_cycle"]),
        ("wire_syscalls_per_cycle",
         int(round(wire_client_syscalls / h_cycles)),
         "<=", wire_budget["max_syscalls_per_cycle"]),
        ("wire_encodes_per_cycle",
         int(round(wire_encodes / h_cycles)),
         "<=", wire_budget["max_encodes_per_cycle"]),
        # Serve-path re-encodes exclude the compulsory per-mutation
        # append encode, so a disabled/rotted frame cache reads hundreds
        # per cycle here against a near-zero warm baseline.
        ("wire_serve_encodes_per_cycle",
         int(round(wire_serve_encodes / h_cycles)),
         "<=", wire_budget["max_serve_encodes_per_cycle"]),
        ("frame_cache_byte_hit_ratio", wire_byte_hit,
         ">=", wire_budget["min_frame_cache_byte_hit_ratio"]),
        ("wire_spans_grafted", wire_grafted,
         ">=", wire_budget.get("min_spans_grafted", 1)),
    ]

    # Compile-budget gate (kaijit's runtime half): merge the journal
    # the whole run accumulated against the static jit surface and the
    # committed per-kernel signature ceilings.  A kernel the static
    # analyzer never discovered is an ANALYZER GAP and fails loud; a
    # ceiling breach means someone un-bucketed a shape axis (KJT001's
    # runtime shadow) — both invisible to every wall-clock gate above.
    surface = jittrace.discover_surface()
    cb = jittrace.load_budget(compile_budget_path)
    audit = jittrace.validate_observed(
        surface, [jittrace.TRACER.dump()], budget=cb)
    checks_compile = [
        ("compile_unexplained_kernels", len(audit["unexplained"]),
         "<=", 0),
        ("compile_uncovered_kernels", len(audit["uncovered"]),
         "<=", 0),
    ]
    for kern, n_sigs in audit["kernels"].items():
        ceiling = cb["kernels"].get(kern, cb["default_max"])
        checks_compile.append(
            (f"compile_sigs:{kern.rpartition('.')[2]}", n_sigs,
             "<=", ceiling))
    checks.extend(checks_compile)

    failed = []
    for name, got, op, want in checks:
        ok = (got is not None
              and ((op == "<=" and got <= want)
                   or (op == ">=" and got >= want)))
        mark = "ok  " if ok else "FAIL"
        print(f"{mark} {name:32s} {got!r:>12} {op} {want!r}")
        if not ok:
            failed.append(name)

    if args.json:
        print(json.dumps(result))
    if failed:
        print(f"fleet budget: FAILED ({', '.join(failed)}); the "
              f"committed budget is {budget_path}")
        return 1
    print("fleet budget: within budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
