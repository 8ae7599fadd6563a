"""Chaos matrix: re-run the chaos suite under a sweep of fault seeds.

A chaos test that passes once under one seed proves little — the whole
point of deterministic fault injection (``KAI_FAULT_INJECT`` +
``KAI_FAULT_SEED``) is that the SAME scenarios replay under different
interleavings by just changing the seed.  This harness runs the chaos
marker N times, each iteration with a different ``KAI_FAULT_SEED``, and
fails on ANY flake — one red iteration out of twenty is a real
control-plane bug with a reproducing seed, not noise to rerun away.

Usage:

    python -m kai_scheduler_tpu.tools.chaos_matrix --iterations 20
    python -m kai_scheduler_tpu.tools.chaos_matrix --seeds 7,11,13 \
        --tests tests/test_reconciler.py -k commitlog

The tier-1 suite wires a 3-iteration smoke of this harness
(tests/test_reconciler.py::test_chaos_matrix_smoke); the full sweep is
the ``stress`` pytest marker's job (slow-gated).  Exit code 0 = every
iteration green; 1 = at least one flake (the failing seeds are printed
for replay).
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import time

DEFAULT_TESTS = ["tests/test_reconciler.py", "tests/test_device_guard.py"]
# --arena: the device-arena delta suite — fault seeds exercise
# resync-during-delta and breaker-open-during-scatter interleavings
# (tests/test_snapshot_delta.py reads KAI_FAULT_SEED into its rng).
ARENA_TESTS = ["tests/test_snapshot_delta.py"]
# --latency: the pod-lifecycle suite — fault seeds reshuffle watch gaps,
# binder backoff, fenced aborts, and evict/resubmit interleavings while
# the timeline invariants (no leaked open phases, monotone stamps, new
# attempt per resubmit) are asserted each iteration.
LATENCY_TESTS = ["tests/test_lifecycle.py"]
# --incremental: the incremental-ClusterInfo suite — fault seeds
# reshuffle add/del/mod churn across every consumed kind, resync
# boundaries, and fenced evicts while incremental-vs-full equivalence
# (and identical allocate placements) is asserted at every step.
INCREMENTAL_TESTS = ["tests/test_incremental_cache.py"]
# --fused: the fused-allocation parity ring — each seed regenerates the
# randomized workloads (tests/test_fused_parity.py reads KAI_FAULT_SEED
# into its instance generator) and re-proves jnp/Pallas bit-identity
# to the exact kernel plus the breaker-open fallback.
FUSED_TESTS = ["tests/test_fused_parity.py"]
# --shards: the concurrent-sharded-schedulers churn ring — each seed
# reshuffles the submit/complete stream while two shards cycle in real
# threads against one apiserver, asserting zero double-binds,
# fenced-loser abort, and cross-shard reclaim; plus the queue-forest
# fair-share parity ring (the division both shards rely on), whose
# randomized forests the seed also regenerates.
SHARDS_TESTS = ["tests/test_concurrent_shards.py",
                "tests/test_fairshare_forest.py"]
# --pipeline: the overlapped-cycle suite — each seed reshuffles the
# randomized churn stream while serial-vs-pipelined placement
# bit-identity, fenced-depose speculation rollback, crash-after-journal
# replay, and breaker-open drain-to-serial are asserted.
PIPELINE_TESTS = ["tests/test_pipeline_cycle.py"]
# --columnar: the columnar host-state parity ring — each seed reshuffles
# the randomized watch-delta stream (add/del/mod/resync/fence, plus
# speculative overlays and vocab overflow) while columnar-vs-object
# ClusterInfo equivalence, pack bit-identity, and identical allocate
# placements are asserted at every step.
COLUMNAR_TESTS = ["tests/test_columnar_store.py"]
# --timeaware: the rank & time subsystem rings — each seed regenerates
# the randomized topologies/gangs of the rank-placement parity ring
# (kernel-vs-host bit-identity, hop optimality, parse conventions) and
# re-runs the usage-tensor decay properties (kernel/numpy parity,
# half-life exactness, window cap, restart restore, stale->degraded)
# plus the full-System timeaware trace (over-user yields on bound-pod
# counts, single-dispatch pin, restart survival).
TIMEAWARE_TESTS = ["tests/test_rankplace.py", "tests/test_usagedb.py",
                   "tests/test_timeaware.py"]
# --wire: the daemon-scale apiserver transport ring — pagination
# cursors under concurrent mutation, 410-GONE continue recovery,
# field-selector parity across dialects, per-item bulk outcomes (fenced
# items, torn batch items, crash-after-journal replay through the batch
# path), pool-saturation backpressure, and the watch-mode cache's
# zero-whole-kind-list steady state over a real loopback wire.
WIRE_TESTS = ["tests/test_wire_protocol.py"]
# --wire-faults: the lying-wire ring — each seed reshuffles the churn
# stream while the wire-* fault family (truncated/corrupted watch
# frames, stalled streams, connection reset mid-bulk-POST, 429/503
# storms, 410-GONE compaction storms, response drops) is injected under
# a full System over loopback HTTP, asserting zero double-binds, zero
# lost pods, anti-entropy digest convergence, and bounded cycles —
# including scheduler crash-replay and apiserver restart (seq
# regression) mid bulk-bind-wave.
WIRE_FAULT_TESTS = ["tests/test_wire_faults.py"]
# --wiretrace: the wire-observatory ring (PR 19) — distributed trace
# joins (client wire spans + grafted server_request/phase spans, one
# trace id, Perfetto-exportable), /debug/spans cursor + bounded span
# ring + self-exclusion, graft idempotence (re-grafting the same window
# adds nothing) and client/server byte reconciliation under
# wire-corrupt/reset/drop, and the watch depth-cap GONE contract.
WIRETRACE_TESTS = ["tests/test_wiretrace.py"]
# --compile: the compile-contract ring — the kernel-heaviest suites
# (fused-parity regenerates randomized workloads per seed; rankplace
# and usagedb sweep the rank & time kernels) run with KAI_JITTRACE=1
# (utils/jittrace.py journals each kernel's abstract call signatures =
# XLA compilation keys) and the merged journals are validated against
# the static kaijit surface: a kernel that compiled at runtime but was
# never discovered statically is an analyzer gap and fails the sweep.
COMPILE_TESTS = ["tests/test_fused_parity.py", "tests/test_rankplace.py",
                 "tests/test_usagedb.py"]


def run_iteration(seed: int, tests: list[str], marker: str,
                  keyword: str | None, repo_root: str,
                  timeout_s: float,
                  trace_dir: str | None = None,
                  extra_env: dict | None = None) -> tuple[bool, float, str]:
    """One pytest run under one fault seed; (passed, seconds, tail)."""
    cmd = [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
           "-p", "no:randomly", "-m", marker, *tests]
    # Never select the matrix-harness tests themselves: an iteration
    # that re-runs the smoke/sweep would spawn pytest recursively.
    cmd += ["-k", f"({keyword}) and not chaos_matrix" if keyword
            else "not chaos_matrix"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               KAI_FAULT_SEED=str(seed))
    # The matrix must control the fault spec per test, not inherit an
    # outer one armed for a different experiment.
    env.pop("KAI_FAULT_INJECT", None)
    # Likewise the locktrace contract: only --races arms it, with a
    # per-seed journal path — an inherited KAI_LOCKTRACE would make
    # iterations overwrite each other's dumps.
    for var in ("KAI_LOCKTRACE", "KAI_LOCKTRACE_OUT",
                "KAI_LOCKTRACE_GRAPH"):
        env.pop(var, None)
    # Same for the compile-signature journal: only --compile arms it.
    for var in ("KAI_JITTRACE", "KAI_JITTRACE_OUT"):
        env.pop(var, None)
    env.update(extra_env or {})
    if trace_dir:
        # The flight recorder (utils/tracing.py) dumps every aborted or
        # degraded cycle's Chrome trace JSON here — the post-mortem
        # artifact for a flaking seed.
        env["KAI_TRACE_DIR"] = trace_dir
    else:
        env.pop("KAI_TRACE_DIR", None)
    t0 = time.monotonic()
    try:
        proc = subprocess.run(cmd, cwd=repo_root, env=env,
                              capture_output=True, text=True,
                              timeout=timeout_s)
        out = (proc.stdout or "") + (proc.stderr or "")
        return proc.returncode == 0, time.monotonic() - t0, out[-2000:]
    except subprocess.TimeoutExpired as exc:
        out = ((exc.stdout or b"").decode(errors="replace")
               if isinstance(exc.stdout, bytes) else (exc.stdout or ""))
        return False, time.monotonic() - t0, \
            f"TIMEOUT after {timeout_s:g}s\n{out[-1000:]}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("kai-chaos-matrix")
    ap.add_argument("--iterations", type=int, default=5,
                    help="number of runs (seeds default to 1..N)")
    ap.add_argument("--seeds", default=None,
                    help="comma-separated explicit KAI_FAULT_SEED sweep "
                         "(overrides --iterations)")
    ap.add_argument("--tests", nargs="*", default=None,
                    help=f"test paths (default: {DEFAULT_TESTS})")
    ap.add_argument("--arena", action="store_true",
                    help="arena mode: sweep the device-arena delta suite "
                         f"({ARENA_TESTS}) — each seed reshuffles the "
                         "event interleavings around resync-during-delta "
                         "and breaker-open-during-scatter")
    ap.add_argument("--latency", action="store_true",
                    help="latency mode: sweep the pod-lifecycle suite "
                         f"({LATENCY_TESTS}) — each seed reshuffles "
                         "watch-gap/backoff/abort interleavings while "
                         "the timeline invariants are asserted")
    ap.add_argument("--incremental", action="store_true",
                    help="incremental mode: sweep the incremental-"
                         f"ClusterInfo suite ({INCREMENTAL_TESTS}) — "
                         "each seed reshuffles churn/resync/fence "
                         "interleavings while incremental-vs-full "
                         "snapshot equivalence is asserted")
    ap.add_argument("--fused", action="store_true",
                    help="fused mode: sweep the fused-allocation parity "
                         f"ring ({FUSED_TESTS}) — each seed regenerates "
                         "the randomized workloads and re-proves "
                         "jnp/Pallas placement bit-identity to the "
                         "exact kernel")
    ap.add_argument("--shards", action="store_true",
                    help="shards mode: sweep the concurrent-shards churn "
                         f"ring ({SHARDS_TESTS}) — each seed reshuffles "
                         "the submit/complete stream and the randomized "
                         "queue forests while zero-double-bind, "
                         "fenced-loser-abort, and fair-share bit-parity "
                         "are asserted")
    ap.add_argument("--pipeline", action="store_true",
                    help="pipeline mode: sweep the overlapped-cycle "
                         f"suite ({PIPELINE_TESTS}) — each seed "
                         "reshuffles the churn stream while serial-vs-"
                         "pipelined bit-identity, fenced rollback, "
                         "crash-after-journal replay, and breaker-open "
                         "drain-to-serial are asserted")
    ap.add_argument("--columnar", action="store_true",
                    help="columnar mode: sweep the columnar host-state "
                         f"parity ring ({COLUMNAR_TESTS}) — each seed "
                         "reshuffles the watch-delta stream while "
                         "columnar-vs-object equivalence, pack "
                         "bit-identity, and identical allocate "
                         "placements are asserted")
    ap.add_argument("--timeaware", action="store_true",
                    help="timeaware mode: sweep the rank & time "
                         f"subsystem rings ({TIMEAWARE_TESTS}) — each "
                         "seed regenerates the randomized rank-"
                         "placement instances and re-proves kernel/"
                         "host bit-identity, decay-math parity, and "
                         "the over-user-yields trace")
    ap.add_argument("--wire", action="store_true",
                    help="wire mode: sweep the apiserver transport ring "
                         f"({WIRE_TESTS}) — pagination under mutation, "
                         "GONE-continue recovery, field-selector "
                         "dialect parity, per-item bulk outcomes, pool "
                         "backpressure, and the zero-whole-kind-list "
                         "steady state over a real loopback wire")
    ap.add_argument("--wire-faults", action="store_true",
                    help="wire-faults mode: sweep the lying-wire ring "
                         f"({WIRE_FAULT_TESTS}) — each seed reshuffles "
                         "the churn stream under injected wire faults "
                         "(truncate/corrupt/stall/reset/storm/GONE/"
                         "drop) while zero-double-bind, zero-lost-pod, "
                         "and anti-entropy digest convergence are "
                         "asserted, incl. crash-replay and apiserver "
                         "restart mid bulk-bind-wave")
    ap.add_argument("--wiretrace", action="store_true",
                    help="wire-observatory mode: sweep the distributed-"
                         f"tracing ring ({WIRETRACE_TESTS}) — each seed "
                         "reshuffles fleet churn while trace joins "
                         "(grafted server spans, one trace id), graft "
                         "idempotence, client/server byte "
                         "reconciliation under wire-corrupt/reset/drop, "
                         "the bounded /debug/spans ring, and the watch "
                         "depth-cap GONE contract are asserted.  "
                         "Composes with --wire/--wire-faults/--pipeline")
    ap.add_argument("--races", action="store_true",
                    help="runtime lock-order validation: every iteration "
                         "runs with KAI_LOCKTRACE=1 (threading factories "
                         "traced, per-thread acquisition orders recorded "
                         "— utils/locktrace.py) and the merged observed "
                         "orders are checked against the static kairace "
                         "lock graph; any contradiction, uncovered "
                         "threaded subsystem, or empty journal fails "
                         "the sweep.  Composes with every mode flag")
    ap.add_argument("--compile", action="store_true",
                    help="compile-contract validation: sweep the kernel-"
                         f"heaviest suites ({COMPILE_TESTS}) with "
                         "KAI_JITTRACE=1 (every jitted kernel journals "
                         "its abstract call signatures = XLA compile "
                         "keys — utils/jittrace.py) and validate the "
                         "merged journals against the static kaijit "
                         "surface; a runtime compile from a kernel the "
                         "static model never discovered, or an empty "
                         "journal, fails the sweep.  Composes with "
                         "every mode flag (adds its suites + arms the "
                         "tracer for all of them)")
    ap.add_argument("-k", "--keyword", default=None,
                    help="pytest -k filter (narrow the smoke subset)")
    ap.add_argument("--marker", default="chaos",
                    help="pytest marker to select (default: chaos)")
    ap.add_argument("--timeout", type=float, default=300.0,
                    help="per-iteration timeout in seconds")
    ap.add_argument("--trace-dir", default=None,
                    help="keep each FAILING iteration's cycle traces "
                         "(Chrome trace JSON from the flight recorder) "
                         "under <dir>/seed<seed>/ for post-mortem; "
                         "passing iterations' traces are cleaned up")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the fault grid (seed/tests/marker/"
                         "timeout per iteration) without running "
                         "anything — lets CI validate the matrix "
                         "definition cheaply")
    args = ap.parse_args(argv)

    seeds = ([int(s) for s in args.seeds.split(",") if s.strip()]
             if args.seeds else list(range(1, args.iterations + 1)))
    if args.tests:
        tests = args.tests
    else:
        # Modes compose: --arena --latency --incremental --fused
        # --shards --pipeline --columnar --timeaware --wire
        # --wire-faults sweeps every selected suite per seed.
        tests = (ARENA_TESTS if args.arena else []) + \
            (LATENCY_TESTS if args.latency else []) + \
            (INCREMENTAL_TESTS if args.incremental else []) + \
            (FUSED_TESTS if args.fused else []) + \
            (SHARDS_TESTS if args.shards else []) + \
            (PIPELINE_TESTS if args.pipeline else []) + \
            (COLUMNAR_TESTS if args.columnar else []) + \
            (TIMEAWARE_TESTS if args.timeaware else []) + \
            (WIRE_TESTS if args.wire else []) + \
            (WIRE_FAULT_TESTS if args.wire_faults else []) + \
            (WIRETRACE_TESTS if args.wiretrace else []) + \
            (COMPILE_TESTS if args.compile else [])
        if not tests:
            tests = DEFAULT_TESTS
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    if args.trace_dir:
        # The child resolves KAI_TRACE_DIR against cwd=repo_root while
        # the cleanup below resolves against the invoker's cwd — pin
        # both to one absolute path.
        args.trace_dir = os.path.abspath(args.trace_dir)

    def seed_trace_dir(seed: int) -> str | None:
        return (os.path.join(args.trace_dir, f"seed{seed}")
                if args.trace_dir else None)

    if args.dry_run:
        for seed in seeds:
            print(f"seed {seed:>6}  marker={args.marker}  "
                  f"keyword={args.keyword or '-'}  "
                  f"timeout={args.timeout:g}s  "
                  f"trace-dir={seed_trace_dir(seed) or '-'}  "
                  f"races={'on' if args.races else 'off'}  "
                  f"compile={'on' if args.compile else 'off'}  "
                  f"tests={' '.join(tests)}",
                  flush=True)
        if args.races:
            print("races mode: each iteration runs with KAI_LOCKTRACE=1 "
                  "+ a per-seed journal; merged orders are validated "
                  "against the static kairace lock graph", flush=True)
        if args.compile:
            print("compile mode: each iteration runs with KAI_JITTRACE=1 "
                  "+ a per-seed journal; merged compile signatures are "
                  "validated against the static kaijit surface",
                  flush=True)
        print(f"\nchaos matrix (dry run): {len(seeds)} iteration(s) "
              f"planned, nothing executed", flush=True)
        return 0

    races_dir, races_graph = None, None
    if args.races:
        # The static contract is computed ONCE per sweep (the package
        # doesn't change mid-run) and handed to every iteration: the
        # child validates online (live contradiction counters in
        # /metrics), the parent re-validates the merged journals below.
        import json as _json
        import tempfile

        from .kairace.cli import lock_graph, package_root
        races_graph = lock_graph([package_root()])
        if races_graph["errors"]:
            for err in races_graph["errors"]:
                print(f"races: static-graph parse error: {err}",
                      flush=True)
            return 1
        races_dir = tempfile.mkdtemp(prefix="kai-locktrace-")
        graph_path = os.path.join(races_dir, "lock_graph.json")
        with open(graph_path, "w", encoding="utf-8") as fh:
            _json.dump(races_graph, fh)
        print(f"races: static lock graph: "
              f"{len(races_graph['locks'])} lock(s), "
              f"{len(races_graph['edges'])} order edge(s)", flush=True)

    def races_env(seed: int) -> dict:
        if not args.races:
            return {}
        return {"KAI_LOCKTRACE": "1",
                "KAI_LOCKTRACE_OUT": os.path.join(races_dir,
                                                  f"seed{seed}.json"),
                "KAI_LOCKTRACE_GRAPH": os.path.join(races_dir,
                                                    "lock_graph.json")}

    compile_dir, compile_surface = None, None
    if args.compile:
        # The static jit surface is computed ONCE per sweep — the SAME
        # discovery kaijit runs (tools/kailint/jitsurface.py), so the
        # journal and the static model cannot drift.
        import tempfile

        from ..utils.jittrace import discover_surface
        compile_surface = discover_surface()
        if compile_surface["errors"]:
            for err in compile_surface["errors"]:
                print(f"compile: static-surface parse error: {err}",
                      flush=True)
            return 1
        compile_dir = tempfile.mkdtemp(prefix="kai-jittrace-")
        n_jitted = sum(1 for d in compile_surface["kernels"].values()
                       if d.get("jitted"))
        print(f"compile: static jit surface: {n_jitted} jitted "
              f"kernel(s) across "
              f"{len(compile_surface['kernels'])} surface entries",
              flush=True)

    def compile_env(seed: int) -> dict:
        if not args.compile:
            return {}
        return {"KAI_JITTRACE": "1",
                "KAI_JITTRACE_OUT": os.path.join(compile_dir,
                                                 f"seed{seed}.json")}

    rows, failed = [], []
    for seed in seeds:
        tdir = seed_trace_dir(seed)
        ok, secs, tail = run_iteration(seed, tests, args.marker,
                                       args.keyword, repo_root,
                                       args.timeout, trace_dir=tdir,
                                       extra_env={**races_env(seed),
                                                  **compile_env(seed)})
        rows.append((seed, ok, secs))
        status = "ok" if ok else "FLAKE"
        print(f"seed {seed:>6}  {status:<5}  {secs:6.1f}s", flush=True)
        if ok and tdir:
            # Chaos tests abort cycles on purpose; only a flaking seed's
            # traces are post-mortem material.
            shutil.rmtree(tdir, ignore_errors=True)
        if not ok:
            failed.append(seed)
            if tdir and os.path.isdir(tdir):
                print(f"cycle traces kept in {tdir}", flush=True)
            print(tail, flush=True)

    print(f"\nchaos matrix: {len(rows) - len(failed)}/{len(rows)} green",
          flush=True)

    races_red = False
    if args.races:
        races_red = not _report_races(races_dir, races_graph, seeds)
        if races_red or failed:
            # Post-mortem material: the per-seed journals + the static
            # graph they were validated against.
            print(f"races: journals kept in {races_dir}", flush=True)
        else:
            # A green sweep's journals are pure $TMPDIR litter —
            # repeated CI/soak runs would accumulate them unbounded.
            shutil.rmtree(races_dir, ignore_errors=True)

    compile_red = False
    if args.compile:
        compile_red = not _report_compile(compile_dir, compile_surface,
                                          seeds)
        if compile_red or failed:
            print(f"compile: journals kept in {compile_dir}", flush=True)
        else:
            shutil.rmtree(compile_dir, ignore_errors=True)

    if failed:
        print("replay a flake with: "
              f"KAI_FAULT_SEED={failed[0]} python -m pytest -m "
              f"{args.marker} {' '.join(tests)}", flush=True)
        return 1
    return 1 if (races_red or compile_red) else 0


def _report_races(races_dir: str, graph: dict, seeds: list) -> bool:
    """Merge the per-seed locktrace journals, validate against the
    static graph, print the coverage table.  True = validator green."""
    import json as _json

    from ..utils.locktrace import validate_observed
    dumps = []
    for seed in seeds:
        path = os.path.join(races_dir, f"seed{seed}.json")
        try:
            with open(path, encoding="utf-8") as fh:
                dumps.append(_json.load(fh))
        except (OSError, ValueError):
            print(f"races: seed {seed}: no journal at {path} "
                  f"(iteration died before the atexit dump?)",
                  flush=True)
    report = validate_observed(graph, dumps)

    print("\nraces: observed lock orders per threaded subsystem:",
          flush=True)
    for sub, ent in report["subsystems"].items():
        print(f"  {sub:<34} locks={ent['locks_created']:>4}  "
              f"acquires={ent['acquires']:>7}  "
              f"orders={ent['orders']:>3}", flush=True)
    print(f"races: {len(report['orders'])} distinct order(s), "
          f"{len(report['contradictions'])} contradiction(s), "
          f"{len(report['uncovered_subsystems'])} uncovered "
          f"subsystem(s)", flush=True)
    for c in report["contradictions"]:
        a, b = c["observed"]
        print(f"races: CONTRADICTION: observed {a} -> {b} but the "
              f"static graph orders {c['static_path']} — the analyzer "
              f"missed an acquisition path or an annotation rotted",
              flush=True)
    for sub in report["uncovered_subsystems"]:
        print(f"races: UNCOVERED: {sub} created statically-known locks "
              f"but recorded zero acquisitions — the sweep never "
              f"exercised it", flush=True)
    if not report["orders"]:
        print("races: EMPTY journal — a validator that records nothing "
              "validates nothing", flush=True)
    return report["ok"]


def _report_compile(compile_dir: str, surface: dict,
                    seeds: list) -> bool:
    """Merge the per-seed jittrace journals, validate against the
    static kaijit surface, print the signature table.  True = green."""
    import json as _json

    from ..utils.jittrace import validate_observed
    dumps = []
    for seed in seeds:
        path = os.path.join(compile_dir, f"seed{seed}.json")
        try:
            with open(path, encoding="utf-8") as fh:
                dumps.append(_json.load(fh))
        except (OSError, ValueError):
            print(f"compile: seed {seed}: no journal at {path} "
                  f"(iteration died before the atexit dump?)",
                  flush=True)
    report = validate_observed(surface, dumps)

    print("\ncompile: distinct signatures (XLA compile keys) per "
          "kernel, max across seeds:", flush=True)
    for kernel, n in report["kernels"].items():
        short = kernel.replace("kai_scheduler_tpu.", "")
        print(f"  {short:<44} sigs={n:>3}  "
              f"calls={report['calls'].get(kernel, 0):>7}", flush=True)
    print(f"compile: {len(report['kernels'])} kernel(s) journaled, "
          f"{len(report['unexplained'])} unexplained", flush=True)
    for kernel in report["unexplained"]:
        print(f"compile: UNEXPLAINED: {kernel} compiled at runtime but "
              f"the static kaijit surface never discovered it — the "
              f"analyzer's discovery has a gap", flush=True)
    if not report["kernels"]:
        print("compile: EMPTY journal — a validator that records "
              "nothing validates nothing", flush=True)
    return report["ok"]


if __name__ == "__main__":
    sys.exit(main())
