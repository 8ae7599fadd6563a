"""Scheduler daemon entry point: flags, HTTP endpoints, leader election.

Mirrors cmd/scheduler/app (RunApp server.go:103, options
options.go, leader election server.go:196-240, /metrics :184-187, pprof
profiling/profiler.go) for the embedded deployment: a CLI that assembles
the System (operator), runs the scheduling loop, and serves observability
endpoints:

  GET /metrics        Prometheus text (utils/metrics.py)
  GET /get-snapshot   full cluster+config dump (snapshot plugin)
  GET /job-order      current job ordering per queue (reflectjoborder)
  GET /healthz        liveness + device-guard breaker state: a tripped
                      breaker reports {"status": "degraded", ...} with
                      HTTP 200 — the daemon is alive and scheduling on
                      the CPU fallback path, not dead (docs/DEGRADATION.md)
  GET /debug/cycles   flight recorder: last-N cycle summaries (duration,
                      span breakdown, abort/degraded flags)
  GET /debug/trace    Chrome trace-event JSON for one cycle
                      (?cycle=<trace id | cycle number>; default latest)
                      — load in Perfetto (docs/OBSERVABILITY.md)
  GET /explain        latest unschedulability reasons for a PodGroup
                      (?podgroup=<name>; without it, the known names)
  GET /debug/latency  pod-lifecycle timelines (submit -> watch-observed ->
                      grouped -> snapshotted -> scheduled -> bind-requested
                      -> bound/evicted) joined to the /explain ledger
                      (?queue=|podgroup=|limit=; docs/OBSERVABILITY.md)
  GET /debug/flame    the continuous fleet profiler's folded stacks,
                      flamegraph/speedscope-ready (utils/stackprof.py;
                      arm with --stackprof, --enable-profiler or
                      KAI_STACKPROF=1; ?summary=1 for the leaf table;
                      /debug/pprof and /debug/profile are the same page)

Leader election comes in two flavors:

- ``--leader-elect`` with no ``--api-server``: an fcntl file lock.
  **Single-machine scope only** — flock serializes processes sharing one
  filesystem; two replicas on different hosts would both become leader.
- ``--leader-elect`` with ``--api-server URL``: a distributed coordination
  Lease through the shared API store (utils/leaderelect.py), matching the
  reference's Lease-based election (server.go:196-240) across hosts.
"""

from __future__ import annotations

import argparse
import fcntl
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from .controllers import ShardSpec, System, SystemConfig
from .framework.conf import SchedulerConfig
from .plugins.snapshot_plugin import dump_cluster
from .utils import parse_bool as _parse_bool
from .utils import wireobs
from .utils.deviceguard import configure_device_guard, device_guard
from .utils.lifecycle import LIFECYCLE
from .utils.jittrace import TRACER as JITTRACE
from .utils.jittrace import sync_metrics as jittrace_sync_metrics
from .utils.locktrace import TRACER as LOCKTRACE
from .utils.locktrace import sync_metrics as locktrace_sync_metrics
from .utils.logging import LOG, init_loggers
from .utils.metrics import METRICS
from .utils.stackprof import STACKPROF, ensure_started_from_env
from .utils.tracing import TRACER


def healthz_payload(state: dict | None = None) -> dict:
    """Liveness + degraded-mode report: alive is HTTP 200 regardless;
    ``status`` flips to "degraded" while the device-guard breaker is not
    closed (scheduling continues on the CPU fallback path).  ``device``
    names the platform, device kind and device count JAX reported at
    start-up and ``node_store`` the session's node-mirror backing.  When the
    daemon runs leader-elected/journaled, a ``control_plane`` section
    reports the leadership epoch, watch-gap count, and the last startup
    reconcile summary (docs/DEGRADATION.md failure matrix)."""
    guard = device_guard()
    payload = {"status": "degraded" if guard.degraded else "ok",
               "device_guard": guard.status()}
    state = state or {}
    if state.get("device") is not None:
        # What JAX found at start-up (run_app records it once; the HTTP
        # thread never asks the backend): a daemon that came up on the
        # CPU says so here instead of passing for an accelerator run.
        payload["device"] = state["device"]
    ssn = state.get("last_session")
    if ssn is not None:
        payload["node_store"] = ssn.node_store
    if LOCKTRACE.installed:
        # Runtime lock-order validator (KAI_LOCKTRACE=1): surface the
        # journal so a fleet run shows the validator actually recorded
        # orders — and loudly shows any contradiction vs the static
        # kairace graph (docs/STATIC_ANALYSIS.md).
        locktrace_sync_metrics()
        payload["locktrace"] = LOCKTRACE.stats()
    if JITTRACE.installed:
        # Runtime compile-budget audit (KAI_JITTRACE=1): surface the
        # compile-signature journal so a fleet run shows the tracer is
        # recording — the offline half (fleet_budget / chaos_matrix
        # --compile) merges the journals against the static kaijit
        # model (docs/STATIC_ANALYSIS.md).
        jittrace_sync_metrics()
        payload["jittrace"] = JITTRACE.stats()
    elector = state.get("lease_elector")
    control: dict = {}
    if elector is not None:
        control["leader"] = bool(elector.is_leader)
        control["epoch"] = elector.epoch
    if state.get("reconcile_summary") is not None:
        control["startup_reconcile"] = state["reconcile_summary"]
    gaps = METRICS.counters.get("watch_gap_total")
    if gaps:
        control["watch_gaps"] = gaps
    if control:
        payload["control_plane"] = control
    # Degraded observability must itself be observable: a full lifecycle
    # ring or a profiler that silently never started reads right here.
    payload["observability"] = {
        "lifecycle": LIFECYCLE.status(),
        "stackprof": STACKPROF.status(),
    }
    executor = getattr(state.get("system"), "commit_executor", None)
    if executor is not None:
        # Overlapped pipeline: queue depth / poison state — a poisoned
        # executor means the fleet fell back to the serial cycle path.
        payload["pipeline"] = executor.stats()
    anti_entropy: dict = {}
    checks = METRICS.counters.get("anti_entropy_checks_total")
    if checks:
        anti_entropy["checks"] = checks
    divergence = sum(v for name, v in METRICS.counters.items()
                     if name.startswith("cache_divergence_total"))
    if divergence:
        # Any non-zero here means the wire lied at least once and the
        # self-healing path ran — the DEGRADATION table's
        # "anti-entropy" rows.
        anti_entropy["divergence"] = divergence
    # The SCHEDULERS' caches are the verified replicas (each shard
    # builds its own; System.cache never snapshots, so its verdict is
    # forever empty).
    system = state.get("system")
    caches = [s.cache for s in getattr(system, "schedulers", None) or ()]
    last = next((c.last_anti_entropy for c in caches
                 if getattr(c, "last_anti_entropy", None)), None)
    if last is not None:
        anti_entropy["last"] = last
        anti_entropy["columnar_quarantined"] = any(
            getattr(c, "_columnar_quarantined", False) for c in caches)
    if anti_entropy:
        payload["anti_entropy"] = anti_entropy
    return payload


class LeaderElector:
    """flock-based lease. SINGLE-MACHINE ONLY: flock serializes processes
    on one host's filesystem; use utils.leaderelect.LeaseElector (backed by
    the shared API store) for multi-host deployments."""

    def __init__(self, lock_path: str):
        self.lock_path = lock_path
        self._fh = None

    def acquire(self, poll_seconds: float = 1.0) -> None:
        self._fh = open(self.lock_path, "a+")
        while True:
            try:
                fcntl.flock(self._fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
                self._fh.seek(0)
                self._fh.truncate()
                self._fh.write(str(os.getpid()))
                self._fh.flush()
                return
            except BlockingIOError:
                time.sleep(poll_seconds)

    def release(self) -> None:
        if self._fh is not None:
            fcntl.flock(self._fh, fcntl.LOCK_UN)
            self._fh.close()
            self._fh = None


def _make_handler(server_state):
    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            from urllib.parse import parse_qs
            path, _, raw_query = self.path.partition("?")
            q = {k: v[0] for k, v in parse_qs(raw_query).items()}
            if path == "/metrics":
                if LOCKTRACE.installed:
                    locktrace_sync_metrics()
                if JITTRACE.installed:
                    jittrace_sync_metrics()
                body = METRICS.to_prometheus_text().encode()
                ctype = "text/plain"
            elif path == "/healthz":
                body = json.dumps(healthz_payload(server_state)).encode()
                ctype = "application/json"
            elif path == "/get-snapshot":
                ssn = server_state.get("last_session")
                body = json.dumps(
                    dump_cluster(ssn) if ssn else {}).encode()
                ctype = "application/json"
            elif path == "/job-order":
                body = json.dumps(
                    server_state.get("job_order", {})).encode()
                ctype = "application/json"
            elif path == "/debug/cycles":
                # Flight recorder: last-N cycle summaries, newest first,
                # plus the device arena's pack/residency stats (delta
                # ratio, generation, full-rebuild and scatter totals).
                payload = {"capacity": TRACER.capacity,
                           "cycles": TRACER.cycles()}
                ssn = server_state.get("last_session")
                arena = getattr(getattr(ssn, "cache", None), "arena",
                                None)
                if arena is not None:
                    payload["arena"] = arena.stats()
                cache_stats = getattr(getattr(ssn, "cache", None),
                                      "last_snapshot_stats", None)
                if cache_stats:
                    # Incremental host pipeline: last snapshot's dirty
                    # counts, store sizes, and watch-delta mode.
                    payload["incremental_cache"] = cache_stats
                wire = wireobs.wire_totals()
                if wire:
                    # Wire observatory: cumulative byte/syscall/frame-cache
                    # totals across both transport ends.  Per-cycle deltas
                    # ride each cycle summary's "wire" section.
                    payload["wire"] = wire
                system = server_state.get("system")
                executor = getattr(system, "commit_executor", None)
                if executor is not None:
                    # Overlapped pipeline: per-cycle stage overlap plus
                    # the commit executor's live state (DESIGN §10).
                    payload["pipeline"] = {
                        "executor": executor.stats(),
                        "recent_cycles": list(system.pipeline_stats),
                    }
                body = json.dumps(payload).encode()
                ctype = "application/json"
            elif path == "/debug/trace":
                # Serialized under the ring lock: async commit-stage
                # spans may still be attaching to a finalized trace.
                chrome = TRACER.export_chrome(q.get("cycle"))
                if chrome is None:
                    self.send_error(
                        404, "no such cycle trace (list: /debug/cycles)")
                    return
                body = json.dumps(chrome).encode()
                ctype = "application/json"
            elif path == "/explain":
                name = q.get("podgroup")
                if not name:
                    body = json.dumps({
                        "podgroups": TRACER.explained_podgroups()}).encode()
                else:
                    record = TRACER.explain_for(name)
                    if record is None:
                        self.send_error(
                            404, f"no recorded rejection for podgroup "
                                 f"{name!r}")
                        return
                    body = json.dumps(record).encode()
                ctype = "application/json"
            elif path == "/debug/latency":
                # Lifecycle observatory: timelines (filtered by queue /
                # podgroup) joined to the flight recorder's /explain
                # ledger and the status updater's Unschedulable marks.
                try:
                    limit = max(1, min(2000, int(q.get("limit", 200))))
                except ValueError:
                    self.send_error(400, "limit must be an integer")
                    return
                payload = {
                    "status": LIFECYCLE.status(),
                    "pod_latency": LIFECYCLE.summary(),
                    "timelines": LIFECYCLE.timelines(
                        queue=q.get("queue"),
                        podgroup=q.get("podgroup"), limit=limit),
                }
                podgroup = q.get("podgroup")
                if podgroup:
                    payload["explain"] = TRACER.explain_for(podgroup)
                    mark = LIFECYCLE.group_mark(podgroup)
                    if mark:
                        payload["unschedulable_message"] = mark
                body = json.dumps(payload).encode()
                ctype = "application/json"
            elif path in ("/debug/flame", "/debug/pprof",
                          "/debug/profile"):
                # Continuous fleet profiler (whole-cycle host stacks, not
                # just run_once): folded format for flamegraph.pl /
                # speedscope, or the leaf table with ?summary=1.
                if not STACKPROF.running and not STACKPROF.total_samples:
                    self.send_error(
                        404, "stackprof not running (arm with --stackprof, "
                             "--enable-profiler or KAI_STACKPROF=1)")
                    return
                if q.get("summary") in ("1", "true"):
                    body = json.dumps({
                        **STACKPROF.status(),
                        "total_samples": STACKPROF.total_samples,
                        "top_leaves": STACKPROF.top_frames(30)}).encode()
                    ctype = "application/json"
                else:
                    try:
                        # Clamped: top=0/-1 would silently drop the
                        # heaviest stacks via slice semantics.
                        top = max(1, min(1 << 20, int(q.get("top", 5000))))
                    except ValueError:
                        self.send_error(400, "top must be an integer")
                        return
                    body = STACKPROF.folded(top=top).encode()
                    ctype = "text/plain"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    return Handler


def _job_order_dump(ssn) -> dict:
    """reflectjoborder analog: expose the queue/job ordering."""
    from .actions.utils import JobsOrderByQueues
    jobs = [pg for pg in ssn.cluster.podgroups.values()
            if pg.has_tasks_to_allocate() and pg.queue_id
            in ssn.cluster.queues]
    order = JobsOrderByQueues(ssn, jobs)
    out = []
    while not order.empty():
        job = order.pop_next_job()
        if job is None:
            break
        out.append({"job": job.name, "queue": job.queue_id})
        order.requeue_queue(job.queue_id)
        if len(out) > 1000:
            break
    return {"order": out}


def run_app(argv=None) -> None:
    ap = argparse.ArgumentParser("kai-scheduler-tpu")
    ap.add_argument("--schedule-period", type=float, default=1.0)
    ap.add_argument("--http-port", type=int, default=8080)
    ap.add_argument("--verbosity", "-v", type=int, default=0)
    # Both `--leader-elect` and `--leader-elect=false` are valid: chart
    # values templating renders the explicit form.
    ap.add_argument("--leader-elect", nargs="?", const=True, default=False,
                    type=_parse_bool)
    ap.add_argument("--lock-file", default="/tmp/kai-scheduler-tpu.lock")
    ap.add_argument("--api-server", default=None,
                    help="URL of a kai-apiserver; the fleet then runs over "
                         "HTTP instead of the embedded in-memory API, and "
                         "--leader-elect uses a distributed Lease")
    ap.add_argument("--lease-name", default="kai-scheduler")
    ap.add_argument("--lease-duration", type=float, default=15.0)
    ap.add_argument("--controllers-only", action="store_true",
                    help="run the companion-controller fleet without a "
                         "scheduler (the controllers Deployment's mode)")
    ap.add_argument("--node-pool-label", default=None)
    ap.add_argument("--node-pool", default=None)
    ap.add_argument("--k-value", type=float, default=1.0)
    ap.add_argument("--actions", default=None,
                    help="comma-separated action order override")
    ap.add_argument("--cycles", type=int, default=0,
                    help="stop after N cycles (0 = forever)")
    ap.add_argument("--enable-profiler", action="store_true",
                    help="same as --stackprof (the pprof/Pyroscope "
                         "analog, cmd/scheduler/profiling/): collapsed "
                         "stacks at GET /debug/flame")
    ap.add_argument("--profile-dir", default=None,
                    help="write a JAX profiler trace of the run here "
                         "(the pprof/Pyroscope analog)")
    ap.add_argument("--stackprof", action="store_true",
                    help="continuous whole-fleet host profiler "
                         "(utils/stackprof.py, ~67Hz, ring-bounded): "
                         "folded stacks at GET /debug/flame; "
                         "KAI_STACKPROF=1 arms it too, KAI_STACKPROF_DIR "
                         "dumps the profile on exit")
    ap.add_argument("--usage-db", default=None,
                    help="usage client spec for time-based fairness, "
                         "e.g. memory://")
    ap.add_argument("--cycle-deadline", type=float, default=0.0,
                    help="whole-cycle deadline in seconds (0 disables): "
                         "past it the cycle aborts with statement "
                         "rollback and the daemon moves on degraded")
    ap.add_argument("--device-deadline", type=float, default=None,
                    help="per-dispatch watchdog deadline in seconds "
                         "(default KAI_DEVICE_DEADLINE_S or 30)")
    ap.add_argument("--fault-inject", default=None,
                    help="deterministic device-fault injection for the "
                         "chaos ring: hang | slow:<ms> | error | "
                         "flaky:<p> | badshape (KAI_FAULT_INJECT analog)")
    ap.add_argument("--commit-log", default=None,
                    help="path to the crash-safe bind journal "
                         "(utils/commitlog.py); statement commits "
                         "journal intents and a restart replays them — "
                         "unset disables journaling")
    ap.add_argument("--pipeline", nargs="?", const=True, default=False,
                    type=_parse_bool,
                    help="overlapped fleet cycle (DESIGN §10): commit "
                         "I/O and binder round trips run on a commit-"
                         "executor thread, overlapping the next cycle's "
                         "host prep; drains to the serial path on "
                         "breaker-open or a fenced commit")
    args = ap.parse_args(argv)

    init_loggers(args.verbosity)
    # KAI_LOCKTRACE=1 is honored by the package __init__ (the factories
    # must be patched before module-level singletons create their
    # locks); by the time run_app executes the shim is already live.
    if args.fault_inject or args.device_deadline is not None:
        configure_device_guard(fault=args.fault_inject,
                               deadline_s=args.device_deadline)
    config = SchedulerConfig(k_value=args.k_value,
                             cycle_deadline_s=args.cycle_deadline)
    if args.actions:
        config.actions = [a.strip() for a in args.actions.split(",")]
    api = None
    if args.api_server:
        from .controllers.httpclient import HTTPKubeAPI
        api = HTTPKubeAPI(args.api_server)

    import jax

    from .utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # Touch the backend here, once, on the main thread: the first
    # guarded dispatch then pays a compile and not the runtime's
    # start-up, and /healthz can name the device without asking JAX.
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "count": len(devices)}
    LOG.info("devices: %s; compile cache: %s", device, cache_dir)

    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)

    lease_elector = None
    if args.leader_elect:
        if api is not None:
            from .utils.leaderelect import LeaseElector
            identity = f"{os.uname().nodename}-{os.getpid()}"
            LOG.info("waiting for Lease %s as %s", args.lease_name, identity)
            lease_elector = LeaseElector(api, args.lease_name, identity,
                                         lease_duration=args.lease_duration)
            lease_elector.acquire()
        else:
            LOG.info("waiting for leadership (%s)", args.lock_file)
            elector = LeaderElector(args.lock_file)
            elector.acquire()
        LOG.info("became leader")

    system = System(SystemConfig(
        shards=[ShardSpec("default", args.node_pool_label, args.node_pool,
                          config)],
        usage_db=args.usage_db,
        commitlog_path=args.commit_log,
        pipelined_cycles=bool(args.pipeline),
        scheduling_enabled=not args.controllers_only), api=api)

    state: dict = {"system": system, "device": device}
    if lease_elector is not None:
        # Fenced leadership: scheduler writes carry the Lease epoch; a
        # deposed incarnation's writes are rejected at the store.
        system.set_fence(args.lease_name,
                         lambda: lease_elector.epoch)
        state["lease_elector"] = lease_elector
    # Restart crash-consistency pass BEFORE the first cycle: replay the
    # bind journal, GC orphaned reservations, reap dead BindRequests.
    state["reconcile_summary"] = system.startup_reconcile()
    if args.stackprof or args.enable_profiler:
        STACKPROF.start()
    else:
        ensure_started_from_env()
    handler = _make_handler(state)
    httpd = ThreadingHTTPServer(("127.0.0.1", args.http_port), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    LOG.info("serving http on :%d", httpd.server_port)

    import urllib.error

    cycle = 0
    try:
        while True:
            if lease_elector is not None and not lease_elector.is_leader:
                # The Lease was stolen or could not be renewed: stop
                # scheduling immediately (split-brain guard) and exit so
                # the supervisor restarts us as a candidate.
                LOG.warning("lost leadership; stopping scheduling loop")
                break
            try:
                system.run_cycle()
                if system.schedulers:
                    # Keep the last session for introspection endpoints.
                    ssn = system.schedulers[0].last_session
                    if ssn is not None:
                        state["last_session"] = ssn
                        state["job_order"] = _job_order_dump(ssn)
            except (urllib.error.URLError, ConnectionError,
                    TimeoutError, OSError) as exc:
                # Apiserver unreachable mid-cycle: ride out the outage
                # degraded instead of dying.  The watch thread is already
                # backing off+reconnecting; the Lease renewal loop keeps
                # retrying until the lease itself would have expired
                # (utils/leaderelect.py) — so a short outage costs
                # skipped cycles, never the daemon.
                METRICS.inc("control_plane_outage_cycles")
                LOG.warning("cycle %d skipped: apiserver unreachable "
                            "(%s); retrying", cycle, exc)
            cycle += 1
            if args.cycles and cycle >= args.cycles:
                break
            time.sleep(args.schedule_period)
    finally:
        try:
            # Overlapped pipeline: in-flight commit batches must land
            # before the daemon exits (a clean shutdown loses nothing),
            # then the executor thread joins.
            system.flush_pipeline()
            system.stop_pipeline()
        except Exception as exc:
            LOG.warning("pipeline flush on shutdown: %s", exc)
        if args.profile_dir:
            jax.profiler.stop_trace()
        if STACKPROF.running:
            STACKPROF.stop()  # dumps to KAI_STACKPROF_DIR when armed
        httpd.shutdown()


if __name__ == "__main__":
    run_app()
