"""Flight-recorder ring: structured cycle tracing under chaos.

The observability acceptance ladder (ISSUE 4): a traced cycle's root
span carries snapshot/plugin/action/kernel child kinds; a cycle run
under fault injection records the aborted span with error status and
the degraded/CPU-fallback attribute; binds and events correlate back to
the producing cycle's trace id; `/explain` answers why a PodGroup is
pending; and the recorder's memory is bounded (ring of N traces, span
cap per trace).  Also home to the metrics satellites: scrape-compatible
histogram buckets and edge-quantile correctness.
"""

import json
import math

import pytest

from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.scheduler import Scheduler
from kai_scheduler_tpu.utils.cluster_spec import build_cluster
from kai_scheduler_tpu.utils.deviceguard import (configure_device_guard,
                                                 reset_device_guard)
from kai_scheduler_tpu.utils.metrics import METRICS, Histogram, Metrics
from kai_scheduler_tpu.utils.tracing import TRACER, Tracer

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    """Pristine guard + tracer per test; no KAI_* leakage between tests."""
    for var in ("KAI_FAULT_INJECT", "KAI_DEVICE_DEADLINE_S",
                "KAI_DEVICE_RETRIES", "KAI_BREAKER_THRESHOLD",
                "KAI_BREAKER_COOLOFF_S", "KAI_FAULT_SEED",
                "KAI_TRACE_DIR"):
        monkeypatch.delenv(var, raising=False)
    reset_device_guard()
    TRACER.reset()
    yield
    reset_device_guard()
    TRACER.reset()


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


def kinds_of(trace):
    return {sp.kind for sp in trace.spans}


# -- the span tree ------------------------------------------------------------

class TestCycleTrace:
    def test_healthy_cycle_records_full_span_tree(self):
        ssn = Scheduler(lambda: small_cluster(),
                        SchedulerConfig()).run_once()
        trace = TRACER.get_trace()
        assert trace is not None and trace.aborted is None
        # The acceptance span kinds: root + snapshot + plugin + action +
        # kernel dispatch all present in one cycle.
        assert {"cycle", "snapshot", "plugin", "action",
                "kernel"} <= kinds_of(trace)
        root = trace.spans[-1]
        assert root.kind == "cycle" and root.status == "ok"
        # Kernel spans carry the guard verdict: device path, breaker
        # closed, no fallback.
        kernels = [sp for sp in trace.spans if sp.kind == "kernel"]
        assert kernels and all(sp.attrs["fallback"] is False
                               and sp.attrs["breaker"] == "closed"
                               for sp in kernels)
        # Nesting: every non-root span has a parent inside the trace.
        ids = {sp.span_id for sp in trace.spans}
        assert all(sp.parent_id in ids for sp in trace.spans
                   if sp is not root)
        # Bind-to-cycle correlation on the in-memory path.
        assert ssn.cluster.bind_requests
        assert all(br.trace_id == trace.trace_id
                   for br in ssn.cluster.bind_requests)

    def test_healthy_cycle_inside_except_block_is_not_aborted(self):
        """run_once called from an except handler (a retry-on-error
        wrapper): the OUTER handled exception must not leak into the
        trace finalize — only exceptions escaping run_once count."""
        sched = Scheduler(lambda: small_cluster(), SchedulerConfig())
        try:
            raise RuntimeError("outer, already handled")
        except RuntimeError:
            ssn = sched.run_once()
        assert ssn.aborted is None
        trace = TRACER.get_trace()
        assert trace.aborted is None
        assert trace.spans[-1].status == "ok"

    def test_chrome_export_is_perfetto_shaped(self):
        Scheduler(lambda: small_cluster(), SchedulerConfig()).run_once()
        out = json.loads(json.dumps(TRACER.get_trace().to_chrome()))
        events = out["traceEvents"]
        assert events and all(e["ph"] == "X" for e in events)
        for e in events:
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert e["cat"] and e["name"] and e["args"]["status"]
        assert out["otherData"]["trace_id"].startswith("t")

    def test_span_latency_histograms_land_in_metrics(self):
        Scheduler(lambda: small_cluster(), SchedulerConfig()).run_once()
        for family in ("cycle_span_cycle_latency_ms",
                       "cycle_span_kernel_latency_ms",
                       "cycle_span_action_latency_ms",
                       "cycle_span_snapshot_latency_ms"):
            assert METRICS.histograms[family].n >= 1, family


# -- chaos: degraded and aborted cycles ---------------------------------------

class TestTracingUnderFaults:
    def test_hang_cycle_marks_kernel_spans_fallback(self):
        """KAI_FAULT_INJECT=hang: the cycle completes degraded on the CPU
        fallback and every kernel span says so (fallback attribute, open
        breaker), with the trace flagged degraded."""
        configure_device_guard(deadline_s=0.3, retries=0,
                               breaker_threshold=1, fault="hang")
        ssn = Scheduler(lambda: small_cluster(),
                        SchedulerConfig(cycle_deadline_s=120.0)).run_once()
        assert ssn.aborted is None
        trace = TRACER.get_trace()
        assert trace.degraded is True and trace.aborted is None
        kernels = [sp for sp in trace.spans if sp.kind == "kernel"]
        assert kernels and all(sp.attrs["fallback"] for sp in kernels)
        assert any(sp.attrs["breaker"] == "open" for sp in kernels)
        assert trace.to_summary()["degraded"] is True

    def test_aborted_cycle_captures_error_span(self, monkeypatch):
        """A device death mid-action (error fault, fallback disabled):
        the flight recorder keeps the aborted cycle with the failing
        kernel + action spans marked error, the root span error'd with
        the abort reason, and >= 4 child span kinds present."""
        guard = configure_device_guard(deadline_s=5.0, retries=0,
                                       breaker_threshold=100,
                                       fallback_enabled=False)

        class DieMidAction:
            name = "chaos"

            def execute(self, ssn):
                guard.set_fault("error")
                ssn.dispatch_kernel(lambda: 1, label="chaos_kernel")

        monkeypatch.setattr("kai_scheduler_tpu.scheduler.build_actions",
                            lambda names: [DieMidAction()])
        ssn = Scheduler(lambda: small_cluster(),
                        SchedulerConfig()).run_once()
        assert ssn.aborted and "chaos" in ssn.aborted
        trace = TRACER.get_trace()
        assert trace.aborted and "chaos" in trace.aborted
        assert {"snapshot", "plugin", "action", "kernel"} \
            <= kinds_of(trace)
        failing = [sp for sp in trace.spans
                   if sp.kind == "kernel"
                   and sp.attrs.get("kernel") == "chaos_kernel"]
        assert failing and failing[0].status == "error"
        assert "injected device error" in failing[0].error
        action = [sp for sp in trace.spans if sp.kind == "action"]
        assert action and action[0].status == "error"
        root = trace.spans[-1]
        assert root.kind == "cycle" and root.status == "error"
        assert trace.to_summary()["aborted"]

    def test_trace_dir_dumps_aborted_cycle(self, monkeypatch, tmp_path):
        """KAI_TRACE_DIR (the chaos_matrix --trace-dir hook): an aborted
        cycle's Chrome trace JSON lands on disk for post-mortem."""
        monkeypatch.setenv("KAI_TRACE_DIR", str(tmp_path / "traces"))
        configure_device_guard(deadline_s=5.0, retries=0,
                               breaker_threshold=100, fault="error",
                               fallback_enabled=False)
        ssn = Scheduler(lambda: small_cluster(),
                        SchedulerConfig()).run_once()
        assert ssn.aborted
        dumps = list((tmp_path / "traces").glob("cycle_*.json"))
        assert len(dumps) == 1
        data = json.loads(dumps[0].read_text())
        assert data["otherData"]["aborted"]
        assert data["traceEvents"]


# -- explainability ledger ----------------------------------------------------

class TestExplain:
    def test_pending_podgroup_has_rejection_reasons(self):
        cluster = build_cluster({
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {"fits": {"queue": "q", "tasks": [{"gpu": 2}]},
                     "too-big": {"queue": "q", "tasks": [{"gpu": 16}]}},
        })
        Scheduler(lambda: cluster, SchedulerConfig()).run_once()
        record = TRACER.explain_for("too-big")
        assert record is not None
        assert record["reasons"] and any(
            "16 gpu" in r for r in record["reasons"])
        assert record["trace_id"] == TRACER.get_trace().trace_id
        assert TRACER.explain_for("fits") is None
        assert "too-big" in TRACER.get_trace().to_summary()[
            "rejected_podgroups"]

    def test_explain_survives_later_clean_cycles(self):
        cluster = build_cluster({
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {"too-big": {"queue": "q", "tasks": [{"gpu": 16}]}},
        })
        sched = Scheduler(lambda: cluster, SchedulerConfig())
        sched.run_once()
        first = TRACER.explain_for("too-big")
        sched.run_once()  # still pending: the record refreshes
        second = TRACER.explain_for("too-big")
        assert second["cycle"] > first["cycle"]

    def test_record_drops_once_the_group_schedules(self):
        """A group that was rejected and later binds must not keep
        serving its stale 'why pending' record — an operator would be
        pointed at a group that is actually running."""
        spec = {
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {"j": {"queue": "q", "tasks": [{"gpu": 16}]}},
        }
        sched = Scheduler(lambda: build_cluster(spec), SchedulerConfig())
        sched.run_once()
        assert TRACER.explain_for("j") is not None
        # The job shrinks (user edited it) and now fits.
        spec["jobs"]["j"] = {"queue": "q", "tasks": [{"gpu": 2}]}
        ssn = sched.run_once()
        assert ssn.cluster.bind_requests
        assert TRACER.explain_for("j") is None
        assert "j" not in TRACER.explained_podgroups()


# -- boundedness --------------------------------------------------------------

class TestFlightRecorderBounds:
    def test_ring_holds_last_n_traces(self):
        tracer = Tracer(capacity=3)
        for cycle in range(1, 8):
            tracer.begin_cycle(cycle)
            with tracer.span("s", kind="action"):
                pass
            tracer.end_cycle()
        cycles = tracer.cycles()
        assert [c["cycle"] for c in cycles] == [7, 6, 5]
        assert tracer.get_trace("1") is None
        assert tracer.get_trace(str(7)).cycle == 7

    def test_span_cap_counts_overflow_and_keeps_root(self):
        tracer = Tracer(capacity=2, max_spans_per_trace=16)
        tracer.begin_cycle(1)
        for i in range(40):
            with tracer.span(f"s{i}", kind="kernel"):
                pass
        trace = tracer.end_cycle()
        assert len(trace.spans) <= 16
        assert trace.dropped_spans == 40 - (16 - 1)
        assert trace.spans[-1].kind == "cycle"  # the root always survives

    def test_explain_ledger_is_bounded_with_counted_drops(self):
        """A sustained over-capacity cluster (thousands of pending
        groups) must not grow the per-trace ledger without bound."""
        from kai_scheduler_tpu.utils.tracing import CycleTrace
        tracer = Tracer(capacity=2)
        tracer.begin_cycle(1)
        for g in range(CycleTrace.MAX_EXPLAIN_GROUPS + 50):
            tracer.note_rejection(f"pg{g}", "no fit")
        for r in range(CycleTrace.MAX_REASONS_PER_GROUP + 5):
            tracer.note_rejection("pg0", f"reason {r}")
        trace = tracer.end_cycle()
        assert len(trace.explain) == CycleTrace.MAX_EXPLAIN_GROUPS
        assert len(trace.explain["pg0"]) == \
            CycleTrace.MAX_REASONS_PER_GROUP
        # 50 groups over the cap + (13 new reasons for pg0 of which only
        # 7 fit next to its existing "no fit").
        assert trace.dropped_rejections == 50 + (
            (CycleTrace.MAX_REASONS_PER_GROUP + 5)
            - (CycleTrace.MAX_REASONS_PER_GROUP - 1))
        assert trace.to_summary()["dropped_rejections"] > 0

    def test_null_span_outside_cycle_is_safe(self):
        tracer = Tracer(capacity=2)
        with tracer.span("orphan", kind="kernel") as sp:
            sp.set(anything=1)
        assert tracer.cycles() == []
        assert tracer.current_trace_id() is None


# -- the trace across the guard's worker thread (PR 25) -----------------------

def cycle_session():
    """A live cycle on this thread and a session to dispatch through."""
    from kai_scheduler_tpu.framework.session import Session
    TRACER.begin_cycle(1)
    return Session(small_cluster())


def by_name(trace):
    out = {}
    for sp in trace.spans:
        out.setdefault(sp.name, []).append(sp)
    return out


class TestHandOff:
    @pytest.mark.parametrize("path", ("device", "fallback", "inline"))
    def test_span_in_guarded_thunk_lands_under_its_dispatch(self, path):
        """The guard runs the thunk on its worker thread (device attempt
        and CPU fallback alike) or, without a deadline, inline: a span
        opened inside is a child of the open ``dispatch:<label>`` span
        either way."""
        import threading
        configure_device_guard(
            deadline_s=0.0 if path == "inline" else 5.0, retries=0,
            breaker_threshold=100,
            fault="error" if path == "fallback" else "")
        ssn = cycle_session()
        ran_on = []

        def thunk():
            with TRACER.span("seam:probe", kind="seam", n=1):
                ran_on.append(threading.get_ident())
            return 1

        assert ssn.dispatch_kernel(thunk, label="probe") == 1
        trace = TRACER.end_cycle()
        spans = by_name(trace)
        (dispatch,), (probe,) = spans["dispatch:probe"], spans["seam:probe"]
        assert probe.parent_id == dispatch.span_id
        assert probe.attrs == {"n": 1} and probe.duration_s > 0
        assert dispatch.attrs["fallback"] is (path == "fallback")
        assert (ran_on[0] == threading.get_ident()) is (path == "inline")
        assert dispatch.start_s <= probe.start_s
        assert probe.start_s + probe.duration_s \
            <= dispatch.start_s + dispatch.duration_s + 1e-6
        assert trace.dropped_spans == 0

    @pytest.mark.parametrize("fallback", (True, False))
    def test_abandoned_worker_writes_into_no_trace(self, fallback):
        """A thunk that outlives the guard's deadline: what it opens or
        closes afterwards lands neither in its own cycle's trace (ended
        by then, or taken over by the CPU fallback) nor in the next
        one's, and is counted."""
        import threading

        from kai_scheduler_tpu.utils.deviceguard import DeviceGuardError
        configure_device_guard(deadline_s=0.1, retries=0,
                               breaker_threshold=100,
                               fallback_enabled=fallback)
        ssn = cycle_session()
        release, finished = threading.Event(), threading.Event()
        calls = []

        def revoked():
            return METRICS.counters.get("trace_spans_revoked_total", 0)
        revoked0 = revoked()

        def thunk():
            calls.append(1)
            if len(calls) > 1:  # the CPU fallback's clean re-run
                with TRACER.span("seam:fallback", kind="seam"):
                    return 1
            try:
                with TRACER.span("seam:early", kind="seam"):
                    assert release.wait(30.0)
                with TRACER.span("seam:late", kind="seam"):
                    pass
                TRACER.stamp("dispatch:slow", late=True)
            finally:
                finished.set()
            return 1

        if fallback:
            assert ssn.dispatch_kernel(thunk, label="slow") == 1
        else:
            with pytest.raises(DeviceGuardError):
                ssn.dispatch_kernel(thunk, label="slow")
        first = TRACER.end_cycle()
        TRACER.begin_cycle(2)
        release.set()
        assert finished.wait(30.0)
        second = TRACER.end_cycle()
        for trace in (first, second):
            names = set(by_name(trace))
            assert not {"seam:early", "seam:late"} & names, names
        assert ("seam:fallback" in by_name(first)) is fallback
        assert "late" not in by_name(first)["dispatch:slow"][0].attrs
        # seam:early's close and seam:late's open were both dropped, and
        # counted on the tracer: the first trace had ended by then.
        assert revoked() - revoked0 == 2
        assert first.dropped_spans == 0 and second.dropped_spans == 0
        assert first.name_totals.get("seam:early") is None

    def test_hand_off_outside_a_cycle_carries_nothing(self):
        def thunk():
            return 7
        with TRACER.hand_off() as seam:
            assert seam.adopting(thunk) is thunk


def topology_gang_cluster():
    """8 nodes in 2 zones x 2 racks and one gang that prefers a rack: a
    master beside two workers makes the chunk non-homogeneous, so it
    takes the exact kernel with a live mask and live boosts."""
    nodes = {f"n{i}": {"gpu": 8, "labels": {"zone": f"z{i // 4}",
                                            "rack": f"r{i // 2}"}}
             for i in range(8)}
    return build_cluster({
        "nodes": nodes, "queues": {"q": {}},
        "topologies": {"topo": {"levels": ["zone", "rack"]}},
        "jobs": {"gang": {
            "queue": "q", "min_available": 3, "topology": "topo",
            "preferred_topology_level": "rack",
            "tasks": [{"cpu": "2", "mem": "1Gi", "gpu": 1},
                      {"cpu": "1", "mem": "1Gi", "gpu": 1},
                      {"cpu": "1", "mem": "1Gi", "gpu": 1}]}}})


# Span of the issue's table -> its parent.
TABLE = {
    "allocate:order": "action:allocate",
    "allocate:job": "action:allocate",
    "topology:subset_nodes": "allocate:job",
    "propose:operands": "allocate:job",
    "extra_scores:topology": "propose:operands",
    "dispatch:allocate_jobs": "allocate:job",
    "seam:stage": "dispatch:allocate_jobs",
    "seam:launch": "dispatch:allocate_jobs",
    "dispatch:allocate_jobs_fetch": "allocate:job",
    "seam:wait": "dispatch:allocate_jobs_fetch",
    "seam:download": "dispatch:allocate_jobs_fetch",
    "propose:unpack": "allocate:job",
    "statement:apply": "allocate:job",
    "statement:commit": "allocate:job",
}
OLD_NAMES = ("dispatch:allocate_jobs", "dispatch:allocate_jobs_fetch")
COUNTERS = ("device_upload_bytes", "host_convert_bytes",
            "device_download_bytes")


def counters_now():
    return {c: METRICS.counters.get(c, 0.0) for c in COUNTERS}


class TestAllocateSpans:
    @pytest.fixture
    def gang_cycle(self):
        """(trace, counter movement, session) of one topology-gang
        cycle."""
        before = counters_now()
        ssn = Scheduler(topology_gang_cluster, SchedulerConfig()).run_once()
        moved = {c: v - before[c] for c, v in counters_now().items()}
        return TRACER.get_trace(), moved, ssn

    def test_each_span_of_the_table_once_under_its_parent(self, gang_cycle):
        trace, _moved, ssn = gang_cycle
        assert len(ssn.cluster.bind_requests) == 3
        spans = by_name(trace)
        ids = {sp.span_id: sp for sp in trace.spans}
        for name, parent in TABLE.items():
            assert len(spans.get(name, ())) == 1, (name, sorted(spans))
            assert ids[spans[name][0].parent_id].name == parent, name
        job = spans["allocate:job"][0].attrs
        assert job == {"job": "gang", "queue": "q", "tasks": 3,
                       "success": True}
        operands = spans["propose:operands"][0].attrs
        assert operands == {"t": 3, "t_pad": 4, "nodes": 8,
                            "path": "exact", "extras": "row",
                            "mask": "row", "affinity": "none",
                            "strategy": "binpack"}
        assert spans["topology:subset_nodes"][0].attrs["nodes_in_first"] == 2
        # One [N] row for the gang, not a row a task.
        assert spans["extra_scores:topology"][0].attrs["bytes"] == 8 * 8
        assert spans["seam:launch"][0].attrs == {"kernel": "allocate_jobs"}
        assert spans["statement:apply"][0].attrs == {"ops": 3}
        assert spans["statement:commit"][0].attrs == {"binds": 3}
        # Every registered score fn has a span named after its plugin,
        # all of one kind.
        scores = [sp for sp in trace.spans
                  if sp.name.startswith("extra_scores:")]
        assert len(scores) == len(ssn.extra_score_fns) >= 2
        assert {sp.name.partition(":")[2] for sp in scores} \
            <= {p.name for p in ssn.plugins}
        assert all(sp.kind == "topology" for sp in scores)

    def test_no_new_span_reads_as_a_dispatch(self, gang_cycle):
        """``allocate_host_ms`` is ``action:allocate`` minus every
        ``dispatch:*`` under it: nothing this PR names may match."""
        from fnmatch import fnmatchcase
        trace, _moved, _ssn = gang_cycle
        new = {sp.name for sp in trace.spans
               if sp.kind in ("allocate", "topology", "propose", "seam")
               or sp.name.startswith("statement:")}
        assert set(TABLE) - set(OLD_NAMES) <= new
        assert not [n for n in new if fnmatchcase(n, "dispatch:*")]

    def test_the_new_kinds_feed_no_per_kind_histogram(self, gang_cycle):
        """A quantile over ``seam`` would mix a staging with a launch:
        these kinds are read by name (``span_names``), not by kind."""
        trace, _moved, _ssn = gang_cycle
        kinds = {sp.kind for sp in trace.spans}
        assert {"allocate", "topology", "propose", "seam"} <= kinds
        for kind in ("allocate", "topology", "propose", "seam"):
            assert f"cycle_span_{kind}_latency_ms" not in METRICS.histograms
        assert METRICS.histograms["cycle_span_commit_latency_ms"].n >= 2

    def test_counters_equal_the_sizes_the_shapes_give(self, gang_cycle):
        """Tier-1 runs in x64, where an f64 operand is uploaded as it is:
        nothing is converted, and a score costs 8 bytes.  The gang's
        boosts and its subset are one [N] row a job (the gang and the
        padding job), never a row a task."""
        trace, moved, ssn = gang_cycle
        t_pad, n = 4, 8
        snap = ssn.snapshot
        rows = (t_pad * snap.task_req.shape[1] * 8            # f64
                + t_pad * 4                                   # task_job
                + t_pad * snap.task_selector.shape[1] * 4
                + t_pad * snap.task_tolerations.shape[1] * 4
                + 2)                                          # job_allowed
        job_rows = 2 * n * (8 + 1)       # boosts f64 + the subset mask
        assert job_rows < 3 * n * (8 + 1)
        stage = by_name(trace)["seam:stage"][0].attrs
        assert moved == {"device_upload_bytes": rows + job_rows,
                         "host_convert_bytes": 0,
                         "device_download_bytes": (2 * t_pad + 2) * 4}
        assert stage == {"bytes_host": rows + job_rows,
                         "bytes_device": rows + job_rows,
                         "bytes_converted": 0, "operands": 7}
        assert by_name(trace)["seam:download"][0].attrs["bytes"] \
            == moved["device_download_bytes"]

    def test_stage_counts_what_the_32_bit_regime_converts(self):
        """The regime the chip runs: f64 narrows to f32 on the host."""
        import jax
        import numpy as np

        from kai_scheduler_tpu.framework.propose import _stage
        before = counters_now()
        with jax.enable_x64(False):
            req, mask, pair, nothing = _stage(
                np.zeros((4, 8)), np.ones((4, 8), bool),
                (np.zeros(4, np.int32), np.zeros((4, 8))), None)
        assert (req.dtype, mask.dtype) == (np.float32, np.bool_)
        assert nothing is None and pair[1].dtype == np.float32
        moved = {c: v - before[c] for c, v in counters_now().items()}
        assert moved["host_convert_bytes"] == 2 * 4 * 8 * 8
        assert moved["device_upload_bytes"] == 2 * 4 * 8 * 4 + 32 + 16

    def test_a_bulk_wave_is_one_statement_span_not_two_a_job(self):
        """The bulk path binds a wave of jobs under one ``statement:bulk``:
        spans must not scale with the jobs of a fill."""
        cluster = small_cluster()
        Scheduler(lambda: cluster,
                  SchedulerConfig(bulk_allocation_threshold=2)).run_once()
        assert len(cluster.bind_requests) == 8
        spans = by_name(TRACER.get_trace())
        (bulk,) = spans["statement:bulk"]
        assert bulk.kind == "commit" and bulk.attrs == {"jobs": 4, "ops": 8}
        assert not {"statement:apply", "statement:commit",
                    "allocate:job"} & set(spans)

    def test_grouped_rung_is_stamped_where_it_is_resolved(self):
        """``allocate_grouped`` runs on the guard's worker and stamps the
        rung on the call site's ``allocate_fused`` span, beside the
        guard's verdict."""
        Scheduler(lambda: small_cluster(), SchedulerConfig()).run_once()
        fused = by_name(TRACER.get_trace())["allocate_fused"]
        assert fused and all(
            {"mode", "groups", "nodes", "releasing_empty", "fallback",
             "timed_out", "breaker"} <= set(sp.attrs) for sp in fused)
        assert {sp.attrs["mode"] for sp in fused} == {"jnp"}


class TestNameTotals:
    def test_totals_by_name_survive_the_span_cap(self):
        tracer = Tracer(capacity=2, max_spans_per_trace=8)
        tracer.begin_cycle(1)
        for _ in range(50):
            with tracer.span("allocate:job", kind="allocate"):
                with tracer.span("seam:stage", kind="seam"):
                    pass
        trace = tracer.end_cycle()
        assert trace.dropped_spans == 100 - 7
        names = trace.to_summary()["span_names"]
        assert names["allocate:job"]["count"] == 50
        assert names["seam:stage"]["count"] == 50
        assert names["cycle"]["count"] == 1
        assert 0 < names["seam:stage"]["total_ms"] \
            <= names["allocate:job"]["total_ms"] \
            <= names["cycle"]["total_ms"]
        # By kind is what the kept spans say, as before.
        kinds = trace.to_summary()["spans"]
        assert sum(k["count"] for k in kinds.values()) == 8

    def test_distinct_names_are_bounded(self):
        from kai_scheduler_tpu.utils.tracing import CycleTrace
        tracer = Tracer(capacity=2, max_spans_per_trace=8)
        tracer.begin_cycle(1)
        extra = 40
        for i in range(CycleTrace.MAX_SPAN_NAMES + extra):
            with tracer.span(f"bind:pod-{i}", kind="kubeapi"):
                pass
        trace = tracer.end_cycle()
        totals = trace.name_totals
        assert len(totals) == CycleTrace.MAX_SPAN_NAMES + 1
        # The root closes last: it and the overflow share the last row.
        assert totals[CycleTrace.OTHER_NAMES][0] == extra + 1


def recorder_counters():
    return (METRICS.counters["trace_spans_total"],
            METRICS.counters["trace_spans_dropped_total"])


class TestWhatTheRecorderKept:
    """``trace_spans_total`` / ``trace_spans_dropped_total``: once a cycle
    ``end_cycle`` folds into ``METRICS`` how many spans closed and how
    many of them the trace had no room for, so that a reader of
    ``CycleTrace.spans`` (the benchmark's ``span_sum``) can tell a trace
    that is whole from one whose sums are short."""

    @pytest.mark.parametrize("spans,dropped", [(5, 0), (7, 0), (8, 1),
                                               (40, 33)],
                             ids=["under", "at-its-room", "one-over",
                                  "far-over"])
    def test_the_counters_are_the_traces_own_numbers(self, spans, dropped):
        tracer = Tracer(capacity=2, max_spans_per_trace=8)
        tracer.begin_cycle(1)
        recorded0, dropped0 = recorder_counters()
        for i in range(spans):
            with tracer.span(f"s{i % 3}", kind="kernel"):
                pass
        trace = tracer.end_cycle()
        assert trace.dropped_spans == dropped
        assert len(trace.spans) == spans + 1 - dropped
        recorded = sum(n for n, _s in trace.name_totals.values())
        assert recorded == spans + 1      # and the root
        assert recorder_counters() == (recorded0 + recorded,
                                       dropped0 + dropped)

    def test_both_are_there_at_zero_from_the_first_begin_cycle(self):
        METRICS.reset()
        tracer = Tracer(capacity=2)
        assert "trace_spans_total" not in METRICS.counters
        tracer.begin_cycle(1)
        assert recorder_counters() == (0, 0)
        tracer.end_cycle()
        assert recorder_counters() == (1, 0)

    def test_they_move_once_a_cycle_not_once_a_span(self, monkeypatch):
        calls = []
        real = METRICS.inc

        def inc(name, value=1.0, **labels):
            if name.startswith("trace_spans_") \
                    and name != "trace_spans_revoked_total":
                calls.append((name, value))
            return real(name, value, **labels)
        monkeypatch.setattr(METRICS, "inc", inc)
        tracer = Tracer(capacity=2, max_spans_per_trace=8)
        tracer.begin_cycle(1)
        for _ in range(20):
            with tracer.span("s", kind="kernel"):
                pass
        tracer.end_cycle()
        assert calls == [("trace_spans_total", 0),
                         ("trace_spans_dropped_total", 0),
                         ("trace_spans_total", 21),
                         ("trace_spans_dropped_total", 13)]

    def test_a_span_attached_after_the_cycle_is_in_neither(self):
        tracer = Tracer(capacity=2, max_spans_per_trace=8)
        trace_id = tracer.begin_cycle(1)
        tracer.end_cycle()
        before = recorder_counters()
        assert tracer.attach_async_span(trace_id, "commit:wave", "commit",
                                        0.001)
        assert recorder_counters() == before

    def test_a_scheduler_cycle_counts_every_span_it_recorded(self):
        sched = Scheduler(small_cluster, SchedulerConfig())
        METRICS.reset()
        sched.run_once()
        trace = TRACER.get_trace()
        assert trace.dropped_spans == 0
        assert recorder_counters() == (len(trace.spans), 0)


class TestProfilerClock:
    @staticmethod
    def profiled_cycle(sched, out_dir):
        """(spans by name, {annotation: (line, start_ns, duration_ns)}) of
        one cycle run under a profiler session."""
        import glob

        import jax
        from jax.profiler import ProfileData
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(out_dir), profiler_options=options)
        try:
            sched.run_once()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(out_dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        found = {}
        lines = [line for plane in ProfileData.from_file(path).planes
                 for line in plane.lines]
        for i, line in enumerate(lines):
            for ev in line.events:
                if ev.name.startswith("kai:"):
                    assert ev.name not in found, ev.name
                    found[ev.name] = (i, ev.start_ns, ev.duration_ns)
        return by_name(TRACER.get_trace()), found

    def test_spans_are_annotations_on_the_profilers_clock(self, tmp_path):
        """Under a profiler session the trace holds ``kai:<span name>``
        for the cycle thread's spans and, on another line, for the
        worker's; offsets between them agree with the flight
        recorder's."""
        sched = Scheduler(topology_gang_cluster, SchedulerConfig())
        sched.run_once()  # compile outside the profiled cycle
        # The two clocks are read a few bytecodes apart, and under a
        # loaded test run the interpreter can switch threads in between
        # (5 ms): a cycle with such a stall is measured again, a clock
        # that is off would be off every time.
        for attempt in range(3):
            spans, found = self.profiled_cycle(sched, tmp_path / str(attempt))
            assert {f"kai:{name}" for name in TABLE} <= set(found)
            cycle_line = found["kai:cycle"][0]
            worker_line = found["kai:seam:stage"][0]
            assert worker_line != cycle_line
            assert found["kai:seam:launch"][0] == worker_line
            for name in ("action:allocate", "allocate:job",
                         "propose:operands", "statement:commit"):
                assert found[f"kai:{name}"][0] == cycle_line, name
            # One clock: the offset of every span from action:allocate,
            # and its length, as the profiler has them and as the
            # recorder does.
            base_ns = found["kai:action:allocate"][1]
            base_s = spans["action:allocate"][0].start_s
            off = {}
            for name in TABLE:
                (sp,) = spans[name]
                _line, start_ns, dur_ns = found[f"kai:{name}"]
                off[name] = max(
                    abs((start_ns - base_ns) / 1e9 - (sp.start_s - base_s)),
                    abs(dur_ns / 1e9 - sp.duration_s))
            if max(off.values()) < 1e-3:
                return
        pytest.fail(f"offsets beyond a millisecond in 3 cycles: {off}")

    def test_without_jax_loaded_spans_record_as_before(self, monkeypatch):
        import sys
        monkeypatch.delitem(sys.modules, "jax")
        tracer = Tracer(capacity=2)
        tracer.begin_cycle(1)
        with tracer.span("s", kind="action") as sp:
            assert sp.annotation is None
        trace = tracer.end_cycle()
        assert [s.name for s in trace.spans] == ["s", "cycle"]


# -- the cyclic collector (PR 40) ---------------------------------------------

def gc_counters():
    """(collections, pause seconds) folded into METRICS so far, by
    generation."""
    return [(METRICS.counters.get(
                f'gc_collections_total{{generation="{g}"}}', 0.0),
             METRICS.counters.get(
                f'gc_pause_seconds_total{{generation="{g}"}}', 0.0))
            for g in range(3)]


def gc_moved(before):
    return [(n1 - n0, s1 - s0)
            for (n0, s0), (n1, s1) in zip(before, gc_counters())]


def within(seconds, body):
    """``body`` on a thread of its own, given ``seconds``: a deadlock
    fails the test and does not hang the run."""
    import threading
    out = []
    thread = threading.Thread(target=lambda: out.append(body()),
                              daemon=True)
    thread.start()
    thread.join(seconds)
    assert not thread.is_alive(), f"no return within {seconds} s"
    assert out, "the body raised"
    return out[0]


class TestCollector:
    @pytest.fixture(autouse=True)
    def only_the_collections_a_test_asks_for(self):
        import gc
        gc.disable()
        # One cycle installs the callback and folds what earlier tests
        # left, so that a test's counters move by its own collections.
        TRACER.begin_cycle(0)
        TRACER.end_cycle()
        yield
        gc.enable()

    def test_full_collection_is_a_span_under_the_open_span(self):
        import gc
        before = gc_counters()
        TRACER.begin_cycle(1)
        with TRACER.span("propose:operands", kind="propose"):
            gc.collect()
        trace = TRACER.end_cycle()
        spans = by_name(trace)
        (full,), (operands,) = spans["gc:full"], spans["propose:operands"]
        assert full.kind == "gc" and full.parent_id == operands.span_id
        assert full.attrs["generation"] == 2
        assert isinstance(full.attrs["collected"], int)
        assert operands.start_s <= full.start_s and full.duration_s > 0
        assert full.start_s + full.duration_s \
            <= operands.start_s + operands.duration_s
        young, middle, old = gc_moved(before)
        assert old[0] == 1 and old[1] == pytest.approx(full.duration_s,
                                                       abs=1e-9)
        assert young[0] == 0 and middle[0] == 0
        # Kind gc keeps its histogram: the operator's pause quantile.
        assert METRICS.histograms["cycle_span_gc_latency_ms"].n >= 1

    def test_what_the_walk_collected_is_on_the_span(self):
        import gc

        class Node:
            pass
        TRACER.begin_cycle(1)
        for _ in range(10):
            a, b = Node(), Node()
            a.other, b.other = b, a
        del a, b
        gc.collect()
        trace = TRACER.end_cycle()
        (full,) = by_name(trace)["gc:full"]
        assert full.attrs["collected"] >= 20
        assert full.parent_id == trace.root.span_id

    @pytest.mark.parametrize("generation", (0, 1))
    def test_young_collection_opens_nothing_and_is_counted(self,
                                                           generation):
        import gc
        before = gc_counters()
        TRACER.begin_cycle(1)
        with TRACER.span("statement:apply", kind="allocate"):
            gc.collect(generation)
        trace = TRACER.end_cycle()
        assert [sp.name for sp in trace.spans] == ["statement:apply",
                                                   "cycle"]
        moved = gc_moved(before)
        assert [n for n, _s in moved] == [1.0 if g == generation else 0.0
                                          for g in range(3)]
        assert moved[generation][1] > 0

    def test_collection_on_a_thread_with_no_cycle_is_counted(self):
        import gc
        before = gc_counters()
        TRACER.begin_cycle(1)
        with TRACER.span("statement:apply", kind="allocate"):
            assert within(30.0, gc.collect) >= 0  # a status worker
        trace = TRACER.end_cycle()
        assert "gc:full" not in by_name(trace)
        old = gc_moved(before)[2]
        assert old[0] == 1 and old[1] > 0

    def test_collection_between_cycles_is_in_the_cycle_that_follows(self):
        import gc
        before = gc_counters()
        gc.collect()  # the client, between two cycles
        assert gc_moved(before)[2] == (0, 0)
        TRACER.begin_cycle(1)
        trace = TRACER.end_cycle()
        assert "gc:full" not in by_name(trace)
        old = gc_moved(before)[2]
        assert old[0] == 1 and old[1] > 0
        TRACER.begin_cycle(2)
        TRACER.end_cycle()
        assert gc_moved(before)[2] == old  # folded once

    def test_callback_is_installed_once_a_process(self):
        import gc

        def installed():
            return [cb for cb in gc.callbacks
                    if getattr(cb, "__func__", None) is Tracer._on_gc]
        for cycle in range(5):
            TRACER.begin_cycle(cycle)
            TRACER.end_cycle()
        TRACER.reset()
        other = Tracer(capacity=2)  # a private recorder adds none
        other.begin_cycle(1)
        other.end_cycle()
        TRACER.begin_cycle(9)
        gc.collect()
        trace = TRACER.end_cycle()
        assert len(installed()) == 1 and installed()[0].__self__ is TRACER
        assert len(by_name(trace)["gc:full"]) == 1

    def test_full_collection_is_an_annotation_on_the_profilers_clock(
            self, tmp_path):
        import gc
        import glob

        import jax
        from jax.profiler import ProfileData
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            TRACER.begin_cycle(1)
            with TRACER.span("propose:operands", kind="propose"):
                gc.collect()
            trace = TRACER.end_cycle()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        found = {ev.name: ev.duration_ns
                 for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith("kai:")}
        (full,) = by_name(trace)["gc:full"]
        assert found["kai:gc:full"] / 1e9 == pytest.approx(
            full.duration_s, abs=1e-3)
        assert found["kai:gc:full"] <= found["kai:propose:operands"]

    # -- the callback takes no lock: each case under its own time limit ----
    def test_collection_under_the_metrics_lock_returns(self):
        """A due collection starts between two bytecodes, also inside
        ``METRICS.inc``'s ``with self._data_lock`` on the same thread."""
        import gc
        before = gc_counters()

        def body():
            TRACER.begin_cycle(1)
            with TRACER.span("statement:commit", kind="allocate"):
                with METRICS._data_lock:
                    gc.collect()
                    gc.collect(0)
            return TRACER.end_cycle()
        trace = within(30.0, body)
        assert len(by_name(trace)["gc:full"]) == 1
        moved = gc_moved(before)
        assert moved[2][0] == 1 and moved[0][0] == 1

    @pytest.mark.parametrize("recorded_by", ("next_close", "hand_off_exit"))
    def test_collection_under_the_ring_lock_on_an_adopter_returns(
            self, recorded_by):
        """An adopting worker closes its spans under the ring lock; a
        collection that lands there queues its span on the hand-off, and
        the worker's next close, or the cycle thread leaving the hand-off,
        records it."""
        import gc
        TRACER.begin_cycle(1)

        def thunk():
            if recorded_by == "next_close":
                with TRACER.span("seam:stage", kind="seam"):
                    with TRACER._lock:
                        gc.collect()
            else:
                with TRACER._lock:
                    gc.collect()
            return 7
        with TRACER.span("dispatch:probe", kind="kernel"), \
                TRACER.hand_off() as seam:
            assert within(30.0, seam.adopting(thunk)) == 7
            if recorded_by == "hand_off_exit":
                assert seam.pending and "gc:full" not in \
                    TRACER._state()["trace"].name_totals
        trace = TRACER.end_cycle()
        spans = by_name(trace)
        (full,) = spans["gc:full"]
        parent = "seam:stage" if recorded_by == "next_close" \
            else "dispatch:probe"
        assert full.parent_id == spans[parent][0].span_id
        assert full.attrs["generation"] == 2 and full.duration_s > 0
        assert not seam.pending and trace.dropped_spans == 0

    def test_collection_on_a_live_guard_worker_lands_under_its_seam(self):
        import gc
        configure_device_guard(deadline_s=5.0, retries=0,
                               breaker_threshold=100)
        ssn = cycle_session()

        def thunk():
            with TRACER.span("seam:stage", kind="seam"):
                gc.collect()
            return 1
        assert ssn.dispatch_kernel(thunk, label="probe") == 1
        spans = by_name(TRACER.end_cycle())
        (full,), (stage,) = spans["gc:full"], spans["seam:stage"]
        assert full.parent_id == stage.span_id
        assert stage.parent_id == spans["dispatch:probe"][0].span_id

    def test_collection_on_an_abandoned_worker_records_nothing(
            self, monkeypatch):
        """A worker the guard abandoned at its deadline: its collection
        is counted, opens no span in its own cycle's trace nor in the
        next one's, and raises nothing (CPython hands what a callback
        raises to ``sys.unraisablehook``)."""
        import gc
        import sys
        import threading

        from kai_scheduler_tpu.utils.deviceguard import DeviceGuardError
        configure_device_guard(deadline_s=0.1, retries=0,
                               breaker_threshold=100,
                               fallback_enabled=False)
        ssn = cycle_session()
        before = gc_counters()
        release, finished = threading.Event(), threading.Event()
        raised = []
        monkeypatch.setattr(sys, "unraisablehook", raised.append)

        def thunk():
            try:
                assert release.wait(30.0)
                gc.collect()
            finally:
                finished.set()
            return 1
        with pytest.raises(DeviceGuardError):
            ssn.dispatch_kernel(thunk, label="slow")
        first = TRACER.end_cycle()
        TRACER.begin_cycle(2)
        release.set()
        assert finished.wait(30.0)
        second = TRACER.end_cycle()
        assert "gc:full" not in by_name(first)
        assert "gc:full" not in by_name(second)
        assert gc_moved(before)[2][0] == 1
        assert raised == []


class TestProfileEndpoints:
    """``--enable-profiler`` arms the one sampling profiler there is
    (tests/test_server.py starts the daemon with it); the three paths it
    was ever served under are one page."""

    @pytest.fixture
    def serve(self):
        import threading
        import urllib.request
        from http.server import ThreadingHTTPServer

        from kai_scheduler_tpu.server import _make_handler
        from kai_scheduler_tpu.utils.stackprof import STACKPROF
        STACKPROF.stop(dump=False)
        STACKPROF.reset()
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler({}))
        threading.Thread(target=httpd.serve_forever, daemon=True).start()

        def get(path):
            return urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.server_port}{path}",
                timeout=5).read()
        yield get
        httpd.shutdown()
        STACKPROF.stop(dump=False)
        STACKPROF.reset()

    @pytest.mark.parametrize("path", ("/debug/flame", "/debug/pprof",
                                      "/debug/profile"))
    def test_every_profile_path_serves_stackprof(self, serve, path):
        import time
        import urllib.error

        from kai_scheduler_tpu.utils.stackprof import STACKPROF
        with pytest.raises(urllib.error.HTTPError) as err:
            serve(path)
        assert err.value.code == 404
        STACKPROF.start()
        deadline = time.monotonic() + 10
        while not STACKPROF.total_samples and time.monotonic() < deadline:
            time.sleep(0.02)
        folded = serve(path).decode()
        assert "socketserver.py:serve_forever" in folded
        summary = json.loads(serve(path + "?summary=1"))
        assert summary["total_samples"] > 0 and summary["running"]


# -- fleet correlation (BindRequest spec + events over the API) ---------------

class TestFleetCorrelation:
    def test_bindrequest_and_event_carry_trace_id(self):
        from kai_scheduler_tpu.controllers import System, SystemConfig
        from kai_scheduler_tpu.controllers.kubeapi import make_pod

        system = System(SystemConfig())
        system.api.create({"kind": "Node", "metadata": {"name": "n1"},
                           "status": {"allocatable": {
                               "cpu": "32", "memory": "256Gi",
                               "nvidia.com/gpu": 8}}})
        system.api.create({"kind": "Queue", "metadata": {"name": "q"},
                           "spec": {}})
        system.api.create(make_pod("p1", queue="q", gpu=1))
        system.api.create(make_pod("p-huge", queue="q", gpu=64))
        # BindRequests are consumed (and GC'd) within the same run_cycle,
        # so capture them at creation time like the binder does.
        seen_brs = []
        system.api.watch("BindRequest",
                         lambda ev, obj: seen_brs.append(obj)
                         if ev == "ADDED" else None)
        system.run_cycle()
        assert seen_brs
        trace = TRACER.get_trace()
        assert all(br["spec"]["traceId"] == trace.trace_id
                   for br in seen_brs)
        # kubeapi spans recorded the fenced write path (epoch None when
        # un-fenced, but the span itself must exist).
        assert any(sp.kind == "kubeapi"
                   and sp.attrs.get("op") in ("bindrequest_create",
                                              "bindrequest_create_bulk")
                   for sp in trace.spans)
        # The unschedulable gang's event correlates to a cycle trace.
        events = [e for e in system.api.list("Event")
                  if e["spec"].get("reason") == "Unschedulable"]
        assert events and all(e["spec"].get("traceId") for e in events)
        # And its PodGroup condition names the cycle too.
        conds = [c for pg in system.api.list("PodGroup")
                 for c in pg.get("status", {}).get("conditions", [])
                 if c["type"] == "Unschedulable"]
        assert conds and all(c["traceId"] for c in conds)


# -- metrics satellites -------------------------------------------------------

class TestPrometheusHistograms:
    def test_bucket_lines_are_cumulative_and_end_at_inf(self):
        m = Metrics()
        m.observe("cycle_ms", 3.0)      # le=5
        m.observe("cycle_ms", 3.0)      # le=5
        m.observe("cycle_ms", 40.0)     # le=50
        m.observe("cycle_ms", 99999.0)  # le=+Inf
        text = m.to_prometheus_text()
        assert '# TYPE cycle_ms histogram' in text
        assert 'cycle_ms_bucket{le="5"} 2' in text
        assert 'cycle_ms_bucket{le="50"} 3' in text
        assert 'cycle_ms_bucket{le="2000"} 3' in text
        assert 'cycle_ms_bucket{le="+Inf"} 4' in text
        assert "cycle_ms_sum" in text and "cycle_ms_count 4" in text
        # Cumulative monotonicity across every bucket line.
        counts = [float(line.rsplit(" ", 1)[1])
                  for line in text.splitlines()
                  if line.startswith("cycle_ms_bucket")]
        assert counts == sorted(counts)

    def test_custom_buckets_without_inf_still_emit_inf(self):
        m = Metrics()
        m.histograms["lat"] = Histogram(buckets=[1, 10])
        m.observe("lat", 0.5)
        m.observe("lat", 5000.0)  # beyond the last edge
        text = m.to_prometheus_text()
        assert 'lat_bucket{le="10"} 1' in text
        assert 'lat_bucket{le="+Inf"} 2' in text


class TestHistogramQuantile:
    def test_empty_histogram_is_zero(self):
        h = Histogram()
        assert h.quantile(0.0) == 0.0
        assert h.quantile(0.5) == 0.0
        assert h.quantile(1.0) == 0.0

    def test_q0_returns_first_nonempty_bucket(self):
        h = Histogram()
        h.observe(3.0)   # le=5
        h.observe(700.0)  # le=1000
        # Previously q=0 returned bucket 1 (empty): target degenerated
        # to 0, satisfied before any observation was accumulated.
        assert h.quantile(0.0) == 5
        assert h.quantile(1.0) == 1000

    def test_q_is_clamped(self):
        h = Histogram()
        h.observe(3.0)
        assert h.quantile(-1.0) == 5
        assert h.quantile(2.0) == 5

    def test_mid_quantiles_unchanged(self):
        h = Histogram()
        for v in (1, 1, 8, 60, 400, 900, 3000, 9999):
            h.observe(float(v))
        assert h.quantile(0.5) == 100   # 4th of 8 obs sits in le=100
        assert h.quantile(0.99) == math.inf
