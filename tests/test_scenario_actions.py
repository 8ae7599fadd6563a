"""Integration tests for reclaim / preempt / consolidation /
stalegangeviction — analog of the reference's
pkg/scheduler/actions/integration_tests/{reclaim,preempt,consolidation,
stalegangeviction}."""

import numpy as np
import pytest

from kai_scheduler_tpu.api import PodStatus, resources as rs
from tests.fixtures import build_session, placements, run_action


def statuses(ssn, job):
    return {t.uid: t.status.name
            for t in ssn.cluster.podgroups[job].pods.values()}


class TestReclaim:
    def _spec(self, **overrides):
        spec = {
            "nodes": {"n1": {"gpu": 8}},
            "queues": {
                "q_a": {"deserved": dict(cpu="16", memory="128Gi", gpu=4)},
                "q_b": {"deserved": dict(cpu="16", memory="128Gi", gpu=4)},
            },
            "jobs": {
                # q_a hogs the whole node.
                "hog1": {"queue": "q_a",
                         "tasks": [{"gpu": 4, "status": "RUNNING",
                                    "node": "n1"}]},
                "hog2": {"queue": "q_a", "creation_ts": 10.0,
                         "tasks": [{"gpu": 4, "status": "RUNNING",
                                    "node": "n1"}]},
                # q_b starved, under fair share.
                "starved": {"queue": "q_b", "tasks": [{"gpu": 4}]},
            },
        }
        spec.update(overrides)
        return spec

    def test_reclaims_over_share_queue(self):
        ssn = build_session(self._spec())
        run_action(ssn, "reclaim")
        # One hog evicted; starved job pipelined onto the freed node.
        assert len(ssn.cache.evicted) == 1
        st = statuses(ssn, "starved")
        assert st["starved-0"] == "PIPELINED"
        # The newer hog is the weaker claim.
        assert ssn.cluster.podgroups["hog2"].pods["hog2-0"].status \
            == PodStatus.RELEASING

    def test_no_reclaim_when_within_fair_share(self):
        # q_b already holds its fair share -> CanReclaimResources fails.
        ssn = build_session({
            "nodes": {"n1": {"gpu": 8}},
            "queues": {
                "q_a": {"deserved": dict(cpu="16", memory="128Gi", gpu=4)},
                "q_b": {"deserved": dict(cpu="16", memory="128Gi", gpu=4)},
            },
            "jobs": {
                "a_run": {"queue": "q_a",
                          "tasks": [{"gpu": 4, "status": "RUNNING",
                                     "node": "n1"}]},
                "b_run": {"queue": "q_b",
                          "tasks": [{"gpu": 4, "status": "RUNNING",
                                     "node": "n1"}]},
                "b_more": {"queue": "q_b", "tasks": [{"gpu": 4}]},
            },
        })
        run_action(ssn, "reclaim")
        assert ssn.cache.evicted == []

    def test_non_preemptible_victims_protected(self):
        spec = self._spec()
        spec["jobs"]["hog1"]["preemptible"] = False
        spec["jobs"]["hog2"]["preemptible"] = False
        ssn = build_session(spec)
        run_action(ssn, "reclaim")
        assert ssn.cache.evicted == []

    def test_minruntime_protects_young_victims(self):
        spec = self._spec()
        spec["now"] = 1000.0
        spec["queues"]["q_a"]["reclaim_min_runtime"] = 600.0
        for j in ("hog1", "hog2"):
            spec["jobs"][j]["last_start_ts"] = 900.0  # 100s old < 600s
        ssn = build_session(spec)
        run_action(ssn, "reclaim")
        assert ssn.cache.evicted == []


class TestPreempt:
    def _spec(self):
        return {
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"q": {"deserved": dict(cpu="32", memory="256Gi",
                                              gpu=8)}},
            "jobs": {
                "low": {"queue": "q", "priority": 1,
                        "tasks": [{"gpu": 8, "status": "RUNNING",
                                   "node": "n1"}]},
                "high": {"queue": "q", "priority": 10,
                         "tasks": [{"gpu": 8}]},
            },
        }

    def test_higher_priority_preempts(self):
        ssn = build_session(self._spec())
        run_action(ssn, "preempt")
        assert len(ssn.cache.evicted) == 1
        assert statuses(ssn, "high")["high-0"] == "PIPELINED"

    def test_equal_priority_does_not_preempt(self):
        spec = self._spec()
        spec["jobs"]["high"]["priority"] = 1
        ssn = build_session(spec)
        run_action(ssn, "preempt")
        assert ssn.cache.evicted == []

    def test_cross_queue_never_preempts(self):
        spec = self._spec()
        spec["queues"]["q2"] = {}
        spec["jobs"]["high"]["queue"] = "q2"
        ssn = build_session(spec)
        run_action(ssn, "preempt")
        assert ssn.cache.evicted == []


class TestConsolidation:
    def test_relocates_to_make_room(self):
        # Two 4-GPU pods spread across two 8-GPU nodes; an 8-GPU gang needs
        # one node emptied.  Moving one pod to the other node frees it.
        ssn = build_session({
            "nodes": {"n1": {"gpu": 8}, "n2": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {
                "frag1": {"queue": "q",
                          "tasks": [{"gpu": 4, "status": "RUNNING",
                                     "node": "n1"}]},
                "frag2": {"queue": "q",
                          "tasks": [{"gpu": 4, "status": "RUNNING",
                                     "node": "n2"}]},
                "big": {"queue": "q", "tasks": [{"gpu": 8}]},
            },
        })
        # Production order: allocate fails the job first (recording the fit
        # error consolidation now requires), then consolidation relocates.
        run_action(ssn, "allocate")
        run_action(ssn, "consolidation")
        # One frag pod moved (evicted + pipelined elsewhere); big pipelined.
        assert len(ssn.cache.evicted) == 1
        st = statuses(ssn, "big")
        assert st["big-0"] == "PIPELINED"
        # The displaced pod is re-placed, not lost.
        moved = [pg for pg in ("frag1", "frag2")
                 if any(t.status == PodStatus.PIPELINED
                        for t in ssn.cluster.podgroups[pg].pods.values())]
        assert len(moved) == 1

    def test_no_solution_without_full_replacement(self):
        # No room anywhere to re-place a displaced pod -> no consolidation.
        ssn = build_session({
            "nodes": {"n1": {"gpu": 8}, "n2": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {
                "f1": {"queue": "q", "tasks": [{"gpu": 8, "status": "RUNNING",
                                                "node": "n1"}]},
                "f2": {"queue": "q", "tasks": [{"gpu": 8, "status": "RUNNING",
                                                "node": "n2"}]},
                "big": {"queue": "q", "tasks": [{"gpu": 8}]},
            },
        })
        run_action(ssn, "consolidation")
        assert ssn.cache.evicted == []

    @pytest.mark.parametrize("newest", ("a", "b", "alone"))
    def test_moves_the_job_whose_leaving_seats_the_pending_pod(self,
                                                               newest):
        # n1 is shared by two jobs, n2 holds one alone, n3 has room for
        # what is moved.  Whichever is newest, moving "alone" empties a
        # node with one pod moved; newest first would move "a" and "b"
        # (two pods) where either of them is the newest.
        def frag(name, node):
            return {"queue": "q", "creation_ts": 2.0 if name == newest
                    else 1.0,
                    "tasks": [{"gpu": 2, "status": "RUNNING",
                               "node": node}]}
        ssn = build_session({
            "nodes": {"n1": {"gpu": 8}, "n2": {"gpu": 8}, "n3": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {
                "a": frag("a", "n1"), "b": frag("b", "n1"),
                "alone": frag("alone", "n2"),
                "held": {"queue": "q", "preemptible": False,
                         "tasks": [{"gpu": 4, "status": "RUNNING",
                                    "node": "n3"}]},
                "big": {"queue": "q", "tasks": [{"gpu": 8}]},
            },
        })
        from kai_scheduler_tpu.actions.consolidation import \
            collect_consolidation_victims
        big = ssn.cluster.podgroups["big"]
        victims = collect_consolidation_victims(
            ssn, big, list(big.pods.values()))
        assert victims[0].name == "alone"
        assert {v.name for v in victims} == {"a", "b", "alone"}
        # Among the jobs that seat nothing alone: the newest first.
        assert victims[1].name == ("b" if newest == "b" else "a")
        run_action(ssn, "allocate")
        run_action(ssn, "consolidation")
        assert ssn.cache.evicted == ["alone-0"]
        assert statuses(ssn, "big")["big-0"] == "PIPELINED"
        assert statuses(ssn, "alone")["alone-0"] == "PIPELINED"


class TestStaleGangEviction:
    def test_evicts_stale_gang_after_grace(self):
        ssn = build_session({
            "now": 1000.0,
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {"gang": {
                "queue": "q", "min_available": 3,
                "last_start_ts": 100.0,  # stale for 900s > 60s grace
                "tasks": [
                    {"gpu": 2, "status": "RUNNING", "node": "n1"},
                    {"gpu": 2, "status": "FAILED"},
                    {"gpu": 2, "status": "FAILED"},
                ]}},
        })
        run_action(ssn, "stalegangeviction")
        assert len(ssn.cache.evicted) == 1  # the surviving pod
        assert any(k == "StaleGangEvicted" for k, _ in ssn.cache.events)

    def test_grace_period_respected(self):
        ssn = build_session({
            "now": 1000.0,
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {"gang": {
                "queue": "q", "min_available": 3,
                "last_start_ts": 990.0,  # only 10s stale
                "tasks": [
                    {"gpu": 2, "status": "RUNNING", "node": "n1"},
                    {"gpu": 2, "status": "FAILED"},
                    {"gpu": 2, "status": "FAILED"},
                ]}},
        })
        run_action(ssn, "stalegangeviction")
        assert ssn.cache.evicted == []

    def test_healthy_gang_untouched(self):
        ssn = build_session({
            "now": 1000.0,
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"q": {}},
            "jobs": {"gang": {
                "queue": "q", "min_available": 2,
                "last_start_ts": 100.0,
                "tasks": [
                    {"gpu": 2, "status": "RUNNING", "node": "n1"},
                    {"gpu": 2, "status": "RUNNING", "node": "n1"},
                ]}},
        })
        run_action(ssn, "stalegangeviction")
        assert ssn.cache.evicted == []


class TestBatchedPrescreen:
    def test_prescreen_skips_infeasible_prefixes(self):
        """With many small victims, the batched pre-screen must skip the
        prefixes that cannot host the reclaimer — visible as fewer
        simulated scenarios than victim steps."""
        from kai_scheduler_tpu.utils.metrics import METRICS
        # 8 single-GPU victims in over-quota queue b; reclaimer needs 4
        # GPUs, so prefixes 1..3 are infeasible and must not simulate.
        jobs = {
            f"v{i}": {"queue": "b", "tasks": [
                {"gpu": 1, "status": "RUNNING", "node": "n1"}]}
            for i in range(8)}
        jobs["claimer"] = {"queue": "a", "tasks": [{"gpu": 4}]}
        ssn = build_session({
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"a": {"deserved": {"gpu": 4}},
                       "b": {"deserved": {"gpu": 4}}},
            "jobs": jobs,
        })
        key = 'scenarios_simulation_by_action{action="reclaim"}'
        before = METRICS.counters.get(key, 0)
        run_action(ssn, "reclaim")
        after = METRICS.counters.get(key, 0)
        p = placements(ssn)
        assert p["claimer-0"][0] == "n1"
        evicted = [uid for uid, (node, status) in p.items()
                   if status == "RELEASING"]
        assert len(evicted) == 4
        # The prescreen engages lazily after scenario_prescreen_after
        # (=1) failed simulations, then skips the remaining infeasible
        # prefix (3 victims) in one batched call: 1 warmup failure + 1
        # successful simulation, instead of 4 sequential scenarios.
        assert after - before == 2

    def test_prescreen_operands_are_counted_at_the_seam(self):
        """The prescreen's operands cross where every kernel's do:
        ``device_upload_bytes`` moves by the bytes of the release rows
        and the claimer's padded task rows."""
        from kai_scheduler_tpu.utils.metrics import METRICS
        from kai_scheduler_tpu.utils.tracing import TRACER
        jobs = {
            f"v{i}": {"queue": "b", "tasks": [
                {"gpu": 1, "status": "RUNNING", "node": "n1"}]}
            for i in range(8)}
        jobs["claimer"] = {"queue": "a", "tasks": [{"gpu": 4}]}
        ssn = build_session({
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"a": {"deserved": {"gpu": 4}},
                       "b": {"deserved": {"gpu": 4}}},
            "jobs": jobs,
        })
        before = METRICS.counters.get("device_upload_bytes", 0.0)
        TRACER.begin_cycle(1)
        run_action(ssn, "reclaim")
        trace = TRACER.end_cycle()
        moved = METRICS.counters.get("device_upload_bytes", 0.0) - before
        ids = {sp.span_id: sp for sp in trace.spans}
        stages = [sp for sp in trace.spans if sp.name == "seam:stage"]
        (prescreen,) = [sp for sp in stages if ids[sp.parent_id].name
                        == "dispatch:scenario_prescreen"]
        # Seven victim steps are left after the one failed simulation:
        # 8 prefixes, 8 release rows; one claimer task, no padding.
        snap = ssn.snapshot
        n_res = snap.task_req.shape[1]
        release = 8 * 4 + 8 * 4 + 8 * n_res * 8
        task_rows = (n_res * 8 + 4 + snap.task_selector.shape[1] * 4
                     + snap.task_tolerations.shape[1] * 4)
        assert prescreen.attrs["operands"] == 7
        assert prescreen.attrs["bytes_device"] == release + task_rows
        # Nothing else uploads uncounted: the cycle's count is its
        # stagings', the prescreen's among them.
        assert moved == sum(sp.attrs["bytes_device"] for sp in stages)
        assert moved > prescreen.attrs["bytes_device"] > 0

    def test_prescreen_disabled_matches(self):
        """Soundness guard: results identical with prescreen off."""
        from kai_scheduler_tpu.framework import SchedulerConfig
        spec = {
            "nodes": {"n1": {"gpu": 8}},
            "queues": {"a": {"deserved": {"gpu": 4}},
                       "b": {"deserved": {"gpu": 4}}},
            "jobs": {
                **{f"v{i}": {"queue": "b", "tasks": [
                    {"gpu": 1, "status": "RUNNING", "node": "n1"}]}
                   for i in range(6)},
                "claimer": {"queue": "a", "tasks": [{"gpu": 3}]},
            },
        }
        on = build_session(spec)
        run_action(on, "reclaim")
        cfg = SchedulerConfig(scenario_prescreen_max=0)
        off = build_session(spec, cfg)
        run_action(off, "reclaim")
        assert placements(on) == placements(off)
