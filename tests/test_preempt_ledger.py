"""A cycle's preemptors share one ledger of their queue's victims (PR 51).

The preempt action used to rebuild, filter and trim its queue's whole
victim list for every preemptor; it keeps one ``VictimLedger`` a queue a
cycle now, and a preemptor's candidates are a slice of it.  The result has
to be the same result, so every case here holds the action to the parent's
loop, kept below as ``parent_preempt`` (its list comes from
``collect_preempt_victims``, the helper that surveys anew for every
preemptor):

(a) seeded small fleets: what every solve was handed, the steps it built,
    the jobs it took from, and every pod's status and node afterwards;
(b) the lazy head under ``preempt_min_runtime`` against
    ``filter(whole list)[:max_victims_considered]``;
(c) ``_within_budget`` over the ledger's sums against the pod-by-pod walk;
(d) ``preempt_victims_examined_total`` and the benchmark's metric file.
"""

import json
import os

import numpy as np
import pytest

from kai_scheduler_tpu.actions import preempt, solvers
from kai_scheduler_tpu.actions.preempt import (EXAMINED, FILTER_CHUNK,
                                               VictimLedger,
                                               collect_preempt_victims,
                                               survey_preempt_victims)
from kai_scheduler_tpu.actions.utils import INFINITE, JobsOrderByQueues
from kai_scheduler_tpu.api import resources as rs
from kai_scheduler_tpu.framework.conf import (DEFAULT_PLUGINS, PluginConfig,
                                              SchedulerConfig)
from kai_scheduler_tpu.plugins.minruntime import MinRuntimePlugin
from kai_scheduler_tpu.utils.metrics import METRICS
from tests.fixtures import build_session, run_action

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the parent's loop, the oracle --------------------------------------------
def parent_preempt(ssn, solve=solvers.solve_job) -> None:
    """``PreemptAction.execute`` as the parent had it: for every preemptor
    one pass over its queue's surveyed victims, the filters over the whole
    of what is left, and a solver that reads every victim's pods itself.
    The parent surveyed once and trimmed; a new survey limited to the first
    one's jobs is the same list (a job leaves either with its last active
    pod, and the sort is the same stable sort)."""
    pending = [pg for pg in ssn.cluster.podgroups.values()
               if pg.has_tasks_to_allocate()
               and pg.is_ready_for_scheduling()
               and pg.queue_id in ssn.cluster.queues]
    if not pending:
        return
    order = JobsOrderByQueues(
        ssn, pending, ssn.config.queue_depth_per_action.get("preempt",
                                                            INFINITE))
    failed_signatures = set()
    surveyed = None
    while not order.empty():
        job = order.pop_next_job()
        if job is None:
            break
        sig = job.scheduling_signature()
        if ssn.config.use_scheduling_signatures and sig in failed_signatures:
            order.requeue_queue(job.queue_id)
            continue
        if surveyed is None:
            surveyed = {pg.uid for jobs in
                        survey_preempt_victims(ssn).values() for pg in jobs}
        victims = [pg for pg in collect_preempt_victims(ssn, job)
                   if pg.uid in surveyed]
        victims = ssn.filter_preempt_victims(job, victims)
        if not victims:
            order.requeue_queue(job.queue_id)
            continue
        result = solve(ssn, job, victims, ssn.validate_preempt_scenario,
                       "preempt")
        if not result.success and ssn.config.use_scheduling_signatures:
            failed_signatures.add(sig)
        order.requeue_queue(job.queue_id)


def recording(log: list):
    """``solve_job`` that writes down what it was handed and what came of
    it: the victims it will read, the steps they make (from the offers
    where the caller handed them), the verdict and the jobs taken from."""
    def solve(ssn, job, victims, validate, action, offers=None):
        cap = ssn.config.max_victims_considered
        steps = solvers.ScenarioBuilder(
            job, [], victims[:cap],
            offers)._steps
        entry = {"job": job.uid, "victims": [v.uid for v in victims[:cap]],
                 "steps": [(v.uid, [t.uid for t in ts]) for v, ts in steps]}
        if offers is not None:
            assert len(offers.reqs) == len(victims) <= cap
        result = solvers.solve_job(ssn, job, victims, validate, action,
                                   offers=offers)
        entry.update(solved=result.success, took=list(result.evicted_jobs),
                     tried=result.scenarios_tried,
                     skipped=result.scenarios_skipped)
        log.append(entry)
        return result
    return solve


def pods_of(ssn) -> dict:
    return {t.uid: (t.status.name, t.node_name)
            for pg in ssn.cluster.podgroups.values()
            for t in pg.pods.values()}


# -- (a) seeded fleets --------------------------------------------------------
GPUS = 4


def fleet(seed: int, preemptors: int, nodes: int = 28) -> dict:
    """A full fleet of ``nodes`` four-GPU nodes under queue ``team``'s jobs
    of three priorities (some fixed, some of queue ``other``, creation
    times that tie), a third of them elastic (one GPU a pod, a gang
    minimum under their count, so a solve may take their surplus alone),
    and ``preemptors`` pending jobs of ``team`` at three priorities, a few
    of them preemptible jobs that already run a pod."""
    rng = np.random.default_rng(seed)
    jobs = {}

    def running(name, node, gpu):
        return {"name": name, "gpu": gpu, "cpu": "1", "status": "RUNNING",
                "node": node}

    for n in range(nodes):
        node = f"n{n:02d}"
        def common():
            return {"queue": "team" if rng.random() < 0.85 else "other",
                    "priority": int(rng.choice([10, 50, 90])),
                    "preemptible": bool(rng.random() < 0.9),
                    "creation_ts": float(rng.integers(0, 12))}
        shape = rng.integers(0, 3)
        if shape == 0:           # one elastic job, surplus of 1-3 pods
            jobs[f"el{n:02d}"] = dict(
                common(), min_available=int(rng.integers(1, GPUS)),
                tasks=[running(f"el{n:02d}-{i}", node, 1)
                       for i in range(GPUS)])
        elif shape == 1:         # two gangs of two
            for h in range(2):
                jobs[f"pair{n:02d}{h}"] = dict(
                    common(), min_available=2,
                    tasks=[running(f"pair{n:02d}{h}-{i}", node, 1)
                           for i in range(2)])
        else:                    # four single pods
            for h in range(GPUS):
                jobs[f"one{n:02d}{h}"] = dict(
                    common(), min_available=1,
                    tasks=[running(f"one{n:02d}{h}-0", node, 1)])
    for p in range(preemptors):
        gang = int(rng.integers(1, 3))
        job = {"queue": "team",
               "priority": int(rng.choice([50, 90, 125])),
               "preemptible": bool(rng.random() < 0.4),
               "creation_ts": 100.0 + p, "min_available": gang,
               "tasks": [{"name": f"pre{p:02d}-{i}", "cpu": "1",
                          "gpu": int(rng.integers(1, 3))}
                         for i in range(gang)]}
        jobs[f"pre{p:02d}"] = job
    spec = {"nodes": {f"n{n:02d}": {"gpu": GPUS} for n in range(nodes)},
            "queues": {"team": {"deserved": {"gpu": 4 * nodes}},
                       "other": {"deserved": {"gpu": 4 * nodes}}},
            "jobs": jobs}
    # Preemptors that run already and are preemptible: victims of the
    # stronger ones, never of themselves.
    for p in range(0, preemptors, 5):
        node = f"x{p:02d}"
        spec["nodes"][node] = {"gpu": 1}
        job = jobs[f"pre{p:02d}"]
        job.update(preemptible=True, min_available=1)
        job["tasks"].insert(0, running(f"pre{p:02d}-r", node, 1))
    return spec


CONFIGS = {
    "cap6": dict(max_victims_considered=6),
    "cap3-no-prescreen": dict(max_victims_considered=3,
                              scenario_prescreen_max=0),
    "cap12-no-signatures": dict(max_victims_considered=12,
                                use_scheduling_signatures=False,
                                scenario_prescreen_max=0),
}


def both_ways(spec, config: dict, monkeypatch):
    """The spec driven by the action and by the parent's loop."""
    ours, theirs = [], []
    ssn = build_session(spec, SchedulerConfig(**config))
    monkeypatch.setattr(preempt, "solve_job", recording(ours))
    run_action(ssn, "preempt")
    ref = build_session(spec, SchedulerConfig(**config))
    parent_preempt(ref, recording(theirs))
    return ssn, ref, ours, theirs


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("seed, preemptors", [
    (1, 8), (2, 12), (3, 16), (4, 24), (5, 32), (3000000019, 32)])
def test_every_preemptor_is_handed_what_the_parents_rebuild_gave(
        seed, preemptors, config, monkeypatch):
    ssn, ref, ours, theirs = both_ways(fleet(seed, preemptors),
                                       CONFIGS[config], monkeypatch)
    assert len(ours) == len(theirs) >= 4
    for mine, want in zip(ours, theirs):
        assert mine == want
    assert pods_of(ssn) == pods_of(ref)
    # Non-vacuity: preemptors were solved, a cap was reached, an elastic
    # job shed its surplus and stayed a candidate with fewer pods.
    assert sum(e["solved"] for e in ours) >= 2
    cap = CONFIGS[config]["max_victims_considered"]
    assert any(len(e["victims"]) == cap for e in ours)
    assert [uid for i, e in enumerate(ours) if e["solved"]
            for uid in e["took"]
            if any(uid == v for later in ours[i + 1:]
                   for v, _ in later["steps"])]


def test_a_job_that_shed_its_surplus_stays_with_what_is_left(monkeypatch):
    """Hand-made: the weakest job runs four pods over a minimum of two.
    The first preemptor takes its surplus, the second meets it again with
    its core gang alone (a recomputed offer), and then it is gone."""
    jobs = {"elastic": {"queue": "team", "priority": 10, "min_available": 2,
                        "creation_ts": 9.0,
                        "tasks": [{"gpu": 1, "cpu": "1", "status": "RUNNING",
                                   "node": f"n{i // 2}"}
                                  for i in range(4)]},
            "solid": {"queue": "team", "priority": 10, "min_available": 2,
                      "creation_ts": 1.0,
                      "tasks": [{"gpu": 1, "cpu": "1", "status": "RUNNING",
                                 "node": "n2"}
                                for i in range(2)]}}
    for p in range(3):
        jobs[f"vip{p}"] = {"queue": "team", "priority": 125,
                           "preemptible": False, "creation_ts": 50.0 + p,
                           "tasks": [{"gpu": 2, "cpu": "1"}]}
    spec = {"nodes": {f"n{i}": {"gpu": 2} for i in range(3)},
            "queues": {"team": {"deserved": {"gpu": 64}}}, "jobs": jobs}
    ssn, ref, ours, theirs = both_ways(
        spec, dict(use_scheduling_signatures=False), monkeypatch)
    assert ours == theirs
    assert [e["steps"] for e in ours] == [
        [("elastic", ["elastic-2", "elastic-3"]),
         ("elastic", ["elastic-0", "elastic-1"]),
         ("solid", ["solid-0", "solid-1"])],
        [("elastic", ["elastic-0", "elastic-1"]),
         ("solid", ["solid-0", "solid-1"])],
        [("solid", ["solid-0", "solid-1"])]]
    assert [e["took"] for e in ours] == [["elastic"], ["elastic"],
                                         ["solid"]]
    assert pods_of(ssn) == pods_of(ref)


def test_a_running_preemptible_preemptor_is_no_victim_of_itself(
        monkeypatch):
    """It is in the ledger at its own priority: inside a stronger
    preemptor's slice, outside its own."""
    def running(node):
        return {"gpu": 2, "cpu": "1", "status": "RUNNING", "node": node}
    jobs = {"low-a": {"queue": "team", "priority": 10, "creation_ts": 2.0,
                      "tasks": [running("n0")]},
            "low-b": {"queue": "team", "priority": 10, "creation_ts": 1.0,
                      "tasks": [running("n1")]},
            "mid": {"queue": "team", "priority": 50, "creation_ts": 5.0,
                    "tasks": [running("n2"), {"gpu": 2, "cpu": "1"}]},
            "top": {"queue": "team", "priority": 125, "preemptible": False,
                    "creation_ts": 9.0, "tasks": [{"gpu": 2, "cpu": "1"}]}}
    spec = {"nodes": {f"n{i}": {"gpu": 2} for i in range(3)},
            "queues": {"team": {"deserved": {"gpu": 64}}}, "jobs": jobs}
    ssn, ref, ours, theirs = both_ways(spec, {}, monkeypatch)
    assert ours == theirs
    assert [(e["job"], e["victims"], e["took"]) for e in ours] == [
        ("top", ["low-a", "low-b", "mid"], ["low-a"]),
        ("mid", ["low-b"], ["low-b"])]
    assert pods_of(ssn) == pods_of(ref)
    assert pods_of(ssn)["mid-0"] == ("RUNNING", "n2")
    assert pods_of(ssn)["mid-1"] == ("PIPELINED", "n1")


def test_two_queues_keep_a_ledger_each(monkeypatch):
    spec = fleet(7, 8)
    for name, job in list(spec["jobs"].items()):
        if name.startswith("pre") and int(name[3:]) % 2:
            job["queue"] = "other"
    ssn, ref, ours, theirs = both_ways(spec, CONFIGS["cap6"], monkeypatch)
    assert ours == theirs and {e["job"] for e in ours} >= {"pre00", "pre01"}
    assert pods_of(ssn) == pods_of(ref)


# -- (b) the lazy head under a minimum runtime --------------------------------
NOW = 1000.0


def runtime_spec(protected: list, where: str) -> tuple:
    """Queue ``team`` (child of ``dept``) under one-GPU jobs, job ``i``
    the ``i``-th weakest, started 10 s ago where ``protected[i]`` and
    900 s ago elsewhere; a minimum runtime of 100 s set at ``where``."""
    jobs = {f"v{i:03d}": {
        "queue": "team", "priority": 10, "creation_ts": float(500 - i),
        "last_start_ts": NOW - (10.0 if young else 900.0),
        "tasks": [{"gpu": 1, "cpu": "1", "status": "RUNNING",
                   "node": f"n{i:03d}"}]}
        for i, young in enumerate(protected)}
    jobs["vip"] = {"queue": "team", "priority": 125, "preemptible": False,
                   "tasks": [{"gpu": 1, "cpu": "1"}]}
    queues = {"dept": {"deserved": {"gpu": 512}},
              "team": {"parent": "dept", "deserved": {"gpu": 512}}}
    plugins = [PluginConfig(p) for p in DEFAULT_PLUGINS]
    if where == "default":
        plugins = [PluginConfig(p, {"preempt_min_runtime": 100.0}
                                if p == "minruntime" else {})
                   for p in DEFAULT_PLUGINS]
    elif where != "nowhere":
        queues[{"queue": "team", "parent": "dept"}[where]][
            "preempt_min_runtime"] = 100.0
    spec = {"now": NOW, "queues": queues, "jobs": jobs,
            "nodes": {f"n{i:03d}": {"gpu": 1}
                      for i in range(len(protected))}}
    return spec, plugins


CAP = 16
PATTERNS = {
    # More than the cap pass: the head is cut at the cap.
    "every-third": [i % 3 == 0 for i in range(200)],
    # The first chunk (the cap's worth) is all protected.
    "first-chunk-protected": [i < CAP for i in range(200)],
    # Chunks of FILTER_CHUNK that come back empty, then a few.
    "long-protected-run": [i < CAP + 2 * FILTER_CHUNK + 5
                           for i in range(CAP + 3 * FILTER_CHUNK)],
    # Fewer than the cap pass in the whole list.
    "few-pass": [i % 20 != 7 for i in range(200)],
    "none-pass": [True] * 40,
    "all-pass": [False] * 40,
    "shorter-than-the-cap": [False, True, False, False, True],
}


@pytest.mark.parametrize("where", ("queue", "parent", "default", "nowhere"))
@pytest.mark.parametrize("pattern", PATTERNS)
def test_the_lazy_head_is_the_filtered_lists_head(pattern, where):
    protected = PATTERNS[pattern]
    spec, plugins = runtime_spec(protected, where)
    ssn = build_session(spec, SchedulerConfig(plugins=plugins,
                                              max_victims_considered=CAP))
    vip = ssn.cluster.podgroups["vip"]
    whole = collect_preempt_victims(ssn, vip)
    assert [pg.uid for pg in whole] == [f"v{i:03d}"
                                        for i in range(len(protected))]
    want = ssn.filter_preempt_victims(vip, whole)[:CAP]
    if where == "nowhere":
        assert want == whole[:CAP]
    else:
        assert [pg.uid for pg in want] == [
            f"v{i:03d}" for i, p in enumerate(protected) if not p][:CAP]
    ledger = VictimLedger(survey_preempt_victims(ssn)["team"])
    before = METRICS.counters[EXAMINED]
    victims, offers = ledger.candidates(ssn, vip)
    assert victims == want
    assert offers.core == [list(pg.pods.values())
                                        for pg in want]
    # What the filters were handed stops a chunk past the last one needed.
    handed = METRICS.counters[EXAMINED] - before - len(victims)
    if len(want) == CAP:
        last = ledger.jobs.index(want[-1]) + 1
        assert last <= handed <= last + max(CAP, FILTER_CHUNK)
    else:
        assert handed == len(protected)


def test_the_filter_hands_back_its_list_where_nothing_can_be_protected():
    spec, plugins = runtime_spec([False] * 8, "nowhere")
    ssn = build_session(spec, SchedulerConfig(plugins=plugins))
    vip = ssn.cluster.podgroups["vip"]
    whole = collect_preempt_victims(ssn, vip)
    assert ssn.filter_preempt_victims(vip, whole) is whole
    assert ssn.filter_reclaim_victims(vip, whole) is whole
    spec, plugins = runtime_spec([False] * 8, "parent")
    ssn = build_session(spec, SchedulerConfig(plugins=plugins))
    whole = collect_preempt_victims(ssn, ssn.cluster.podgroups["vip"])
    kept = ssn.filter_preempt_victims(vip, whole)
    assert kept == whole and kept is not whole


@pytest.mark.parametrize("kind", ("preempt", "reclaim"))
def test_the_minimum_is_each_victims_own_queues(kind):
    """Victims of several queues in one call (reclaim's case): each is
    judged by its own queue's minimum, in the order given."""
    jobs = {}
    for i, (queue, age) in enumerate([("a", 10), ("b", 10), ("c", 10),
                                      ("a", 900), ("b", 50), ("c", 900)]):
        jobs[f"j{i}"] = {"queue": queue, "last_start_ts": NOW - age,
                         "tasks": [{"gpu": 1, "status": "RUNNING",
                                    "node": "n0"}]}
    spec = {"now": NOW, "nodes": {"n0": {"gpu": 8}}, "jobs": jobs,
            "queues": {"a": {f"{kind}_min_runtime": 100.0},
                       "b": {f"{kind}_min_runtime": 30.0}, "c": {}}}
    ssn = build_session(spec)
    victims = list(ssn.cluster.podgroups.values())
    (plugin,) = {fn.__self__ for fn in ssn.preempt_victim_filters
                 if isinstance(fn.__self__, MinRuntimePlugin)}
    kept = getattr(plugin, f"filter_{kind}")(None, victims)
    assert [pg.uid for pg in kept] == ["j2", "j3", "j4", "j5"]
    other = "reclaim" if kind == "preempt" else "preempt"
    assert getattr(plugin, f"filter_{other}")(None, victims) is victims


# -- (c) the budget -----------------------------------------------------------
def walked_budget(ssn, tasks, ordered_victims) -> bool:
    """``_within_budget`` as the parent had it, pod by pod."""
    total_req = np.sum([t.res_req.to_vec(mig_as_gpu=False)
                        for t in tasks], axis=0)
    budget = ssn.node_idle.sum(axis=0) + ssn.node_releasing.sum(axis=0)
    budget[rs.RES_GPU] += solvers.fractional_headroom(ssn)
    for vjob in ordered_victims:
        for t in vjob.pods.values():
            if t.is_active_allocated():
                budget = budget + t.res_req.to_vec(mig_as_gpu=False)
    return not np.any(total_req > budget + 1e-9)


def budget_spec(victim_gpus: list, ask: float, idle: float = 0.0,
                mem: str = "1Gi") -> dict:
    jobs = {f"v{i}": {"queue": "team", "priority": 10,
                      "tasks": [dict(
                          {"cpu": "1", "mem": mem, "status": "RUNNING",
                           "node": f"n{i}"},
                          **({"gpu": g} if float(g).is_integer()
                             else {"gpu_fraction": g}))]}
            for i, g in enumerate(victim_gpus)}
    jobs["vip"] = {"queue": "team", "priority": 125, "preemptible": False,
                   "tasks": [dict({"cpu": "1", "mem": mem},
                                  **({"gpu": ask}
                                     if float(ask).is_integer()
                                     else {"gpu_fraction": ask}))]}
    nodes = {f"n{i}": {"gpu": max(1, int(np.ceil(g)))}
             for i, g in enumerate(victim_gpus)}
    if idle:
        nodes["idle"] = {"gpu": idle}
    return {"nodes": nodes, "jobs": jobs,
            "queues": {"team": {"deserved": {"gpu": 512}}}}


@pytest.mark.parametrize("victim_gpus, ask, verdict", [
    ([1] * 7, 7, True), ([1] * 7, 8, False), ([2, 4, 8, 1], 15, True),
    ([2, 4, 8, 1], 16, False), ([], 1, False), ([3], 3, True),
    ([1] * 40, 40, True), ([1] * 40, 41, False),
], ids=lambda v: str(v).replace(" ", "")[:18])
def test_the_summed_budget_gives_the_walks_verdict_on_whole_requests(
        victim_gpus, ask, verdict):
    ssn = build_session(budget_spec(victim_gpus, ask))
    vip = ssn.cluster.podgroups["vip"]
    victims = collect_preempt_victims(ssn, vip)
    tasks = list(vip.pods.values())
    offers = solvers.VictimOffers.read(victims)
    assert all((r == np.floor(r)).all() for r in offers.reqs)
    assert solvers._within_budget(ssn, tasks, offers) is verdict
    assert walked_budget(ssn, tasks, victims) is verdict


@pytest.mark.parametrize("seed", range(8))
def test_the_summed_budget_is_the_walked_one_to_the_bit_on_whole_requests(
        seed):
    """Whole numbers under 2**53 add exactly in any order: the two budgets
    agree in every bit, at the edge of the verdict too."""
    rng = np.random.default_rng(seed)
    gpus = [int(g) for g in rng.integers(1, 9, size=int(rng.integers(1, 60)))]
    total = sum(gpus)
    for ask, verdict in ((total, True), (total + 1, False)):
        ssn = build_session(budget_spec(gpus, ask, mem="3Gi"))
        vip = ssn.cluster.podgroups["vip"]
        victims = collect_preempt_victims(ssn, vip)
        tasks = list(vip.pods.values())
        offers = solvers.VictimOffers.read(victims)
        assert solvers._within_budget(ssn, tasks, offers) is verdict
        assert walked_budget(ssn, tasks, victims) is verdict
        walked = rs.zeros()
        for v in victims:
            for t in v.pods.values():
                walked = walked + t.res_req.to_vec(mig_as_gpu=False)
        assert np.array_equal(
            walked, np.concatenate(offers.reqs).sum(axis=0))


@pytest.mark.parametrize("seed", range(24))
def test_the_summed_budget_never_refuses_what_the_walk_admits_on_fractions(
        seed):
    """Fractional GPUs: the two orders of adding may round apart, and the
    request is put right at the walked budget, where a last bit decides.
    The sum may admit what the walk refuses; never the other way."""
    rng = np.random.default_rng(1000 + seed)
    fractions = [float(f) for f in rng.choice(
        [0.1, 0.2, 0.3, 0.4, 0.6, 0.7, 0.9], size=int(rng.integers(3, 40)))]
    ssn = build_session(budget_spec(fractions, 0.5))
    vip = ssn.cluster.podgroups["vip"]
    victims = collect_preempt_victims(ssn, vip)
    offers = solvers.VictimOffers.read(victims)
    rows = np.concatenate(offers.reqs)
    assert list((rows == np.floor(rows)).all(axis=0)) == [True, True, False]
    walked = ssn.node_idle.sum(axis=0) + ssn.node_releasing.sum(axis=0)
    walked[rs.RES_GPU] += solvers.fractional_headroom(ssn)
    for v in victims:
        for t in v.pods.values():
            walked = walked + t.res_req.to_vec(mig_as_gpu=False)

    class Ask:
        """A task that asks ``gpu`` and nothing else."""
        def __init__(self, gpu):
            vec = rs.zeros()
            vec[rs.RES_GPU] = gpu
            self.res_req = type("Req", (), {
                "to_vec": staticmethod(lambda mig_as_gpu=False: vec)})()

    edge = walked[rs.RES_GPU] + 1e-9
    for ulps in range(-4, 5):
        ask = edge
        for _ in range(abs(ulps)):
            ask = np.nextafter(ask, np.inf if ulps > 0 else -np.inf)
        tasks = [Ask(ask)]
        if walked_budget(ssn, tasks, victims):
            assert solvers._within_budget(ssn, tasks, offers)
    # And it is still a precheck: a whole GPU past every victim is refused.
    assert not solvers._within_budget(ssn, [Ask(edge + 1.0)], offers)
    assert not walked_budget(ssn, [Ask(edge + 1.0)], victims)


def test_the_headroom_is_walked_for_only_where_the_budget_refuses_without(
        monkeypatch):
    """Two shared devices, 0.5 and 0.4 in use: the victims' vectors sum to
    0.9 GPU and repacking can empty 1.1 more.  A whole GPU passes by the
    headroom alone; half a GPU passes before anybody asks for it."""
    def shared(i, fraction):
        return {"queue": "team", "priority": 10, "tasks": [{
            "gpu_fraction": fraction, "cpu": "1", "status": "RUNNING",
            "node": f"n{i}", "gpu_group": f"g{i}"}]}
    spec = {"nodes": {"n0": {"gpu": 1}, "n1": {"gpu": 1}},
            "queues": {"team": {"deserved": {"gpu": 8}}},
            "jobs": {"half": shared(0, 0.5), "smaller": shared(1, 0.4),
                     "whole": {"queue": "team", "priority": 125,
                               "tasks": [{"gpu": 1, "cpu": "1"}]},
                     "small": {"queue": "team", "priority": 125,
                               "tasks": [{"gpu_fraction": 0.5,
                                          "cpu": "1"}]},
                     "two": {"queue": "team", "priority": 125,
                             "tasks": [{"gpu": 3, "cpu": "1"}]}}}
    ssn = build_session(spec)
    assert solvers.fractional_headroom(ssn) == pytest.approx(1.1)
    walks = []
    headroom = solvers.fractional_headroom
    monkeypatch.setattr(solvers, "fractional_headroom",
                        lambda ssn: walks.append(1) or headroom(ssn))
    victims = [ssn.cluster.podgroups[j] for j in ("half", "smaller")]
    offers = solvers.VictimOffers.read(victims)
    for name, verdict, walked in (("small", True, 0), ("whole", True, 1),
                                  ("two", False, 1)):
        tasks = list(ssn.cluster.podgroups[name].pods.values())
        del walks[:]
        assert solvers._within_budget(ssn, tasks, offers) is verdict
        assert len(walks) == walked
        assert walked_budget(ssn, tasks, victims) is verdict


def test_no_offers_no_room():
    ssn = build_session(budget_spec([1], 1, idle=2))
    vip = ssn.cluster.podgroups["vip"]
    none = solvers.VictimOffers()
    assert solvers._within_budget(ssn, list(vip.pods.values()), none)
    ssn = build_session(budget_spec([1], 3, idle=2))
    vip = ssn.cluster.podgroups["vip"]
    assert not solvers._within_budget(ssn, list(vip.pods.values()), none)
    assert solvers._within_budget(
        ssn, list(vip.pods.values()),
        solvers.VictimOffers.read([ssn.cluster.podgroups["v0"]]))


def test_an_offer_is_the_split_and_the_sum_of_the_active_pods():
    spec = {"nodes": {"n0": {"gpu": 8}},
            "queues": {"team": {"deserved": {"gpu": 8}}},
            "jobs": {"j": {"queue": "team", "min_available": 2, "tasks": [
                {"name": "j-0", "gpu": 1, "cpu": "2", "status": "RUNNING",
                 "node": "n0"},
                {"name": "j-1", "gpu": 1, "cpu": "2", "status": "RUNNING",
                 "node": "n0"},
                {"name": "j-2", "gpu_fraction": 0.5, "cpu": "2",
                 "status": "RUNNING", "node": "n0"},
                {"name": "j-3", "gpu": 1, "cpu": "2", "status": "RELEASING",
                 "node": "n0"},
                {"name": "j-4", "gpu": 1, "cpu": "2"}]}}}
    ssn = build_session(spec)
    job = ssn.cluster.podgroups["j"]
    elastic, core, reqs = solvers.victim_offer(job)
    assert ([t.name for t in elastic], [t.name for t in core]) \
        == (["j-2"], ["j-0", "j-1"])
    assert (elastic, core) == solvers._split_victim_tasks(job)
    assert reqs.shape == (3, rs.NUM_RES)
    assert list(reqs[:, rs.RES_GPU]) == [0.5, 1.0, 1.0]
    assert reqs[:, rs.RES_CPU].sum() == 6000.0
    elastic, core, reqs = solvers.victim_offer(type(job)("none", "none"))
    assert not elastic and not core and reqs.shape == (0, rs.NUM_RES)
    offers = solvers.VictimOffers.read([job, job])
    assert offers.elastic == [[job.pods["j-2"]]] * 2
    assert len(offers.core) == len(offers.reqs) == 2


# -- (d) the counter and the metric that reads it -----------------------------
def test_the_counter_is_there_at_zero_when_a_session_opens():
    METRICS.reset()
    ssn = build_session({"nodes": {"n0": {"gpu": 1}}})
    assert METRICS.counters[EXAMINED] == 0.0 and EXAMINED in METRICS.counters
    run_action(ssn, "preempt")      # nothing pending: no survey, no read
    assert METRICS.counters[EXAMINED] == 0.0


@pytest.mark.parametrize("seed, preemptors", [(1, 8), (5, 32)])
def test_the_counter_reads_what_the_preemptors_slices_and_patches_touch(
        seed, preemptors, monkeypatch):
    """Not the survey: every preemptor's filtered chunk (the cap's worth
    where no filter drops a victim), the offers read off pods (the first
    preemptor's, then those a commit dropped or that moved into the
    slice), and the jobs a commit patched."""
    cap = 6
    log = []
    ssn = build_session(fleet(seed, preemptors), SchedulerConfig(
        max_victims_considered=cap, scenario_prescreen_max=0))
    monkeypatch.setattr(preempt, "solve_job", recording(log))
    surveyed = sum(len(v) for v in survey_preempt_victims(ssn).values())
    assert surveyed > 4 * cap
    before = METRICS.counters[EXAMINED]
    run_action(ssn, "preempt")
    examined = METRICS.counters[EXAMINED] - before
    solves = len(log)
    patched = sum(len(e["took"]) for e in log if e["solved"])
    assert solves >= 6 and patched >= 2
    # A patched job's offer is read once more, and each job that left lets
    # one more into the slice.
    assert examined <= solves * cap + cap + 3 * patched
    assert examined >= sum(len(e["victims"]) for e in log) + patched
    # The parent's loop read the queue's whole list three times a
    # preemptor and the solver's cut once.
    assert examined < solves * (3 * surveyed // 2)


def test_the_benchmarks_metric_reads_this_counter():
    from benchmark.harness import readers
    path = os.path.join(ROOT, "benchmark", "layer_metrics",
                        "preempt_victims_examined.json")
    doc = json.load(open(path))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "preempt_victims_examined"]
    assert entry == {
        "name": "preempt_victims_examined", "unit": "jobs/cycle",
        "better": "lower", "source": "program_counter",
        "layer": "session and actions", "moves": "cycle_ms",
        "workloads": ["preempt98k-lws-32x4"]}
    assert {k: doc[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert doc["reader"] == {"kind": "counter_delta", "counter": EXAMINED}

    class Rec:
        counters = {}

    # A program without the counter (the parent): nothing, and no raise.
    assert readers.read_all([doc], {"records": [Rec]}) == {}
    ssn = build_session(fleet(2, 8), SchedulerConfig(
        max_victims_considered=6, scenario_prescreen_max=0))
    before = METRICS.counters[EXAMINED]
    run_action(ssn, "preempt")
    Rec.counters = {EXAMINED: METRICS.counters[EXAMINED] - before}
    out = readers.read_all([doc], {"records": [Rec]})
    assert out == {"preempt_victims_examined": {
        "value": Rec.counters[EXAMINED], "unit": "jobs/cycle"}}
    assert out["preempt_victims_examined"]["value"] > 0
