"""Every pass over the fleet says what it walked, ``snapshot`` names its
parts, and the benchmark's files read both (PR 55).

(a) ``fleet_walk_pod_visits_total{walk}``: each pass counts the pods of the
    PodGroups it read off their pods, exactly, by one increment a pass; a
    walk that does not run leaves its series at 0 and present; the survey
    spans and the stale-gang action's span carry the count beside the
    PodGroups the pass asked.  Since PR 56 a PodGroup keeps its pods'
    status census (``tests/test_pod_census.py``): the stale-gang pass and
    the reclaim and preempt surveys read every pod of a fleet nobody has
    counted, none of a fleet that stands as it was counted, and the pods
    of the PodGroups that changed in between.
(b) the ``snapshot:*`` parts nest under ``snapshot``, are disjoint but for
    ``snapshot:aggregates`` under ``snapshot:pack``, say on ``snapshot:pack``
    which pack it was, and open no histogram of their own.
(c) each metric file this PR adds agrees with its ``BENCHMARK.json`` entry,
    asked by name, and its reader returns the fixture's number, 0.0 where
    the walk did not run and nothing on a registry without the counter.

``trace_spans_total`` / ``trace_spans_dropped_total`` are held in
``tests/test_tracing.py``.
"""

import json
import os

import pytest

from kai_scheduler_tpu.actions.consolidation import \
    collect_consolidation_victims
from kai_scheduler_tpu.actions.preempt import survey_preempt_victims
from kai_scheduler_tpu.actions.reclaim import VictimStream
from kai_scheduler_tpu.api import PodStatus
from kai_scheduler_tpu.api.snapshot import survey_pods
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.scheduler import Scheduler
from kai_scheduler_tpu.utils.metrics import METRICS
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.fixtures import build_cluster, build_session, run_action

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POD_VISITS = "fleet_walk_pod_visits_total"
FLEET_WALKS = ("stale_gangs", "victim_survey", "pod_survey")


def series(walk: str) -> str:
    return f'{POD_VISITS}{{walk="{walk}"}}'


def read(walk: str) -> float:
    return METRICS.counters[series(walk)]


# -- the fixture --------------------------------------------------------------
# name -> (queue, preemptible, running pods, pending pods)
JOBS = {
    "victim-a": ("a0", True, 3, 0),
    "victim-b": ("a1", True, 2, 1),
    "fixed": ("a0", False, 2, 0),       # not preemptible: no survey reads it
    "waiting": ("a1", True, 0, 2),      # preemptible, nothing running
    "orphan": ("gone", True, 4, 0),     # its queue is not in the cluster
    "claim": ("b0", False, 0, 2),       # the pending gang
}
EVERY_POD = sum(run + wait for _q, _p, run, wait in JOBS.values())
# The pods of the PodGroups each pass reads off their pods, in a fleet
# nobody has counted.
READ_BY = {
    "stale_gangs": EVERY_POD,
    "pod_survey": EVERY_POD,
    # In a queue of the cluster and preemptible.
    "reclaim": 3 + 3 + 2,
    # The same, less the consolidator itself (not preemptible anyway).
    "consolidation": 3 + 3 + 2,
    # Preemptible, whatever its queue.
    "preempt": 3 + 3 + 2 + 4,
}


def fleet() -> dict:
    spec = {"nodes": {}, "jobs": {}, "queues": {
        "a": {"deserved": {"gpu": 1}}, "b": {"deserved": {"gpu": 16}},
        "a0": {"parent": "a", "deserved": {"gpu": 1}},
        "a1": {"parent": "a", "deserved": {"gpu": 1}},
        "b0": {"parent": "b", "deserved": {"gpu": 16}}}}
    for j, (name, (queue, preemptible, run, wait)) in enumerate(JOBS.items()):
        node = f"n{j}"
        if run:
            spec["nodes"][node] = {"gpu": run}
        spec["jobs"][name] = {
            "queue": queue, "preemptible": preemptible, "min_available": 1,
            "priority": 90 if name == "claim" else 10,
            "tasks": [{"gpu": 1, "status": "RUNNING", "node": node}] * run
            + [{"gpu": 1}] * wait}
    return spec


# The passes that read what a PodGroup keeps of its pods' statuses; the
# two others read every pod every time (``consolidation:victims`` wants
# every running pod's node and request, ``survey_pods`` its constraints).
READ_KEPT = ("preempt", "reclaim", "stale_gangs")


def forget(ssn) -> None:
    """A fleet nobody has counted: opening the session counted the
    PodGroups of its queues for the queue sums."""
    for pg in ssn.cluster.podgroups.values():
        pg.invalidate_caches()


def walk_of(ssn, which: str) -> None:
    """One pass of ``which`` over the session's fleet."""
    if which == "stale_gangs":
        run_action(ssn, "stalegangeviction")
    elif which == "pod_survey":
        survey_pods(ssn.cluster)
    elif which == "reclaim":
        VictimStream(ssn).close()
    elif which == "preempt":
        survey_preempt_victims(ssn)
    else:
        job = ssn.cluster.podgroups["claim"]
        collect_consolidation_victims(
            ssn, job, job.tasks_to_allocate(real_allocation=False))


WALK = {"stale_gangs": "stale_gangs", "pod_survey": "pod_survey",
        "reclaim": "victim_survey", "consolidation": "victim_survey",
        "preempt": "victim_survey"}


@pytest.fixture
def incs(monkeypatch):
    """Every ``METRICS.inc`` of the family, as (walk, by)."""
    calls = []
    real = METRICS.inc

    def inc(name, value=1.0, **labels):
        if name == POD_VISITS:
            calls.append((labels["walk"], value))
        return real(name, value, **labels)
    monkeypatch.setattr(METRICS, "inc", inc)
    return calls


# -- (a) what each pass counts ------------------------------------------------
@pytest.mark.parametrize("which", sorted(WALK))
def test_a_pass_counts_the_pods_it_read_by_one_increment(which, incs):
    ssn = build_session(fleet())
    forget(ssn)
    before = {w: read(w) for w in FLEET_WALKS}
    del incs[:]
    walk_of(ssn, which)
    walk = WALK[which]
    # One increment, for this walk alone, however many PodGroups.
    assert incs == [(walk, READ_BY[which])]
    for w in FLEET_WALKS:
        assert read(w) - before[w] == (READ_BY[which] if w == walk else 0), w


@pytest.mark.parametrize("which", sorted(WALK))
def test_a_second_pass_counts_what_changed(which, incs):
    """The first pass over a fleet nobody counted reads every pod; the
    next over the fleet as it stands reads none, and still says so by one
    increment; a pod that changes status in between has its PodGroup read
    again, and no other."""
    ssn = build_session(fleet())
    forget(ssn)
    walk = WALK[which]
    kept = which in READ_KEPT
    visits0 = read(walk)
    walk_of(ssn, which)
    assert read(walk) == visits0 + READ_BY[which]
    del incs[:]
    walk_of(ssn, which)
    again = 0 if kept else READ_BY[which]
    assert incs == [(walk, again)]
    victim = ssn.cluster.podgroups["victim-b"]
    waiting = next(t for t in victim.pods.values() if not t.node_name)
    victim.update_task_status(waiting, PodStatus.GATED)
    del incs[:]
    walk_of(ssn, which)
    assert incs == [(walk, len(victim.pods) if kept else READ_BY[which])]
    # A PodGroup no pass reads counts for nothing, changed or not.
    fixed = ssn.cluster.podgroups["fixed"]
    fixed.invalidate_caches()
    del incs[:]
    walk_of(ssn, which)
    assert incs == [(walk, {"stale_gangs": len(fixed.pods)}.get(
        which, again))]


@pytest.mark.parametrize("walk", FLEET_WALKS)
def test_a_walk_that_did_not_run_reads_zero_and_is_there(walk):
    """A session is enough to register the three series, at 0: a bare
    ``Session`` packs from scratch and surveys no pod."""
    METRICS.reset()
    assert series(walk) not in METRICS.counters
    build_session(fleet())
    assert read(walk) == 0


SURVEY_SPANS = ("reclaim:survey", "consolidation:victims", "preempt:survey")
SPANS = dict(zip(("reclaim", "consolidation", "preempt"), SURVEY_SPANS),
             stale_gangs="action:stalegangeviction")


@pytest.mark.parametrize("which", sorted(SPANS))
def test_the_open_span_carries_what_the_pass_counted(which):
    ssn = build_session(fleet())
    forget(ssn)
    TRACER.begin_cycle(1)
    try:
        with TRACER.span("above", kind="action") as above, \
                TRACER.span(SPANS[which], kind="action") as sp:
            walk_of(ssn, which)
    finally:
        TRACER.end_cycle()
    assert (sp.attrs["podgroups"], sp.attrs["pod_visits"]) \
        == (len(JOBS), READ_BY[which])
    assert "pod_visits" not in above.attrs
    # Outside a cycle, or under no span of that name, the pass counts and
    # stamps nothing.
    forget(ssn)
    before = read(WALK[which])
    walk_of(ssn, which)
    assert read(WALK[which]) - before == READ_BY[which]
    if which in READ_KEPT:
        # A pass that read nothing off the pods says 0, and not nothing.
        TRACER.begin_cycle(2)
        try:
            with TRACER.span(SPANS[which], kind="action") as sp:
                walk_of(ssn, which)
        finally:
            TRACER.end_cycle()
        assert (sp.attrs["podgroups"], sp.attrs["pod_visits"]) \
            == (len(JOBS), 0)
        assert read(WALK[which]) - before == READ_BY[which]


# -- one Scheduler over one cluster: the cycle's own spans --------------------
class Loop:
    """``Scheduler.run_once`` over one persistent cluster, the binds
    settled by hand as a client would."""

    def __init__(self, spec=None):
        self.cluster = build_cluster(spec or fleet())
        self.sched = Scheduler(lambda: self.cluster, SchedulerConfig())

    def cycle(self):
        from kai_scheduler_tpu.api import PodStatus
        ssn = self.sched.run_once()
        bound = dict(self.sched.cache.bound)
        self.sched.cache.bound.clear()
        ssn.cluster.bind_requests.clear()
        for pg in ssn.cluster.podgroups.values():
            for task in list(pg.pods.values()):
                if task.uid in bound:
                    pg.update_task_status(task, PodStatus.RUNNING)
        trace = TRACER.get_trace()
        assert trace.dropped_spans == 0
        return ssn, trace.spans


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]


def test_a_cycle_says_what_its_passes_walked():
    loop = Loop()
    for cycle in (1, 2, 3):
        before = {w: METRICS.counters.get(series(w), 0) for w in FLEET_WALKS}
        _ssn, spans = loop.cycle()
        moved = {w: read(w) - before[w] for w in FLEET_WALKS}
        (stale,) = named(spans, "action:stalegangeviction")
        assert (stale.attrs["podgroups"], stale.attrs["pod_visits"]) \
            == (len(JOBS), moved["stale_gangs"])
        # Every survey of the cycle is on the counter, and each asked
        # every PodGroup.  The reclaimer in ``b0`` is under its share: one
        # reclaim survey, by the first action that needed one.
        surveys = [s for s in spans if s.name in SURVEY_SPANS]
        assert len(named(surveys, "reclaim:survey")) == 1
        assert all(s.attrs["podgroups"] == len(JOBS) for s in surveys)
        assert moved["victim_survey"] \
            == sum(s.attrs["pod_visits"] for s in surveys)
        assert moved["pod_survey"] == EVERY_POD
        # The queue sums and the pending jobs' readiness counted the
        # PodGroups of the cluster's queues before any pass asked: a pass
        # reads what a statement of this cycle changed before it, and in
        # the first cycle the PodGroup whose queue is gone, which the
        # preempt survey is the first to ask.
        orphan = len(loop.cluster.podgroups["orphan"].pods)
        assert moved["victim_survey"] == (orphan if cycle == 1 else 0)
        assert moved["stale_gangs"] <= EVERY_POD - orphan
        if cycle == 3:
            # Nothing is pending any more and nothing changed.
            assert moved["stale_gangs"] == 0


# -- what the stale-gang pass decides, cold and warm ---------------------------
def gangs(now: float) -> dict:
    """Four gangs of three, minimum three: one below its minimum for 70 s
    at ``now`` = 1020 (grace 60 s), one for 30 s, one with a SUCCEEDED
    pod, one whole."""
    def job(started, *statuses):
        return {"queue": "q", "min_available": 3, "last_start_ts": started,
                "tasks": [{"gpu": 1, "status": s,
                           **({"node": "n0"} if s == "RUNNING" else {})}
                          for s in statuses]}
    return {"now": now, "nodes": {"n0": {"gpu": 16}}, "queues": {"q": {}},
            "jobs": {"broken": job(950.0, "RUNNING", "RUNNING", "FAILED"),
                     "young": job(990.0, "RUNNING", "RUNNING", "FAILED"),
                     "done": job(100.0, "RUNNING", "SUCCEEDED", "FAILED"),
                     "whole": job(100.0, "RUNNING", "RUNNING", "RUNNING")}}


@pytest.mark.parametrize("census", ["cold", "warm", "warm-then-failed"])
def test_a_gang_below_minimum_past_its_grace_is_evicted_whole(census):
    """On the first cycle, where every PodGroup is counted from its pods,
    and on a second where the counts are kept and only the clock moved (a
    kept verdict would say "inside its grace" for ever) or a pod failed
    through the door."""
    if census == "cold":
        loop = Loop(gangs(1020.0))
    else:
        loop = Loop(gangs(1000.0))
        loop.cycle()
        assert loop.sched.cache.evicted == []
        assert all(pg.uncounted_pods() == 0
                   for pg in loop.cluster.podgroups.values())
        loop.cluster.now = 1020.0
    want = {"broken-0", "broken-1"}
    if census == "warm-then-failed":
        whole = loop.cluster.podgroups["whole"]
        whole.update_task_status(whole.pods["whole-2"], PodStatus.FAILED)
        loop.cluster.invalidate_aggregates()      # the snapshot recounts it
        want |= {"whole-0", "whole-1"}
    before = read("stale_gangs")
    _ssn, spans = loop.cycle()
    assert set(loop.sched.cache.evicted) == want
    assert len(loop.sched.cache.evicted) == len(want)
    (stale,) = named(spans, "action:stalegangeviction")
    assert stale.attrs["pod_visits"] == read("stale_gangs") - before == 0
    statuses = {uid: {t.status.name for t in pg.pods.values()}
                for uid, pg in loop.cluster.podgroups.items()}
    assert statuses["broken"] == {"RELEASING", "FAILED"}
    assert statuses["young"] == {"RUNNING", "FAILED"}
    assert statuses["done"] == {"RUNNING", "SUCCEEDED", "FAILED"}
    # The evictions went through the door: the next question counts them.
    assert loop.cluster.podgroups["broken"].uncounted_pods() == 3
    assert not loop.cluster.podgroups["broken"].is_gang_satisfied()


# -- (b) the parts of ``snapshot`` --------------------------------------------
PARTS = ("snapshot:provider", "snapshot:survey", "snapshot:stamps",
         "snapshot:pack", "snapshot:fragmentation")


def parts_of(spans) -> tuple:
    (whole,) = named(spans, "snapshot")
    parts = [s for s in spans if s.kind == "snapshot_part"]
    return whole, parts


@pytest.mark.parametrize("cycle", ["full", "patched"])
def test_the_parts_nest_under_snapshot_and_do_not_overlap(cycle):
    loop = Loop()
    _ssn, spans = loop.cycle()
    if cycle == "patched":
        _ssn, spans = loop.cycle()
    whole, parts = parts_of(spans)
    assert sorted(s.name for s in parts) \
        == sorted(PARTS + ("snapshot:aggregates",))
    by_name = {s.name: s for s in parts}
    for name in PARTS:
        assert by_name[name].parent_id == whole.span_id, name
    assert by_name["snapshot:aggregates"].parent_id \
        == by_name["snapshot:pack"].span_id
    # In the order the work is called, each after the one before ended,
    # and all inside the whole: they cover it but for its own rest.
    top = sorted((by_name[n] for n in PARTS), key=lambda s: s.start_s)
    assert [s.name for s in top] == list(PARTS)
    for a, b in zip(top, top[1:]):
        assert a.start_s + a.duration_s <= b.start_s
    assert whole.start_s <= top[0].start_s
    assert top[-1].start_s + top[-1].duration_s \
        <= whole.start_s + whole.duration_s
    rest = whole.duration_s - sum(s.duration_s for s in top)
    assert 0.0 <= rest < whole.duration_s
    agg, pack = by_name["snapshot:aggregates"], by_name["snapshot:pack"]
    assert pack.start_s <= agg.start_s \
        and agg.start_s + agg.duration_s <= pack.start_s + pack.duration_s


@pytest.mark.parametrize("cycle,reason,full", [
    ("first", "no-previous-pack", True),
    ("patched", "", False),
    ("vocab", "vocab-change", True)])
def test_the_pack_part_says_which_pack_it_was(cycle, reason, full):
    loop = Loop()
    ssn, spans = loop.cycle()
    if cycle != "first":
        if cycle == "vocab":
            pod = next(iter(loop.cluster.podgroups["fixed"].pods.values()))
            pod.tolerations = {"spot"}
        ssn, spans = loop.cycle()
    whole, parts = parts_of(spans)
    (pack,) = [s for s in parts if s.name == "snapshot:pack"]
    assert pack.attrs["reason"] == reason
    assert pack.attrs["full_rebuild"] is full
    # What the whole span carries of the verdict, the part carries too.
    assert {k: whole.attrs[k] for k in ("full_rebuild", "reason",
                                        "changed_rows")} == pack.attrs
    assert ssn.pack_stats["changed_rows"] == pack.attrs["changed_rows"]


def test_the_parts_open_no_histogram_and_the_whole_keeps_its_own():
    METRICS.reset()
    loop = Loop()
    for n in (1, 2):
        loop.cycle()
        assert METRICS.histograms["cycle_span_snapshot_latency_ms"].n == n
    assert "cycle_span_snapshot_part_latency_ms" not in METRICS.histograms
    # By name the recorder still has them (``/debug/cycles``).
    totals = TRACER.get_trace().name_totals
    assert all(totals[name][0] == 1 for name in PARTS)


def test_the_cluster_arena_keeps_its_own_span():
    """A cache that brings a ``ClusterArena`` packs under
    ``snapshot_delta``: no survey, no stamps, no ``snapshot:pack``."""
    from kai_scheduler_tpu.framework.arena import ClusterArena
    from kai_scheduler_tpu.framework.session import InMemoryCache
    cache = InMemoryCache()
    cache.arena = ClusterArena()
    cluster = build_cluster(fleet())
    cache.arena.stamp(cluster)
    Scheduler(lambda: cluster, SchedulerConfig(), cache=cache).run_once()
    spans = TRACER.get_trace().spans
    assert len(named(spans, "snapshot_delta")) == 1
    assert {s.name for s in spans if s.kind == "snapshot_part"} == {
        "snapshot:provider", "snapshot:aggregates", "snapshot:fragmentation"}
    (agg,) = named(spans, "snapshot:aggregates")
    assert agg.parent_id == named(spans, "snapshot_delta")[0].span_id


# -- (c) the benchmark's files ------------------------------------------------
def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


CELLS = [w["name"] for w in bench()["workloads"]][:7]
WIDE = [c for c in CELLS if c != "tas65k-pytorchjob-16k"]
TAS = ["tas65k-pytorchjob-16k", "tasreclaim98k-pytorchjob-8x256"]
SA, SP = "session and actions", "snapshot and pack"


def counter(name: str) -> dict:
    return {"kind": "counter_delta", "counter": name}


def net(*match: str, minus=()) -> dict:
    """The spans' own time: what lies under them of ``minus`` and of the
    collector is taken off."""
    return {"kind": "span_self", "match": list(match),
            "minus": [*minus, "gc:*"]}


# name -> (unit, source, layer, reader, cells)
METRIC_FILES = {
    "stale_gang_ms": ("ms", "program_span", SA, net(
        "action:stalegangeviction"), CELLS),
    "stale_gang_pod_visits": ("pods/cycle", "program_counter", SA, counter(
        series("stale_gangs")), CELLS),
    "victim_survey_ms": ("ms", "program_span", SA, net(*SURVEY_SPANS), WIDE),
    "victim_survey_pod_visits": ("pods/cycle", "program_counter", SA, counter(
        series("victim_survey")), WIDE),
    "snapshot_provider_ms": ("ms", "program_span", SP, net(
        "snapshot:provider"), CELLS),
    "snapshot_survey_ms": ("ms", "program_span", SP, net(
        "snapshot:survey"), CELLS),
    "snapshot_stamps_ms": ("ms", "program_span", SP, net(
        "snapshot:stamps"), CELLS),
    "snapshot_pack_ms": ("ms", "program_span", SP, net(
        "snapshot:pack", minus=["snapshot:aggregates"]), CELLS),
    "snapshot_aggregates_ms": ("ms", "program_span", SP, net(
        "snapshot:aggregates"), CELLS),
    "snapshot_fragmentation_ms": ("ms", "program_span", SP, net(
        "snapshot:fragmentation"), CELLS),
    "pod_survey_visits": ("pods/cycle", "program_counter", SP, counter(
        series("pod_survey")), CELLS),
    "spans_recorded": ("spans/cycle", "program_counter", SA, counter(
        "trace_spans_total"), CELLS),
    "spans_dropped": ("spans/cycle", "program_counter", SA, counter(
        "trace_spans_dropped_total"), CELLS),
    "topology_trees_built": ("trees/cycle", "program_counter", SA, counter(
        "topology_tree_built_total"), TAS),
    "topology_trees_reused": ("trees/cycle", "program_counter", SA, counter(
        "topology_tree_reused_total"), TAS),
}


def metric(name: str) -> tuple:
    """(the entry of ``BENCHMARK.json``, the file), asked by name: later
    PRs append their metrics after these."""
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == name]
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as fh:
        return entry, json.load(fh)


@pytest.mark.parametrize("name", sorted(METRIC_FILES))
def test_the_metric_file_is_its_entry_of_the_benchmark(name):
    unit, source, layer, reader, cells = METRIC_FILES[name]
    entry, doc = metric(name)
    assert entry["workloads"][:len(cells)] == cells
    assert doc.pop("reader") == reader
    assert doc == {k: v for k, v in entry.items() if k != "workloads"}
    assert doc == {"name": name, "unit": unit, "better": "lower",
                   "source": source, "layer": layer, "moves": "cycle_ms"}


class Rec:
    """One cycle as the harness's ``loop.run_once`` records it."""

    def __init__(self, run_once, wanted):
        before = {c: METRICS.counters.get(c, 0.0) for c in wanted}
        run_once()
        self.counters = {c: METRICS.counters[c] - before[c]
                         for c in wanted if c in METRICS.counters}
        self.spans = [(s.name, s.kind, s.span_id, s.parent_id, s.start_s,
                       s.duration_s) for s in TRACER.get_trace().spans]


def read_all(names, recs) -> dict:
    from benchmark.harness import readers
    docs = [metric(n)[1] for n in names]
    return {n: v["value"] for n, v in readers.read_all(
        docs, {"records": recs}).items()}


def test_the_readers_return_what_the_cycle_walked_and_kept():
    from benchmark.harness import readers
    names = sorted(METRIC_FILES)
    wanted = readers.counters_wanted([metric(n)[1] for n in names])
    METRICS.reset()
    loop = Loop()
    rec = Rec(loop.cycle, wanted)
    got = read_all(names, [rec])
    assert set(got) == set(names)
    # The first cycle: the snapshot counted the PodGroups of the cluster's
    # queues before a pass asked, and ``orphan`` was the preempt survey's.
    assert got["stale_gang_pod_visits"] == 0.0
    assert got["pod_survey_visits"] == EVERY_POD
    assert got["victim_survey_pod_visits"] == 4.0
    trace = TRACER.get_trace()
    assert got["spans_recorded"] == len(trace.spans) \
        == sum(n for n, _s in trace.name_totals.values())
    assert got["spans_dropped"] == 0.0
    # No topology in this fleet: neither tree built nor reused, and both
    # there at 0.
    assert (got["topology_trees_built"], got["topology_trees_reused"]) \
        == (0.0, 0.0)
    by_name = {s.name: s for s in trace.spans}
    assert got["stale_gang_ms"] == pytest.approx(
        1e3 * by_name["action:stalegangeviction"].duration_s)
    # Whichever actions needed a survey this cycle: the reclaimer did.
    surveys = [s for s in trace.spans if s.name in SURVEY_SPANS]
    assert "reclaim:survey" in {s.name for s in surveys}
    assert got["victim_survey_ms"] == pytest.approx(
        1e3 * sum(s.duration_s for s in surveys))
    for part in ("provider", "survey", "stamps", "aggregates",
                 "fragmentation"):
        assert got[f"snapshot_{part}_ms"] == pytest.approx(
            1e3 * by_name[f"snapshot:{part}"].duration_s), part
    assert got["snapshot_pack_ms"] == pytest.approx(
        1e3 * (by_name["snapshot:pack"].duration_s
               - by_name["snapshot:aggregates"].duration_s))
    # ``snapshot_ms`` still reads the whole span and nothing else: it
    # matches the name exactly.
    assert read_all(["snapshot_ms"], [rec]) == {"snapshot_ms": pytest.approx(
        1e3 * by_name["snapshot"].duration_s)}


NET = {n: v[3]["match"] for n, v in METRIC_FILES.items()
       if v[3]["kind"] == "span_self"}


@pytest.mark.parametrize("name", sorted(NET))
def test_a_collection_under_the_span_is_not_the_walks_time(name):
    """A full collection comes once in some 37 cycles and costs a second:
    where it falls inside a walk it is the collector's, and a collection
    elsewhere in the cycle takes nothing off."""
    class Cycle:
        counters = {}
        spans = [("cycle", "cycle", "c", None, 0.0, 9.0),
                 ("gc:full", "gc", "g0", "c", 8.0, 0.75)]
    for i, match in enumerate(NET[name]):
        Cycle.spans.append((match, "k", f"m{i}", "c", float(i), 0.5))
    Cycle.spans.append(("gc:full", "gc", "g1", "m0", 0.125, 0.25))
    assert read_all([name], [Cycle]) == {
        name: pytest.approx(1e3 * (0.5 * len(NET[name]) - 0.25))}


def test_a_survey_that_did_not_run_reads_zero():
    """Nothing pending: no reclaimer, consolidator or preemptor asks for a
    survey.  The count reads 0.0 and the time, with no span to read,
    nothing."""
    from benchmark.harness import readers
    spec = fleet()
    del spec["jobs"]["claim"], spec["jobs"]["waiting"]
    spec["jobs"]["victim-b"]["tasks"].pop()
    names = ["victim_survey_pod_visits", "victim_survey_ms",
             "stale_gang_pod_visits"]
    wanted = readers.counters_wanted([metric(n)[1] for n in names])
    METRICS.reset()
    loop = Loop(spec)
    # No queue sum reads ``orphan`` and no survey ran: the stale-gang pass
    # is the first to ask it, and the last, while it stands as it is.
    assert read_all(names, [Rec(loop.cycle, wanted)]) == {
        "victim_survey_pod_visits": 0.0, "stale_gang_pod_visits": 4}
    assert read_all(names, [Rec(loop.cycle, wanted)]) == {
        "victim_survey_pod_visits": 0.0, "stale_gang_pod_visits": 0.0}


@pytest.mark.parametrize("name", sorted(METRIC_FILES))
def test_a_program_without_the_counter_or_span_leaves_the_metric_out(name):
    """The parent: its registry has no such series and its trace no such
    part (``action:stalegangeviction`` and the surveys it had)."""
    class Parent:
        counters = {"queue_aggregate_pod_visits_total": 3.0}
        spans = [("cycle", "cycle", "s1", None, 0.0, 1.0),
                 ("snapshot", "snapshot", "s2", "s1", 0.0, 0.5)]
    assert read_all([name], [Parent]) == {}
