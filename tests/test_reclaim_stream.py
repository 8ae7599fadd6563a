"""The reclaim survey is a stream read as far as the solver reads (PR 52).

The reclaim action used to order every victim of the cycle through a heap
and drain the whole of it to hand its solver ``max_victims_considered`` of
them.  It keeps a ``VictimStream`` now: one pass, a leaf's jobs ordered in
bulk, and the order popped on demand.  What reaches the solver has to be
what reached it before, so the cases here hold the stream to the parent's
drain, kept below as ``parent_survey`` over ``PushedOrder`` (the parent's
``PriorityQueue``, filled one push a job, under the node heap that did not
change):

(a) the stream read to its end, and to any point, is the parent's list;
(b) a reclaimer's candidates are ``filter(whole list less own
    queue)[:cap]`` whatever the filter drops and wherever the cap lies;
(c) ``reclaim_victims_examined_total`` and ``filtered`` on ``reclaim:job``
    count what was read and what was dropped among it;
(d) two reclaimers a cycle, the first committing;
(e) ``JobsOrderByQueues`` built in bulk pops what one built by pushes pops,
    on random queue trees;
(f) the registered reclaim filters judge each victim alone, in order;
(g) since PR 56 the pass and the order's elastic key read what a PodGroup
    keeps of its pods' statuses: the stream over kept counts, over counts
    a statement dropped and over none is one order, and the oracle's pass
    walks the pods itself.
"""

import heapq
import json
import os

import numpy as np
import pytest

from kai_scheduler_tpu.actions import reclaim, solvers
from kai_scheduler_tpu.actions.preempt import FILTER_CHUNK
from kai_scheduler_tpu.actions.reclaim import (EXAMINED, VictimStream,
                                               survey_reclaim_victims)
from kai_scheduler_tpu.actions.utils import (INFINITE, JobsOrderByQueues,
                                             PriorityQueue, _Rev)
from kai_scheduler_tpu.api import PodStatus
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.utils.metrics import METRICS
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.fixtures import build_session, run_action

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the parent's build and drain, the oracle ---------------------------------
class ParentPriorityQueue(PriorityQueue):
    """``PriorityQueue`` as the parent had it: empty at first, one entry
    and one ``heappush`` an item, a victim's key inside a ``_Rev``."""

    def __init__(self, less, max_size=INFINITE, key=None):
        super().__init__(less, max_size, key)

    def push(self, item) -> None:
        if self.key is not None:
            entry = self._KeyedEntry(item, self.key(item),
                                     next(self._counter))
        else:
            entry = self._Entry(item, self.less, next(self._counter))
        if self.max_size != INFINITE and len(self._items) >= self.max_size:
            worst = max(self._items)
            if entry < worst:
                self._items.remove(worst)
                heapq.heapify(self._items)
                heapq.heappush(self._items, entry)
            return
        heapq.heappush(self._items, entry)


class PushedOrder(JobsOrderByQueues):
    """``JobsOrderByQueues`` as the parent built it: every job pushed on
    its leaf's heap, then each node attached once."""

    def __init__(self, ssn, jobs, max_jobs_per_queue=INFINITE,
                 victim_mode=False):
        super().__init__(ssn, [], max_jobs_per_queue,
                         victim_mode=victim_mode)
        if self._job_key is not None and victim_mode:
            self._job_key = lambda j: _Rev(ssn.job_sort_key(j))
        for job in jobs:
            self._leaf(job.queue_id).jobs.push(job)
        for node in list(self._nodes.values()):
            if node.live():
                self._attach(node)

    def _leaf(self, qid, jobs=()):
        node = self._nodes.get(qid)
        if node is None:
            node = super()._leaf(qid)
            node.jobs = ParentPriorityQueue(node.jobs.less, self._max_jobs,
                                            key=self._job_key)
        return node


def drained(order) -> list:
    out = []
    while not order.empty():
        job = order.pop_next_job()
        if job is None:
            break
        out.append(job)
        order.requeue_queue(job.queue_id)
    return out


def parent_survey(ssn) -> list:
    """``survey_reclaim_victims`` as the parent had it: the pass, the
    order and the whole of its drain."""
    victims = [pg for pg in ssn.cluster.podgroups.values()
               if pg.queue_id in ssn.cluster.queues and pg.is_preemptible()
               and any(t.is_active_allocated() for t in pg.pods.values())]
    return drained(PushedOrder(ssn, victims, victim_mode=True))


# -- fleets -------------------------------------------------------------------
# shape -> department -> its leaves; the reclaimers wait in ``b0`` / ``b1``.
SHAPES = {
    "one-leaf": {"a": ["a0"], "b": ["b0", "b1"]},
    "one-department": {"a": ["a0", "a1", "a2"], "b": ["b0", "b1"]},
    "departments": {"a": ["a0", "a1"], "c": ["c0", "c1"],
                    "b": ["b0", "b1"]},
}
# shape -> the leaves that hold victims (the reclaimer's own among them).
HOLDERS = {"one-leaf": ["a0"], "one-department": ["a0", "a1", "a2"],
           "departments": ["a0", "a1", "c0", "c1", "b0", "b1"]}
MODES = ("keys", "job-comparator", "comparators")


def fleet(shape: str, seed: int, jobs: int = 48) -> dict:
    """A full fleet, one node a job: running jobs of three priorities and
    creation times that tie, gangs at their minimum, elastic ones above
    it, broken ones below it (still holding a pod), some not preemptible
    and some with no pod running (neither is a victim); and two pending
    gangs, ``claim0`` in ``b0`` and ``claim1`` in ``b1``."""
    rng = np.random.default_rng(seed)
    tree = SHAPES[shape]
    queues = {}
    for dept, leaves in tree.items():
        queues[dept] = {"deserved": {"gpu": 256 if dept == "b" else 1}}
        for i, leaf in enumerate(leaves):
            queues[leaf] = {"parent": dept, "creation_ts": float(i),
                            "deserved": {"gpu": 128 if dept == "b" else 1}}
    spec = {"nodes": {}, "queues": queues, "jobs": {}}

    def running(name, count, node):
        return [{"name": f"{name}-{i}", "gpu": 1, "cpu": "1",
                 "status": "RUNNING", "node": node} for i in range(count)]

    for j in range(jobs):
        name, node = f"j{j:03d}", f"n{j:03d}"
        job = {"queue": str(rng.choice(HOLDERS[shape])),
               "priority": int(rng.choice([10, 50, 90])),
               "creation_ts": float(rng.integers(0, 9))}
        kind = rng.choice(["solid", "elastic", "broken", "single", "fixed",
                           "waiting"], p=[.3, .25, .1, .2, .075, .075])
        pods = {"solid": 2, "elastic": 4, "broken": 1, "single": 1,
                "fixed": 2, "waiting": 0}[kind]
        job["min_available"] = {"elastic": int(rng.integers(1, 4)),
                                "single": 1}.get(kind, 2)
        job["tasks"] = running(name, pods, node)
        if kind == "fixed":
            job["preemptible"] = False
        if kind == "waiting":
            job["tasks"] = [{"gpu": 1, "cpu": "1"}] * 2
        if pods:
            spec["nodes"][node] = {"gpu": pods}
        spec["jobs"][name] = job
    for i in range(2):
        spec["jobs"][f"claim{i}"] = {
            "queue": f"b{i}", "priority": 50, "preemptible": False,
            "creation_ts": 100.0 + i, "min_available": 2,
            "tasks": [{"gpu": 2, "cpu": "1"}, {"gpu": 2, "cpu": "1"}]}
    return spec


def session(spec: dict, mode: str, **config):
    ssn = build_session(spec, SchedulerConfig(**config))
    if mode != "keys":
        # An order fn with no key: the leaves' heaps compare jobs pairwise.
        ssn.add_job_order_fn(
            lambda l, r: (len(l.pods) > len(r.pods))
            - (len(l.pods) < len(r.pods)))
    if mode == "comparators":
        # And a second queue order fn: so do the nodes' heaps.
        ssn.queue_order_fns.append(lambda l, r, lj, rj, lv, rv: 0)
    return ssn


def uids(jobs) -> list:
    return [pg.uid for pg in jobs]


# -- (a) the stream is the parent's list --------------------------------------
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("seed", (1, 2, 3000000019))
def test_the_stream_read_to_its_end_is_the_parents_drain(seed, shape, mode):
    ssn = session(fleet(shape, seed), mode)
    want = parent_survey(ssn)
    assert len(want) >= 30
    assert {pg.queue_id for pg in want} == set(HOLDERS[shape])
    # Victims below their minimum (a pod of a broken gang) and above it.
    assert any(pg.is_stale() for pg in want)
    assert any(pg.is_elastic() for pg in want)
    assert len({pg.priority for pg in want}) == 3
    stream = VictimStream(ssn)
    assert stream.surveyed == len(want) and stream.read == []
    assert uids(stream.drain()) == uids(want)
    assert uids(survey_reclaim_victims(ssn)) == uids(want)
    # A pop depends on the pops before it alone: any partial read is the
    # head of the whole.
    for n in (1, 7, len(want) // 2, len(want) + 5):
        stream = VictimStream(ssn)
        stream._read_to(n)
        assert uids(stream.read) == uids(want[:n])


def survey_visits() -> float:
    return METRICS.counters[
        'fleet_walk_pod_visits_total{walk="victim_survey"}']


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("seed", (4, 5))
def test_the_stream_over_kept_counts_is_the_stream_over_none(seed, shape):
    """The pass and the order's elastic key read what a PodGroup keeps of
    its pods' statuses (PR 56).  The order is the same over a fleet whose
    counts are all kept, over one where a statement took pods from some
    victims since (gangs pushed below their minimum, jobs taken whole),
    and over one nobody has counted; and ``fleet_walk_pod_visits_total``
    moves by the pods of the PodGroups the pass found uncounted."""
    ssn = session(fleet(shape, seed), "keys")
    asked = [pg for pg in ssn.cluster.podgroups.values()
             if pg.queue_id in ssn.cluster.queues and pg.is_preemptible()]
    # The session's opening counted them for the queue sums.
    assert all(pg.uncounted_pods() == 0 for pg in asked)
    before = survey_visits()
    first = uids(survey_reclaim_victims(ssn))
    assert survey_visits() == before
    assert first == uids(parent_survey(ssn))
    # A statement evicts a pod of every third victim and every pod of
    # every seventh, and stands.
    stmt = ssn.statement()
    touched = {}
    for k, uid in enumerate(first):
        pg = ssn.cluster.podgroups[uid]
        if k % 7 == 0 or k % 3 == 0:
            for task in list(pg.pods.values())[:None if k % 7 == 0 else 1]:
                stmt.evict(task)
            touched[uid] = len(pg.pods)
    taken = {uid for uid in touched if not any(
        t.is_active_allocated()
        for t in ssn.cluster.podgroups[uid].pods.values())}
    assert taken and taken < set(touched)
    before = survey_visits()
    warm = uids(survey_reclaim_victims(ssn))
    assert survey_visits() - before == sum(touched.values())
    assert set(warm) == set(first) - taken and warm != first[:len(warm)]
    want = uids(parent_survey(ssn))
    for pg in ssn.cluster.podgroups.values():
        pg.invalidate_caches()
    before = survey_visits()
    cold = uids(survey_reclaim_victims(ssn))
    assert survey_visits() - before == sum(len(pg.pods) for pg in asked)
    assert warm == cold == want


def test_a_leaf_in_key_mode_is_the_descending_sort_of_its_keys():
    ssn = session(fleet("one-leaf", 5), "keys")
    want = parent_survey(ssn)
    assert uids(want) == uids(sorted(want, key=ssn.job_sort_key,
                                     reverse=True))
    assert uids(VictimStream(ssn).drain()) == uids(want)


# -- (b) a reclaimer's candidates ---------------------------------------------
CAP = 8


def dropped_by(pattern: str, others: list) -> set:
    if pattern == "none":
        return set()
    if pattern == "every-second":
        return {pg.uid for pg in others[::2]}
    if pattern == "long-run-at-the-head":
        return {pg.uid for pg in others[:CAP + 2 * FILTER_CHUNK + 5]}
    assert pattern == "all"
    return {pg.uid for pg in others}


def dropping(ssn, dropped: set) -> None:
    ssn.reclaim_victim_filters.append(
        lambda reclaimer, victims: [pg for pg in victims
                                    if pg.uid not in dropped])


def expected_read(eager: list, own: str, dropped: set, cap: int) -> int:
    """How far the stream is read: the cap's worth, then ``max(cap - have,
    FILTER_CHUNK)`` at a time while the filters left fewer than the cap."""
    pos = have = 0
    while have < cap and pos < len(eager):
        step = cap if pos == 0 else max(cap - have, FILTER_CHUNK)
        chunk = eager[pos:pos + step]
        pos += len(chunk)
        have += sum(pg.queue_id != own and pg.uid not in dropped
                    for pg in chunk)
    return pos


@pytest.mark.parametrize("cap", ("under", "at", "over"))
@pytest.mark.parametrize("pattern", ("none", "every-second",
                                     "long-run-at-the-head", "all"))
@pytest.mark.parametrize("shape, mode", [
    ("one-leaf", "keys"), ("one-department", "job-comparator"),
    ("departments", "keys"), ("departments", "comparators")])
def test_the_candidates_are_the_filtered_lists_head(shape, mode, pattern,
                                                    cap):
    ssn = session(fleet(shape, 7, jobs=300), mode)
    claimer = ssn.cluster.podgroups["claim0"]
    eager = parent_survey(ssn)
    others = [pg for pg in eager if pg.queue_id != claimer.queue_id]
    assert len(others) > CAP + 3 * FILTER_CHUNK
    assert (len(others) < len(eager)) == (shape == "departments")
    dropped = dropped_by(pattern, others)
    dropping(ssn, dropped)
    ssn.config.max_victims_considered = cap = {
        "under": CAP, "at": max(len(others) - len(dropped), 1),
        "over": len(others) + 50}[cap]
    want = ssn.filter_reclaim_victims(claimer, others)
    assert uids(want) == [u for u in uids(others) if u not in dropped]
    stream = VictimStream(ssn)
    before = METRICS.counters[EXAMINED]
    victims, admitted, filtered = stream.candidates(ssn, claimer)
    assert uids(victims) == uids(want[:cap])
    read = expected_read(eager, claimer.queue_id, dropped, cap)
    assert METRICS.counters[EXAMINED] - before == read == len(stream.read)
    assert uids(stream.read) == uids(eager[:read])
    among = [pg for pg in eager[:read] if pg.queue_id != claimer.queue_id]
    assert filtered == sum(pg.uid in dropped for pg in among)
    assert admitted == len(among) - filtered >= len(victims)
    if pattern == "none" and cap == CAP and shape != "departments":
        assert read == CAP      # the cap's worth and nothing more
    if cap > len(want) or pattern == "all":
        assert read == len(eager)   # fewer than the cap: all are read
    # A second reclaimer reads the kept head again and pops no further
    # than it must.
    again, _, _ = stream.candidates(ssn, claimer)
    assert uids(again) == uids(victims) and len(stream.read) == read
    assert METRICS.counters[EXAMINED] - before == 2 * read


# -- (c) the counter and the span through the action --------------------------
def recording(log: list, solve=None):
    """``solve_job`` that writes down what it was handed; with no solver
    behind it, it fails every reclaimer and commits nothing."""
    def handed(ssn, job, victims, validate, action):
        log.append({"job": job.uid, "victims": uids(victims)})
        if solve is None:
            return solvers.SolverResult(False)
        result = solve(ssn, job, victims, validate, action)
        log[-1].update(solved=result.success,
                       took=list(result.evicted_jobs))
        return result
    return handed


def spans_of(ssn, action: str = "reclaim"):
    TRACER.begin_cycle(1)
    run_action(ssn, action)
    return TRACER.end_cycle().spans


@pytest.mark.parametrize("pattern", ("none", "every-second",
                                     "long-run-at-the-head", "all"))
@pytest.mark.parametrize("shape, mode", [
    ("one-leaf", "keys"), ("departments", "job-comparator")])
def test_the_action_counts_what_it_read_and_what_was_dropped_among_it(
        shape, mode, pattern, monkeypatch):
    ssn = session(fleet(shape, 11, jobs=300), mode,
                  max_victims_considered=CAP)
    eager = parent_survey(ssn)
    log = []
    monkeypatch.setattr(reclaim, "solve_job", recording(log))
    dropped = dropped_by(pattern, [pg for pg in eager
                                   if pg.queue_id != "b0"])
    dropping(ssn, dropped)
    before = METRICS.counters[EXAMINED]
    spans = spans_of(ssn)
    (survey,) = [s for s in spans if s.name == "reclaim:survey"]
    assert survey.attrs["victims"] == len(eager)
    jobs = [s for s in spans if s.name == "reclaim:job"]
    # Both gangs pass their gates, each in a queue of its own (and with
    # them whatever else waits in a queue under its share).
    assert {"claim0", "claim1"} <= {s.attrs["job"] for s in jobs}
    read, handed = 0, []
    for span in jobs:
        own = span.attrs["queue"]
        n = expected_read(eager, own, dropped, CAP)
        among = [pg for pg in eager[:n] if pg.queue_id != own]
        assert span.attrs["filtered"] == sum(pg.uid in dropped
                                             for pg in among)
        assert span.attrs["victims"] == len(among) - span.attrs["filtered"]
        read += n
        handed.append([pg.uid for pg in eager if pg.queue_id != own
                       and pg.uid not in dropped][:CAP])
    assert METRICS.counters[EXAMINED] - before == read
    # No victim left, no solve.
    assert [e["victims"] for e in log] == [v for v in handed if v]
    if pattern == "all":
        assert "claim0" not in [e["job"] for e in log]


def test_the_counter_is_there_at_zero_when_a_session_opens():
    METRICS.reset()
    ssn = build_session({"nodes": {"n0": {"gpu": 1}}})
    assert METRICS.counters[EXAMINED] == 0.0 and EXAMINED in METRICS.counters
    run_action(ssn, "reclaim")      # nothing pending: no survey, no read
    assert METRICS.counters[EXAMINED] == 0.0


def test_the_benchmark_reads_the_counter():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # By name: a later PR's entries come after this one.
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "reclaim_victims_examined"]
    metric = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", entry["name"] + ".json")))
    assert entry["name"] == metric["name"] == "reclaim_victims_examined"
    assert metric["reader"] == {"kind": "counter_delta", "counter": EXAMINED}
    # The three reclaim cells it was added for, and those that joined.
    assert entry["workloads"][:3] == ["ns98k-reclaim-wide",
                                      "spread98k-pytorchjob-256",
                                      "pools98k-pytorchjob-256"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == metric[key]
    assert (metric["unit"], metric["better"], metric["source"],
            metric["layer"], metric["moves"]) == (
        "jobs/cycle", "lower", "program_counter", "session and actions",
        "cycle_ms")


# -- (d) two reclaimers a cycle, the first committing -------------------------
def weakest_first(spec: dict, queue: str) -> None:
    """Two jobs at the very head of ``queue``'s victims: ``weak-solid``, a
    gang of two that a reclaimer takes whole, then ``weak-elastic``, four
    pods over a minimum of two, whose surplus it takes."""
    for name, priority, pods, minimum in (("weak-solid", 1, 2, 2),
                                          ("weak-elastic", 2, 4, 2)):
        spec["nodes"][f"n-{name}"] = {"gpu": pods}
        spec["jobs"][name] = {
            "queue": queue, "priority": priority, "creation_ts": 99.0,
            "min_available": minimum,
            "tasks": [{"name": f"{name}-{i}", "gpu": 1, "cpu": "1",
                       "status": "RUNNING", "node": f"n-{name}"}
                      for i in range(pods)]}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("shape", SHAPES)
def test_between_two_reclaimers_the_head_is_kept_and_nothing_comes_twice(
        shape, mode):
    spec = fleet(shape, 13, jobs=120)
    # The leaf that yields the cycle's first victim gets the two weakest.
    weakest_first(spec, parent_survey(session(spec, mode))[0].queue_id)
    ssn = session(spec, mode, max_victims_considered=6)
    pgs = ssn.cluster.podgroups
    eager = parent_survey(ssn)
    stream = VictimStream(ssn)
    first, _, _ = stream.candidates(ssn, pgs["claim0"])
    assert uids(first) == uids(
        [pg for pg in eager if pg.queue_id != "b0"][:6])
    assert first[0].uid == "weak-solid"
    head = list(stream.read)
    result = solvers.solve_job(ssn, pgs["claim0"], first,
                               ssn.validate_reclaim_scenario, "reclaim")
    assert result.success
    gone = {u for u in result.evicted_jobs
            if pgs[u].num_active_allocated() == 0}
    shed = set(result.evicted_jobs) - gone
    assert "weak-solid" in gone
    if shape == "one-leaf":
        # The gang went whole and the elastic job behind it shed its
        # surplus: it stays a candidate with its core gang.
        assert uids(first[:2]) == ["weak-solid", "weak-elastic"]
        assert (gone, shed) == ({"weak-solid"}, {"weak-elastic"})
        assert pgs["weak-elastic"].num_active_allocated() == 2
    stream.committed(ssn, result.evicted_jobs)
    # What was read stays read, in its order, less the jobs taken whole.
    kept_head = [u for u in uids(head) if u not in gone]
    assert uids(stream.read) == kept_head and shed <= set(kept_head)
    # A job of the unread part loses every pod before the stream reaches
    # it: it is never yielded.
    late = eager[len(eager) // 2]
    assert late not in stream.read
    for task in late.pods.values():
        late.update_task_status(task, PodStatus.RELEASING)
    gone.add(late.uid)
    second, _, _ = stream.candidates(ssn, pgs["claim1"])
    assert uids(stream.read[:len(kept_head)]) == kept_head
    assert uids(second) == uids(
        [pg for pg in stream.read if pg.queue_id != "b1"][:6])
    assert len(second) == 6 and not gone & set(uids(second))
    whole = stream.drain()
    assert uids(whole[:len(kept_head)]) == kept_head
    # Every other victim once, none twice, none of the gone.
    assert len(set(uids(whole))) == len(whole)
    assert set(uids(whole)) == set(uids(eager)) - gone
    # Inside one leaf the build's order stands, whatever the queues' keys
    # did after the commit.
    for leaf in HOLDERS[shape]:
        assert [u for u in uids(whole) if pgs[u].queue_id == leaf] == [
            pg.uid for pg in eager
            if pg.queue_id == leaf and pg.uid not in gone]
    if shape == "one-leaf":
        assert uids(whole) == [u for u in uids(eager) if u not in gone]


@pytest.mark.parametrize("mode", ("keys", "job-comparator"))
def test_the_action_hands_its_second_reclaimer_the_kept_head(mode,
                                                             monkeypatch):
    spec = fleet("one-leaf", 17, jobs=120)
    weakest_first(spec, "a0")
    ssn = session(spec, mode, max_victims_considered=6)
    eager = parent_survey(ssn)
    log = []
    monkeypatch.setattr(reclaim, "solve_job",
                        recording(log, solvers.solve_job))
    before = METRICS.counters[EXAMINED]
    spans = spans_of(ssn)
    assert [s.name for s in spans].count("reclaim:survey") == 1
    assert [e["job"] for e in log] == ["claim0", "claim1"]
    assert log[0]["victims"] == uids(eager[:6]) and log[0]["solved"]
    assert sorted(log[0]["took"]) == ["weak-elastic", "weak-solid"]
    # One leaf: the second reclaimer's list is the whole list less the job
    # taken whole, to the cap; the stream was popped once more for it (the
    # kept head is five, the cap's worth is six).
    assert log[1]["victims"] == [u for u in uids(eager)
                                 if u != "weak-solid"][:6]
    assert log[1]["victims"][0] == "weak-elastic" and log[1]["solved"]
    assert METRICS.counters[EXAMINED] - before == 6 + 6


# -- (e) the bulk build pops what the pushes popped ---------------------------
def random_tree(seed: int) -> dict:
    """A random queue tree, one to three levels deep, under running and
    pending jobs of mixed priorities whose creation times tie."""
    rng = np.random.default_rng(seed)
    queues, leaves = {}, []

    def grow(name, parent, depth):
        queues[name] = {"parent": parent,
                        "priority": int(rng.integers(0, 2)),
                        "creation_ts": float(rng.integers(0, 3)),
                        "deserved": {"gpu": int(rng.integers(1, 9))}}
        if depth < 3 and rng.random() < 0.6:
            for i in range(int(rng.integers(1, 4))):
                grow(f"{name}.{i}", name, depth + 1)
        else:
            leaves.append(name)

    for d in range(int(rng.integers(1, 5))):
        grow(f"q{d}", None, 1)
    spec = {"nodes": {}, "queues": queues, "jobs": {}}
    for j in range(int(rng.integers(20, 90))):
        name = f"j{j:03d}"
        pods = int(rng.integers(1, 4))
        runs = rng.random() < 0.7
        if runs:
            spec["nodes"][f"n{j:03d}"] = {"gpu": pods}
        spec["jobs"][name] = {
            "queue": str(rng.choice(leaves)),
            "priority": int(rng.choice([10, 50, 90])),
            "creation_ts": float(rng.integers(0, 6)),
            "min_available": int(rng.integers(1, pods + 1)),
            "tasks": [dict({"gpu": 1, "cpu": "1"},
                           **({"status": "RUNNING", "node": f"n{j:03d}"}
                              if runs else {}))
                      for _ in range(pods)]}
    return spec


@pytest.mark.parametrize("victims", (False, True), ids=("jobs", "victims"))
@pytest.mark.parametrize("depth", (INFINITE, 1, 3),
                         ids=("all", "depth1", "depth3"))
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", range(6))
def test_the_bulk_build_pops_what_the_pushes_popped(seed, mode, depth,
                                                    victims):
    ssn = session(random_tree(seed), mode)
    jobs = list(ssn.cluster.podgroups.values())
    bulk = JobsOrderByQueues(ssn, jobs, depth, victim_mode=victims)
    pushed = PushedOrder(ssn, jobs, depth, victim_mode=victims)
    assert (bulk._job_key is None) == (mode != "keys")
    assert (bulk._queue_key is None) == (mode == "comparators")
    want = drained(pushed)
    got = drained(bulk)
    assert uids(got) == uids(want)
    leaves = {pg.queue_id for pg in jobs}
    if depth == INFINITE:
        assert sorted(uids(got)) == sorted(uids(jobs))
    else:
        assert len(got) == sum(
            min(depth, sum(pg.queue_id == leaf for pg in jobs))
            for leaf in leaves) < len(jobs)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", range(4))
def test_a_job_pushed_after_the_bulk_build_finds_its_place(seed, mode):
    """``push_job`` (an elastic job's next chunk) on heaps that were built
    sorted: the pops stay those of the pushed build."""
    ssn = session(random_tree(100 + seed), mode)
    jobs = list(ssn.cluster.podgroups.values())
    late, jobs = jobs[::5], [pg for i, pg in enumerate(jobs) if i % 5]
    orders = [JobsOrderByQueues(ssn, jobs), PushedOrder(ssn, jobs)]
    popped = [[], []]
    for out, order in zip(popped, orders):
        for job in late:
            first = order.pop_next_job()
            out.append(first)
            order.push_job(job)
            order.requeue_queue(first.queue_id)
        out.extend(drained(order))
    assert uids(popped[0]) == uids(popped[1])
    assert len(popped[0]) == len(jobs) + len(late)


@pytest.mark.parametrize("largest_first", (False, True))
@pytest.mark.parametrize("max_size", (INFINITE, 5))
def test_a_priority_queue_with_items_is_one_of_pushes(max_size,
                                                      largest_first):
    rng = np.random.default_rng(3)
    items = [(int(k), i) for i, k in enumerate(rng.integers(0, 12, 60))]
    key = lambda item: (item[0],)
    less = (lambda a, b: a[0] > b[0]) if largest_first \
        else (lambda a, b: a[0] < b[0])
    for keyed in (key, None):
        bulk = PriorityQueue(less, max_size, key=keyed,
                             largest_key_first=largest_first, items=items)
        pushed = PriorityQueue(less, max_size, key=keyed,
                               largest_key_first=largest_first)
        for item in items:
            pushed.push(item)
        assert len(bulk) == len(pushed) == (
            60 if max_size == INFINITE else 5)
        assert bulk.peek() == pushed.peek()
        # Equal keys pop in the order given, in either direction.
        want = sorted(items, key=key, reverse=largest_first)[:len(bulk)]
        if max_size == INFINITE:
            bulk.push((6, 99))
            pushed.push((6, 99))
            want = sorted(items + [(6, 99)], key=key, reverse=largest_first)
        got = [bulk.pop() for _ in range(len(bulk))]
        assert got == [pushed.pop() for _ in range(len(pushed))] == want
        assert bulk.empty() and pushed.empty()


# -- (f) the filters' contract ------------------------------------------------
POOL = "pool"


def pooled_spec() -> dict:
    """Victims of three queues on nodes of two pools, young and old, and a
    reclaimer that selects one pool; minimum runtimes on two queues."""
    rng = np.random.default_rng(23)
    spec = {"now": 1000.0, "nodes": {}, "jobs": {}, "queues": {
        "a": {"deserved": {"gpu": 1}, "reclaim_min_runtime": 100.0},
        "b": {"deserved": {"gpu": 1}, "reclaim_min_runtime": 30.0},
        "c": {"deserved": {"gpu": 1}},
        "claims": {"deserved": {"gpu": 64}}}}
    for j in range(60):
        node = f"n{j:02d}"
        spec["nodes"][node] = {"gpu": 1, "labels": {
            POOL: str(rng.choice(["red", "blue"]))}}
        spec["jobs"][f"v{j:02d}"] = {
            "queue": str(rng.choice(["a", "b", "c"])),
            "creation_ts": float(j),
            "last_start_ts": 1000.0 - float(rng.choice([10, 50, 900])),
            "tasks": [{"gpu": 1, "cpu": "1", "status": "RUNNING",
                       "node": node}]}
    spec["jobs"]["claim"] = {
        "queue": "claims", "preemptible": False,
        "tasks": [{"gpu": 1, "cpu": "1", "selector": {POOL: "blue"}}]}
    return spec


def test_every_registered_filter_judges_each_victim_alone_and_in_order():
    ssn = build_session(pooled_spec())
    claim = ssn.cluster.podgroups["claim"]
    victims = survey_reclaim_victims(ssn)
    assert len(victims) == 60
    owners = {type(fn.__self__).__name__
              for fn in ssn.reclaim_victim_filters}
    assert owners == {"MinRuntimePlugin", "UpstreamPredicatesPlugin"}
    rng = np.random.default_rng(29)
    for fn in ssn.reclaim_victim_filters + [
            lambda r, v: ssn.filter_reclaim_victims(r, v)]:
        whole = fn(claim, victims)
        assert 0 < len(whole) < len(victims)
        # In the order given: a subsequence of it.
        rest = iter(victims)
        assert all(any(pg is other for other in rest) for pg in whole)
        for _ in range(8):
            cut = int(rng.integers(0, len(victims) + 1))
            a, b = victims[:cut], victims[cut:]
            assert fn(claim, a + b) == fn(claim, a) + fn(claim, b)
        shuffled = [victims[i] for i in rng.permutation(len(victims))]
        kept = {pg.uid for pg in whole}
        assert uids(fn(claim, shuffled)) == [u for u in uids(shuffled)
                                             if u in kept]
        assert fn(claim, []) == []


def test_what_was_not_read_goes_with_the_action_not_with_the_collector(
        monkeypatch):
    """The order's nodes, entries and comparators refer to one another: an
    order abandoned with 290 victims unread would keep every entry and key
    until a full collection.  The action takes it apart when it is done,
    so the collector finds none of them."""
    import gc
    ssn = session(fleet("one-department", 19, jobs=300), "keys",
                  max_victims_considered=CAP)
    monkeypatch.setattr(reclaim, "solve_job", recording([]))
    entries = (PriorityQueue._Entry, PriorityQueue._KeyedEntry)
    gc.collect()
    gc.disable()
    try:
        run_action(ssn, "reclaim")
        alive = sum(isinstance(o, entries) for o in gc.get_objects())
        stream = VictimStream(ssn)
        stream.candidates(ssn, ssn.cluster.podgroups["claim0"])
        held = sum(isinstance(o, entries) for o in gc.get_objects())
        del stream
        left = sum(isinstance(o, entries) for o in gc.get_objects())
    finally:
        gc.enable()
    # A stream dropped without ``close`` keeps its unread entries for the
    # collector; the action's own left none.
    assert alive < 20 and held - alive > 200 and left - alive > 200
