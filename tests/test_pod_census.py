"""A PodGroup keeps its pods' status census (PR 56).

``PodGroupInfo`` answers ``num_active_used``, ``num_active_allocated``,
``is_gang_satisfied``, ``is_stale``, ``is_ready_for_scheduling`` and
``should_pipeline`` from counts it keeps beside ``queue_counts`` and that
``invalidate_caches()`` drops.  Held here:

(a) after any sequence of the moves that go through that door (a client's
    ``add_task`` / ``update_task_status`` / ``set_pod_sets``, a statement's
    allocate / pipeline / evict / rollback / conversion to pipelined, a
    ``clone``) every reader equals a plain walk of ``pg.pods`` written
    below, with one pod set and with several that have minimums of their
    own, with a SUCCEEDED pod, and after ``min_available`` was edited in
    place: what is kept is counts, never verdicts;
(b) the daemon's bulk build, which writes a PodGroup's pods without the
    door and overlays speculative statuses, holds the same equality;
(c) no line of the package writes ``.status =`` on a pod but those pinned
    below: a new direct writer fails here and not in a cell.
"""

import os
import re

import numpy as np
import pytest

from kai_scheduler_tpu.api import PodStatus
from kai_scheduler_tpu.api.pod_info import DEFAULT_SUBGROUP, PodInfo
from kai_scheduler_tpu.api.pod_status import (ACTIVE_ALLOCATED, ACTIVE_USED,
                                              ALIVE)
from kai_scheduler_tpu.api.podgroup_info import PodGroupInfo, PodSet
from kai_scheduler_tpu.api.resources import ResourceRequirements
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.framework.session import InMemoryCache, Session
from kai_scheduler_tpu.utils.metrics import METRICS
from tests.fixtures import build_cluster

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("num_active_used", "num_active_allocated", "is_gang_satisfied",
           "is_stale", "is_ready_for_scheduling", "should_pipeline")
STATUS_WRITE = re.compile(r"\.status\s*=(?!=)")


ONE_GPU = ResourceRequirements.from_spec("1", "1Gi", 1)


def mktask(uid, status=PodStatus.PENDING, **kw):
    return PodInfo(uid=uid, name=uid, status=status, res_req=ONE_GPU, **kw)


# -- the plain walk -----------------------------------------------------------
def walked(pg) -> dict:
    """Every reader's answer read off ``pg.pods``, a pod in the pod set its
    ``subgroup`` names and in the default one where none has that name."""
    pods = list(pg.pods.values())
    members = {name: [] for name in pg.pod_sets}
    for t in pods:
        members[t.subgroup if t.subgroup in members
                else DEFAULT_SUBGROUP].append(t)

    def count(tasks, statuses) -> int:
        return sum(1 for t in tasks if t.status & statuses)

    satisfied = all(count(members[n], ACTIVE_USED) >= ps.min_available
                    for n, ps in pg.pod_sets.items())
    return {
        "num_active_used": count(pods, ACTIVE_USED),
        "num_active_allocated": count(pods, ACTIVE_ALLOCATED),
        "is_gang_satisfied": satisfied,
        "is_stale": (not count(pods, PodStatus.SUCCEEDED)
                     and count(pods, ACTIVE_USED) > 0 and not satisfied),
        "is_ready_for_scheduling": all(
            count(members[n], ALIVE) >= ps.min_available
            for n, ps in pg.pod_sets.items()),
        "should_pipeline": any(
            count(members[n], PodStatus.PIPELINED) > 0
            and count(members[n], ACTIVE_ALLOCATED & ~PodStatus.PIPELINED)
            < ps.min_available for n, ps in pg.pod_sets.items()),
    }


def answered(pg) -> dict:
    return {name: getattr(pg, name)() for name in READERS}


def held_to_a_walk(podgroups) -> None:
    """Asked twice: the first answer may count the pods, the second reads
    what the first kept."""
    for pg in podgroups:
        want = walked(pg)
        assert answered(pg) == want, pg.uid
        assert pg.uncounted_pods() == 0
        assert answered(pg) == want, pg.uid


# -- (a) the moves ------------------------------------------------------------
SHAPES = {
    "one-pod-set": [("default", 2)],
    "three-pod-sets": [("master", 1), ("worker", 2), ("ps", 0)],
}
LIVE = (PodStatus.PENDING, PodStatus.GATED, PodStatus.FAILED,
        PodStatus.SUCCEEDED, PodStatus.UNKNOWN)


class Moves:
    """Seeded moves on one persistent cluster of PodGroups of one shape."""

    def __init__(self, seed: int, shape: str):
        self.rng = np.random.default_rng(seed)
        self.sets = SHAPES[shape]
        spec = {"nodes": {f"n{i}": {"gpu": 64} for i in range(4)},
                "queues": {"q": {}}, "jobs": {}}
        self.cluster = build_cluster(spec)
        self.seq = 0
        for _ in range(4):
            self.arrive()
        self.ssn = Session(self.cluster, SchedulerConfig(), InMemoryCache())
        self.stmt = self.ssn.statement()

    def pick(self, items):
        items = list(items)
        return items[int(self.rng.integers(len(items)))] if items else None

    def pod(self, status=PodStatus.PENDING, node=""):
        self.seq += 1
        # One pod in eight names a pod set the PodGroup does not have: it
        # is the default one's, which ``_index_task`` makes where missing.
        subgroup = "nowhere" if self.seq % 8 == 0 else self.pick(
            name for name, _ in self.sets)
        return mktask(f"p{self.seq}", status, node_name=node,
                      subgroup=subgroup)

    def pods(self, *statuses):
        return [(pg, t) for pg in self.cluster.podgroups.values()
                for t in pg.pods.values() if t.status in statuses]

    # -- a client's ---------------------------------------------------------
    def arrive(self):
        self.seq += 1
        pg = PodGroupInfo(f"pg{self.seq}", f"pg{self.seq}", queue_id="q",
                          min_available=self.sets[0][1])
        if len(self.sets) > 1:
            pg.set_pod_sets(PodSet(n, m) for n, m in self.sets)
        for _ in range(int(self.rng.integers(2, 8))):
            if self.rng.integers(2):
                task = self.pod(PodStatus.RUNNING,
                                self.pick(self.cluster.nodes))
                self.cluster.nodes[task.node_name].add_task(task)
            else:
                task = self.pod()
            pg.add_task(task)
        self.cluster.podgroups[pg.uid] = pg

    def one_more_pod(self):
        self.pick(self.cluster.podgroups.values()).add_task(self.pod())

    def change_status(self):
        found = self.pick(self.pods(*LIVE))
        if found:
            found[0].update_task_status(found[1], self.pick(LIVE))

    def succeed(self):
        found = self.pick(self.pods(PodStatus.PENDING, PodStatus.FAILED))
        if found:
            found[0].update_task_status(found[1], PodStatus.SUCCEEDED)

    def regroup(self):
        """``set_pod_sets``: the same pods in new pod sets with other
        minimums, one name dropped now and then."""
        pg = self.pick(self.cluster.podgroups.values())
        sets = [PodSet(n, int(self.rng.integers(0, 3))) for n, _ in self.sets]
        if len(sets) > 1 and self.rng.integers(2):
            sets.pop()
        pg.set_pod_sets(sets)

    def edit_minimum(self):
        """In place, behind the door's back: nothing is dropped, and the
        next answer is the new minimum's."""
        pg = self.pick(self.cluster.podgroups.values())
        ps = self.pick(pg.pod_sets.values())
        ps.min_available = int(self.rng.integers(0, 5))

    # -- a statement's ------------------------------------------------------
    def allocate(self):
        found = self.pick(self.pods(PodStatus.PENDING))
        if found:
            self.stmt.allocate(found[1], self.pick(self.cluster.nodes))

    def pipeline(self):
        found = self.pick(self.pods(PodStatus.PENDING))
        if found:
            self.stmt.pipeline(found[1], self.pick(self.cluster.nodes))

    def evict(self):
        found = self.pick(self.pods(PodStatus.RUNNING, PodStatus.ALLOCATED))
        if found and found[1].node_name:
            self.stmt.evict(found[1])

    def rollback(self):
        self.stmt.rollback(int(self.rng.integers(len(self.stmt.ops) + 1)))

    def convert(self):
        found = self.pick(self.pods(PodStatus.ALLOCATED))
        if found:
            self.stmt.convert_all_allocated_to_pipelined(found[0].uid)

    def stands(self):
        """The statement is left standing and the next one starts."""
        self.stmt = self.ssn.statement()

    MOVES = ("arrive", "one_more_pod", "change_status", "succeed",
             "regroup", "edit_minimum", "allocate", "allocate", "pipeline",
             "evict", "evict", "rollback", "convert", "stands")


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("seed", range(8))
def test_every_reader_equals_a_walk_after_any_moves(seed, shape):
    moves = Moves(seed, shape)
    podgroups = moves.cluster.podgroups
    held_to_a_walk(podgroups.values())
    seen = set()
    for _ in range(120):
        move = moves.pick(Moves.MOVES)
        getattr(moves, move)()
        held_to_a_walk(podgroups.values())
        seen.update(v for pg in podgroups.values()
                    for v in answered(pg).items())
    # The sequence is long enough to show every verdict both ways.
    for name in READERS[2:]:
        assert {(name, True), (name, False)} <= seen, name
    # A clone has nothing kept, answers the same and leaves the original
    # as it was.
    for pg in podgroups.values():
        twin = pg.clone()
        assert twin._census is None and twin.uncounted_pods() == len(pg.pods)
        assert answered(twin) == walked(twin) == walked(pg)
        task = next(iter(twin.pods.values()))
        twin.update_task_status(task, PodStatus.SUCCEEDED)
        assert twin._census is None and pg._census is not None
    held_to_a_walk(podgroups.values())


@pytest.mark.parametrize("reader", READERS)
def test_the_door_drops_the_census_and_an_edited_minimum_is_read_live(reader):
    pg = PodGroupInfo("pg", "pg", min_available=2)
    tasks = [mktask(f"t{k}", status=s) for k, s in enumerate(
        (PodStatus.RUNNING, PodStatus.PIPELINED, PodStatus.PENDING))]
    for task in tasks:
        pg.add_task(task)
    assert pg._census is None and pg.uncounted_pods() == 3
    first = getattr(pg, reader)()
    assert first == walked(pg)[reader]
    kept = pg._census
    assert kept == (2, 2, 0, ((2, 2, 3, 1),)) and pg.uncounted_pods() == 0
    # Whoever asked, both kept things were filled by the one walk.
    assert pg._queue_counts is not None
    getattr(pg, reader)()
    assert pg._census is kept
    # Counts, not verdicts: the minimum is read at every question.
    pg.pod_sets[DEFAULT_SUBGROUP].min_available = 3
    assert pg._census is kept
    assert getattr(pg, reader)() == walked(pg)[reader]
    assert (pg.is_gang_satisfied(), pg.is_stale(), pg.should_pipeline(),
            pg.is_ready_for_scheduling()) == (False, True, True, True)
    pg.pod_sets[DEFAULT_SUBGROUP].min_available = 1
    assert (pg.is_gang_satisfied(), pg.is_stale(), pg.should_pipeline(),
            pg.is_ready_for_scheduling()) == (True, False, False, True)
    # Each way through the door drops it.
    for through in (
            lambda: pg.update_task_status(tasks[2], PodStatus.SUCCEEDED),
            lambda: pg.add_task(mktask("t9", status=PodStatus.BOUND)),
            lambda: pg.set_pod_sets([PodSet("other", 1)]),
            pg.invalidate_caches):
        getattr(pg, reader)()
        assert pg._census is not None
        through()
        assert pg._census is None and pg._queue_counts is None
        assert getattr(pg, reader)() == walked(pg)[reader]


def test_one_walk_fills_both_kept_things_and_only_the_asker_counts_it():
    """``queue_aggregate_pod_visits_total`` moves where ``queue_counts()``
    found nothing kept, and not where a reader of the census had counted
    the PodGroup before it."""
    name = "queue_aggregate_pod_visits_total"
    pg = PodGroupInfo("pg", "pg")
    for k in range(5):
        pg.add_task(mktask(f"t{k}", status=PodStatus.RUNNING))
    before = METRICS.counters.get(name, 0)
    pg.queue_counts()
    assert METRICS.counters[name] - before == 5
    assert pg._census == (5, 5, 0, ((5, 5, 5, 0),))
    pg.invalidate_caches()
    assert pg.num_active_used() == 5
    assert pg.queue_counts() == (ONE_GPU, 5, 0)
    assert METRICS.counters[name] - before == 5


# -- (b) the daemon's bulk build ----------------------------------------------
@pytest.mark.parametrize("columnar", [True, False], ids=["columnar", "objects"])
def test_a_podgroup_built_without_the_door_counts_itself(columnar,
                                                         monkeypatch):
    """``ClusterCache.snapshot`` writes ``pg.pods``, ``ps.pods`` and the
    pending count by hand and lays the speculative overlay over
    ``task.status``, all before any reader: the census is None until then
    and the first question counts what stands."""
    from kai_scheduler_tpu.controllers import InMemoryKubeAPI
    from kai_scheduler_tpu.controllers.cache_builder import ClusterCache
    from test_incremental_cache import seed_cluster
    monkeypatch.setenv("KAI_COLUMNAR", "1" if columnar else "0")
    api = InMemoryKubeAPI()
    seed_cluster(api)
    cache = ClusterCache(api)
    cache.snapshot()
    def uid_of(pod):
        return pod["metadata"].get("uid", pod["metadata"]["name"])

    pods = api.list("Pod")
    pending = next(p for p in pods if not p["spec"].get("nodeName"))
    entries = [(uid_of(pending), "bind", "n0")]
    entries += [(uid_of(p), "evict", "") for p in pods
                if p["spec"].get("nodeName")][:1]
    cache.speculate(entries)
    cluster = cache.snapshot()
    if columnar:
        assert cache.last_columnar_stats["path"] == "columnar"
    assert cluster.cache_stats["speculative_overlaid"] >= 1
    overlaid = [t for pg in cluster.podgroups.values()
                for t in pg.pods.values() if t.uid == uid_of(pending)]
    assert [t.status for t in overlaid] == [PodStatus.BOUND]
    assert all(pg._census is None for pg in cluster.podgroups.values())
    held_to_a_walk(cluster.podgroups.values())
    assert sum(pg.num_active_used() for pg in cluster.podgroups.values()) > 0


# -- (c) who writes a pod's status --------------------------------------------
# file -> the lines that write ``.status =`` on a pod.  ``update_task_status``
# is the door; a statement writes directly only where the task has no
# PodGroup in the cluster; the daemon's snapshot builders write before the
# PodGroup has been asked anything (a fresh ``PodGroupInfo`` a snapshot).
STATUS_WRITERS = {
    "api/podgroup_info.py": ["task.status = status"],
    "framework/statement.py": [
        "task.status = status", "task.status = status",
        "task.status = PodStatus.RELEASING",
        "task.status = op.prev_status", "task.status = op.prev_status",
        "task.status = op.prev_status",
        "op.task.status = PodStatus.PIPELINED",
        "op.task.status = PodStatus.PIPELINED"],
    "controllers/cache_builder.py": [
        "task.status = PodStatus(int(status[i]))",
        "task.status = PodStatus.BOUND",
        "task.status = PodStatus.RELEASING"],
}
# Not pods: the columnar store's status column and a span's outcome.
NOT_PODS = {"framework/columnar.py": 1, "utils/tracing.py": 2}


def test_the_sites_that_write_a_status_directly_are_these():
    package = os.path.join(ROOT, "kai_scheduler_tpu")
    found: dict = {}
    for folder, _dirs, files in os.walk(package):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as fh:
                lines = [ln.strip() for ln in fh if STATUS_WRITE.search(ln)]
            if lines:
                found[os.path.relpath(path, package)] = lines
    others = {f: len(lines) for f, lines in found.items()
              if f not in STATUS_WRITERS}
    assert others == NOT_PODS
    assert {f: found.get(f) for f in STATUS_WRITERS} == STATUS_WRITERS


def test_a_statement_writes_directly_only_where_the_task_has_no_podgroup():
    """Every direct write of ``framework/statement.py`` is the ``else`` of
    ``if job is not None: job.update_task_status(...)``."""
    with open(os.path.join(ROOT, "kai_scheduler_tpu", "framework",
                           "statement.py")) as fh:
        lines = [ln.strip() for ln in fh]
    sites = [i for i, ln in enumerate(lines) if STATUS_WRITE.search(ln)]
    assert len(sites) == len(STATUS_WRITERS["framework/statement.py"])
    for i in sites:
        assert lines[i - 1] == "else:", lines[i]
        above = " ".join(lines[i - 4:i - 1])
        assert "if job is not None:" in above \
            and "job.update_task_status(" in above, lines[i]
