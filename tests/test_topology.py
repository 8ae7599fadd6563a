"""Topology-aware scheduling tests — analog of the reference's
test/e2e/.../topology suites and plugins/topology unit tests."""

import numpy as np
import pytest

from kai_scheduler_tpu.ops.topology import ROOT_LEVEL, build_tree
from tests.fixtures import build_session, placements, run_action


def rack_zone_cluster(gpus_free=None):
    """4 nodes in 2 zones x 2 racks; gpus_free overrides idle GPUs by
    pre-placing running pods."""
    nodes = {}
    for i in range(4):
        zone = f"z{i // 2}"
        rack = f"r{i}"  # one rack per node here; rack within zone
        nodes[f"n{i}"] = {"gpu": 8, "labels": {"zone": zone, "rack": rack}}
    spec = {
        "nodes": nodes,
        "queues": {"default": {}},
        "topologies": {"topo": {"levels": ["zone", "rack"]}},
        "jobs": {},
    }
    if gpus_free:
        for i, free in enumerate(gpus_free):
            used = 8 - free
            if used > 0:
                spec["jobs"][f"filler{i}"] = {
                    "tasks": [{"gpu": used, "status": "RUNNING",
                               "node": f"n{i}"}]}
    return spec


class TestBuildTree:
    def test_domains(self):
        labels = {"n0": {"zone": "z0", "rack": "r0"},
                  "n1": {"zone": "z0", "rack": "r1"},
                  "n2": {"zone": "z1", "rack": "r0"},
                  "n3": {}}
        tree = build_tree("t", ["zone", "rack"], ["n0", "n1", "n2", "n3"],
                          labels)
        assert tree.num_domains("zone") == 2
        # rack domains are per-zone paths: z0/r0, z0/r1, z1/r0.
        assert tree.num_domains("rack") == 3
        assert tree.node_domain["zone"].tolist()[:3] == [0, 0, 1]
        assert tree.node_domain["rack"][3] == -1  # unlabeled node excluded
        assert tree.node_domain[ROOT_LEVEL].tolist() == [0, 0, 0, 0]


class TestRequiredLevel:
    def test_gang_confined_to_zone(self):
        spec = rack_zone_cluster()
        spec["jobs"]["gang"] = {
            "min_available": 2, "topology": "topo",
            "required_topology_level": "zone",
            "tasks": [{"gpu": 8}, {"gpu": 8}],
        }
        ssn = build_session(spec)
        run_action(ssn)
        p = placements(ssn)
        zones = {ssn.cluster.nodes[p[f"gang-{i}"][0]].labels["zone"]
                 for i in range(2)}
        assert len(zones) == 1  # whole gang in one zone

    def test_no_zone_fits_fails(self):
        # Each zone has only 8 free GPUs; gang needs 16 in one zone.
        spec = rack_zone_cluster(gpus_free=[8, 0, 8, 0])
        spec["jobs"]["gang"] = {
            "min_available": 2, "topology": "topo",
            "required_topology_level": "zone",
            "tasks": [{"gpu": 8}, {"gpu": 8}],
        }
        ssn = build_session(spec)
        run_action(ssn)
        assert all(not uid.startswith("gang")
                   for uid in placements(ssn))
        assert any("topology" in e for e in
                   ssn.cluster.podgroups["gang"].fit_errors)

    def test_without_constraint_gang_spans_zones(self):
        spec = rack_zone_cluster(gpus_free=[8, 0, 8, 0])
        spec["jobs"]["gang"] = {
            "min_available": 2,
            "tasks": [{"gpu": 8}, {"gpu": 8}],
        }
        ssn = build_session(spec)
        run_action(ssn)
        assert len([u for u in placements(ssn) if u.startswith("gang")]) == 2


class TestPreferredLevel:
    def test_prefers_tightest_fitting_rack(self):
        # rack n1 has exactly 4 free (tight fit); n0 has 8.
        spec = rack_zone_cluster(gpus_free=[8, 4, 8, 8])
        spec["jobs"]["j"] = {
            "topology": "topo",
            "preferred_topology_level": "rack",
            "tasks": [{"gpu": 4}],
        }
        ssn = build_session(spec)
        run_action(ssn)
        assert placements(ssn)["j-0"][0] == "n1"  # packed into tight rack

    def test_preferred_falls_back_to_coarser_level(self):
        # No single rack fits the 2x8 gang, but zone z0 does.
        spec = rack_zone_cluster()
        spec["jobs"]["gang"] = {
            "min_available": 2, "topology": "topo",
            "preferred_topology_level": "rack",
            "tasks": [{"gpu": 8}, {"gpu": 8}],
        }
        ssn = build_session(spec)
        run_action(ssn)
        p = placements(ssn)
        assert len([u for u in p if u.startswith("gang")]) == 2


class TestPinnedDomains:
    def test_running_pods_pin_required_domain(self):
        # Job has a running pod in z1; required=zone forces new pods there.
        spec = rack_zone_cluster()
        spec["jobs"]["grow"] = {
            "min_available": 1, "topology": "topo",
            "required_topology_level": "zone",
            "tasks": [{"gpu": 2, "status": "RUNNING", "node": "n2"},
                      {"gpu": 2}],
        }
        ssn = build_session(spec)
        run_action(ssn)
        p = placements(ssn)
        node = p["grow-1"][0]
        assert ssn.cluster.nodes[node].labels["zone"] == "z1"


class TestSubgroupConstraints:
    def test_cliques_pin_to_separate_racks(self):
        """Grove-style gang: each clique confined to its own rack, both
        cliques must land (per-subgroup SubsetNodes recursion)."""
        spec = rack_zone_cluster()
        spec["jobs"]["dynamo"] = {
            "topology": "topo",
            "pod_sets": [
                {"name": "prefill", "min_available": 2,
                 "required_topology_level": "rack"},
                {"name": "decode", "min_available": 2,
                 "required_topology_level": "rack"},
            ],
            "tasks": ([{"gpu": 4, "subgroup": "prefill"}] * 2
                      + [{"gpu": 4, "subgroup": "decode"}] * 2),
        }
        ssn = build_session(spec)
        run_action(ssn)
        p = placements(ssn)
        assert len(p) == 4
        prefill_nodes = {p[f"dynamo-{i}"][0] for i in range(2)}
        decode_nodes = {p[f"dynamo-{i}"][0] for i in range(2, 4)}
        # Each clique within ONE rack (here: one node per rack).
        assert len(prefill_nodes) == 1 and len(decode_nodes) == 1

    def test_subgroup_constraint_failure_rolls_back_whole_gang(self):
        # decode needs a rack with 8 free GPUs; none has after prefill
        # takes its rack -> entire job must not place.
        spec = rack_zone_cluster(gpus_free=[8, 4, 4, 4])
        spec["jobs"]["dynamo"] = {
            "topology": "topo",
            "pod_sets": [
                {"name": "prefill", "min_available": 1,
                 "required_topology_level": "rack"},
                {"name": "decode", "min_available": 2,
                 "required_topology_level": "rack"},
            ],
            "tasks": ([{"gpu": 8, "subgroup": "prefill"}]
                      + [{"gpu": 4, "subgroup": "decode"}] * 2),
        }
        ssn = build_session(spec)
        run_action(ssn)
        assert all(not u.startswith("dynamo") for u in placements(ssn))


# ---------------------------------------------------------------------------
# The trees outlive the session (ops/topology.py ``session_trees``)
# ---------------------------------------------------------------------------
#
# A ``Scheduler``'s arena says which node rows its pack patched; where it
# patched, the topology plugin takes over the trees of the session before
# once it has read the patched rows' labels again.  Whatever the arena
# cannot prove (a full pack of any reason), a changed ``cluster.topologies``
# and a session with no arena build them from scratch.  Reused or built,
# they equal ``build_tree`` on the same cluster, and ``subset_nodes`` gives
# the same masks and boosts.

def _topo_spec(base=None):
    import copy

    from tests.test_snapshot_delta import _bare_spec
    spec = copy.deepcopy(base) if base else _bare_spec(
        labels=lambda i: {"zone": f"z{i // 6}", "rack": f"r{i // 2}"})
    spec["topologies"] = {"topo": {"levels": ["zone", "rack"]}}
    return spec


def _topo_loop(base=None):
    from tests.test_snapshot_delta import BareLoop
    return BareLoop(_topo_spec(base))


def _arrive_gang(loop, size, **levels):
    pg = loop.arrive(size)
    pg.topology_name = "topo"
    pg.required_topology_level = levels.get("required")
    pg.preferred_topology_level = levels.get("preferred")
    return pg


def _topology_of(ssn):
    (plugin,) = [p for p in ssn.plugins if p.name == "topology"]
    return plugin._topo


def _tree_span():
    from kai_scheduler_tpu.utils.tracing import TRACER
    (span,) = [s for s in TRACER.get_trace().spans
               if s.name == "plugin:topology"]
    return span.attrs


def _tree_counts():
    from kai_scheduler_tpu.utils.metrics import METRICS
    return (METRICS.counters.get("topology_tree_reused_total", 0),
            METRICS.counters.get("topology_tree_built_total", 0))


def _scratch_trees(ssn):
    names = ssn.snapshot.node_names
    labels = {n: ssn.cluster.nodes[n].labels for n in names}
    return {name: build_tree(name, list(topo["levels"]), names, labels)
            for name, topo in ssn.cluster.topologies.items()}


def _assert_trees_equal_scratch(ssn):
    have, want = _topology_of(ssn).trees, _scratch_trees(ssn)
    assert list(have) == list(want)
    for name, tree in want.items():
        got = have[name]
        assert got.levels == tree.levels
        assert list(got.node_domain) == list(tree.node_domain)
        for level, seg in tree.node_domain.items():
            assert got.node_domain[level].dtype == seg.dtype
            assert got.node_domain[level].tolist() == seg.tolist(), level
            assert got.domain_names[level] == tree.domain_names[level]


def _assert_subsets_equal_scratch(ssn, size, **levels):
    """``subset_nodes`` of a probe gang on the session's trees and on
    trees built from scratch: the same masks, the same boosts."""
    from kai_scheduler_tpu.api import PodGroupInfo, PodInfo
    from kai_scheduler_tpu.api.resources import ResourceRequirements
    probe = PodGroupInfo("probe", "probe", queue_id="q1",
                         min_available=size, topology_name="topo",
                         required_topology_level=levels.get("required"),
                         preferred_topology_level=levels.get("preferred"))
    for k in range(size):
        probe.add_task(PodInfo(
            uid=f"probe-{k}", name=f"probe-{k}",
            res_req=ResourceRequirements.from_spec("1", "1Gi", 1)))
    tasks = list(probe.pods.values())
    topo = _topology_of(ssn)
    kept = topo.trees
    got = topo.subset_nodes(probe, tasks)
    got_boosts = topo._job_node_scores.get("probe")
    try:
        topo.trees = _scratch_trees(ssn)
        want = topo.subset_nodes(probe, tasks)
        want_boosts = topo._job_node_scores.get("probe")
    finally:
        topo.trees = kept
        topo._job_node_scores.pop("probe", None)
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.tolist() == b.tolist()
    assert (got_boosts is None) == (want_boosts is None)
    if want_boosts is not None:
        assert got_boosts.tobytes() == want_boosts.tobytes()


@pytest.mark.parametrize("levels", [
    {"preferred": "rack"}, {"required": "zone"},
    {"required": "zone", "preferred": "rack"}],
    ids=["preferred", "required", "both"])
def test_reused_trees_equal_trees_from_scratch(levels):
    loop = _topo_loop()
    reused0, built0 = _tree_counts()
    live = []
    for k in range(5):
        if len(live) >= 2:
            loop.complete(live.pop(0))
        pg = _arrive_gang(loop, 3, **levels)
        ssn = loop.cycle()
        assert all(t.node_name for t in pg.pods.values())
        live.append(pg)
        attrs = _tree_span()
        if k == 0:
            assert attrs["tree"] == "built" and "rows_checked" not in attrs
        else:
            assert not ssn.pack_stats["full_rebuild"]
            assert attrs["tree"] == "reused"
            assert attrs["rows_checked"] \
                == ssn.pack_stats["changed_rows"] > 0
            assert _topology_of(ssn).trees \
                is _topology_of(loop.sessions[-2]).trees
        _assert_trees_equal_scratch(ssn)
        _assert_subsets_equal_scratch(ssn, 2, **levels)
    reused, built = _tree_counts()
    assert (reused - reused0, built - built0) == (4, 1)


def _replace_node(loop, name, labels):
    """A new ``NodeInfo`` of the same name and hardware in the old one's
    place: to the arena a patched row (new stamp, equal allocatable)."""
    from kai_scheduler_tpu.api import NodeInfo
    old = loop.cluster.nodes[name]
    assert not old.pod_infos
    loop.cluster.nodes[name] = NodeInfo(
        name, old.allocatable.copy(), labels=labels, taints=set(old.taints),
        gpu_memory_per_device=old.gpu_memory_per_device,
        max_pods=old.max_pods, idx=old.idx)
    loop.touched.add(name)


@pytest.mark.parametrize("labels, tree", [
    ({"zone": "z0", "rack": "r5"}, "built"),       # another rack
    ({"zone": "z1", "rack": "r2"}, "built"),       # same rack, other zone
    ({"zone": "z0", "rack": "r-new"}, "built"),    # a rack nobody had
    ({"zone": "z0"}, "built"),                     # the rack label gone
    ({}, "built"),                                 # no label of the chain
    ({"zone": "z0", "rack": "r2", "tier": "gold"}, "reused"),
], ids=["rack_moved", "zone_moved", "new_rack", "rack_dropped",
        "unlabelled", "same_levels"])
def test_replaced_node_on_a_patched_row_is_read_again(labels, tree):
    loop = _topo_loop()
    _arrive_gang(loop, 2, preferred="rack")
    loop.cycle()
    _arrive_gang(loop, 2, preferred="rack")
    loop.cycle()
    assert _tree_span()["tree"] == "reused"
    assert loop.cluster.nodes["n05"].labels == {"zone": "z0", "rack": "r2"}
    _replace_node(loop, "n05", labels)
    reused0, built0 = _tree_counts()
    ssn = loop.cycle()
    assert not ssn.pack_stats["full_rebuild"], ssn.pack_stats
    assert _tree_span()["tree"] == tree
    assert _tree_counts() == (reused0 + (tree == "reused"),
                              built0 + (tree == "built"))
    _assert_trees_equal_scratch(ssn)
    _assert_subsets_equal_scratch(ssn, 2, preferred="rack")
    # What was built is kept: the next patched session takes it over.
    _arrive_gang(loop, 1, preferred="rack")
    ssn = loop.cycle()
    assert _tree_span()["tree"] == "reused"
    _assert_trees_equal_scratch(ssn)


def _full_pack_cases():
    from tests.test_snapshot_delta import REBUILD_CASES
    return REBUILD_CASES


@pytest.mark.parametrize("case", sorted(_full_pack_cases()))
def test_full_pack_builds_the_trees_from_scratch(case):
    change, reason, base = _full_pack_cases()[case]
    loop = _topo_loop(base)
    _arrive_gang(loop, 3, preferred="rack")
    loop.cycle()
    _arrive_gang(loop, 2, preferred="rack")
    loop.cycle()
    assert _tree_span()["tree"] == "reused"
    change(loop)
    reused0, built0 = _tree_counts()
    ssn = loop.cycle()
    assert ssn.pack_stats["reason"] == reason
    assert _tree_span() == {"plugin": "topology", "tree": "built"}
    assert _tree_counts() == (reused0, built0 + 1)
    _assert_trees_equal_scratch(ssn)
    loop.cycle()
    _arrive_gang(loop, 2, preferred="rack")
    ssn = loop.cycle()
    assert not ssn.pack_stats["full_rebuild"]
    assert _tree_span()["tree"] == "reused"
    _assert_trees_equal_scratch(ssn)
    _assert_subsets_equal_scratch(ssn, 2, preferred="rack")


@pytest.mark.parametrize("change", [
    lambda t: t["topo"].__setitem__("levels", ["zone"]),
    lambda t: t["topo"].__setitem__("levels", ["rack", "zone"]),
    lambda t: t.__setitem__("other", {"levels": ["rack"]}),
    lambda t: (t.__setitem__("first", {"levels": ["zone"]}),
               t.__setitem__("topo", t.pop("topo"))),
], ids=["level_dropped", "levels_swapped", "topology_added",
        "default_tree_changed"])
def test_changed_topologies_build_the_trees_from_scratch(change):
    loop = _topo_loop()
    _arrive_gang(loop, 2, preferred="rack")
    loop.cycle()
    loop.arrive(2)
    loop.cycle()
    assert _tree_span()["tree"] == "reused"
    change(loop.cluster.topologies)
    loop.arrive(2)
    ssn = loop.cycle()
    assert not ssn.pack_stats["full_rebuild"]    # the arena saw nothing
    assert _tree_span()["tree"] == "built"
    _assert_trees_equal_scratch(ssn)
    assert list(_topology_of(ssn).trees) == list(loop.cluster.topologies)
    loop.arrive(1)
    ssn = loop.cycle()
    assert _tree_span()["tree"] == "reused"
    _assert_trees_equal_scratch(ssn)


def test_session_without_arena_builds_from_scratch():
    spec = _topo_spec()
    reused0, built0 = _tree_counts()
    first = build_session(spec)
    second = build_session(spec)
    assert _tree_counts() == (reused0, built0 + 2)
    for ssn in (first, second):
        assert ssn.patched_rows is None
        assert _topology_of(ssn).rows_checked is None
        _assert_trees_equal_scratch(ssn)
    assert first.products is not second.products
    assert _topology_of(first).trees is not _topology_of(second).trees


def test_shared_trees_are_read_only():
    loop = _topo_loop()
    _arrive_gang(loop, 2, preferred="rack")
    ssn = loop.cycle()
    for tree in _topology_of(ssn).trees.values():
        for seg in tree.node_domain.values():
            with pytest.raises(ValueError):
                seg[0] = 7


def test_cluster_arena_carries_the_trees_too():
    """The daemon's path: ``ClusterArena`` offers the same two things, and
    a Node whose labels moved is a full pack there (``node-change``)."""
    from kai_scheduler_tpu.controllers import InMemoryKubeAPI
    from kai_scheduler_tpu.controllers.cache_builder import ClusterCache
    from kai_scheduler_tpu.framework.conf import SchedulerConfig
    from kai_scheduler_tpu.framework.session import Session
    from tests.test_snapshot_delta import _group, _node, _pod
    api = InMemoryKubeAPI()
    for i in range(8):
        _node(api, f"n{i}", labels={"zone": f"z{i // 4}", "rack": f"r{i // 2}"})
    api.create({"kind": "Queue", "metadata": {"name": "q0"}, "spec": {}})
    api.create({"kind": "Topology", "metadata": {"name": "topo"},
                "spec": {"levels": [{"nodeLabel": "zone"},
                                    {"nodeLabel": "rack"}]}})
    _group(api, "pg0", min_member=1)
    _pod(api, "p0", "pg0", gpu=1)
    cache = ClusterCache(api)

    def open_session():
        ssn = Session(cache.snapshot(), SchedulerConfig(), cache).open()
        _assert_trees_equal_scratch(ssn)
        return ssn, _topology_of(ssn)

    ssn, first = open_session()
    assert ssn.pack_stats["full_rebuild"] and first.rows_checked is None
    api.patch("Pod", "p0", {"spec": {"nodeName": "n3"},
                            "status": {"phase": "Running"}})
    ssn, second = open_session()
    assert not ssn.pack_stats["full_rebuild"], ssn.pack_stats
    assert second.rows_checked == ssn.pack_stats["changed_rows"] == 1
    assert second.trees is first.trees
    api.patch("Node", "n3", {"metadata": {"labels": {"rack": "r3"}}})
    ssn, third = open_session()
    assert ssn.pack_stats["reason"] == "node-change"
    assert third.rows_checked is None and third.trees is not first.trees
    assert third.trees["topo"].node_domain["rack"].tolist() \
        != first.trees["topo"].node_domain["rack"].tolist()
    ssn, fourth = open_session()
    assert fourth.rows_checked == 0 and fourth.trees is third.trees
